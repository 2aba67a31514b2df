"""Score entries the flash kernels' static plans compute under the sliding
window, forward and backward together, over the entries the window leaves
live (`W (2 T - W + 1) / 2` a head and sequence, once each way): 1.0 is a
plan that computes nothing dead. Static, from the program's own plan at the
cell's shape (`obs/attribution.flash_tile_stats` with the window layers'
mask; the runner's `measured.window_flash_plan`): a sub-tile the diagonal or
the window's left edge crosses is computed whole and masked, a tile wholly
left of the band is never computed. Nothing where the runner hands no such
plan."""


def read(m):
    plan = getattr(m, "window_flash_plan", None)
    if not plan:
        return None
    work = sum(p["work_elems"] for p in plan.values())
    live = sum(p["ideal_elems"] for p in plan.values())
    return work / live
