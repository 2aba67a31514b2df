"""Score entries the flash kernels' static plans compute under the
block-diffusion mask, forward and backward together, over the entries the
mask leaves live (`L (L + B)` a head and sequence, once each way): 1.0 is a
plan that computes nothing dead. Static, from the program's own plan at the
cell's shape (`obs/attribution.flash_tile_stats` with the family's mask;
the runner's `measured.flash_plan`): a sub-tile the block diagonal or a
quadrant's staircase crosses is computed whole and masked. Nothing where
the runner hands no such plan."""


def read(m):
    plan = getattr(m, "flash_plan", None)
    if not plan:
        return None
    work = sum(p["work_elems"] for p in plan.values())
    live = sum(p["ideal_elems"] for p in plan.values())
    return work / live
