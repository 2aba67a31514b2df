"""JSON persistence for the paged kernels' block-autotuner table.

`paged_attention.py` keeps a small in-memory table of tuned block shapes
((page_size, head_dim, kv_dtype, backend) -> PagedBlockConfig), persisted
as JSON (a tracked file beside this module, or wherever the table's env
var points) so one on-chip sweep serves every later run. Keys serialize
as ':'-joined parts, values as the config's tuple, unreadable/garbled
files are ignored (the table keeps its defaults), and writes publish
atomically via os.replace (the training/checkpoint.py convention).

Cache format v2 (ISSUE 16): every entry carries PROVENANCE —
`{source: sweep|online, capture, ts}` — because the control plane can
now refresh entries from a live run's own captures, and an online
retune must never silently shadow a hardware sweep. The on-disk shape
is `{"version": 2, "entries": {key: {"blocks": [...], "source": ...,
"capture": ..., "ts": ...}}}`. A v1 flat file ({key: [blocks]}) is
migrated LOUDLY on load: one stderr note, entries adopted with
`source: "sweep"` (the conservative read — pre-provenance entries came
from offline sweeps, and "sweep" is the protected class). Writing an
`online` entry over a `sweep` one refuses without `force=True`
(`--force` at the CLI surfaces).
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Callable, Dict, Optional, Tuple

CACHE_VERSION = 2

#: provenance a pre-v2 / meta-less entry adopts: offline sweeps were the
#: only writer before ISSUE 16, and "sweep" is the shadowing-protected
#: class — adopting "online" would let the next online write clobber it
DEFAULT_PROVENANCE = {"source": "sweep", "capture": None, "ts": None}


def default_cache_path(env_var: str, filename: str) -> str:
    """Where a tuned-block table lives: `env_var` when set, else a file
    beside this module that git tracks. Block shapes decide which kernel
    is compiled, so nothing outside the checkout (no `$HOME` cache) may
    supply them unasked; a sweep that should outlive its run is committed."""
    return os.environ.get(
        env_var,
        os.path.join(os.path.dirname(os.path.abspath(__file__)), filename))


def _parse_raw(raw, path: str):
    """Split a loaded JSON document into (entries, migrated): v2 wraps
    entries under {"version": 2, "entries": ...}; a v1 flat dict of
    key -> blocks-list migrates loudly (never a silent KeyError on the
    missing wrapper, never a silent adoption either)."""
    if not isinstance(raw, dict):
        raise ValueError("cache root is not a JSON object")
    if "entries" in raw or "version" in raw:
        v = raw.get("version")
        if not isinstance(v, int) or v > CACHE_VERSION:
            raise ValueError(f"cache version {v!r} is newer than this "
                             f"reader (v{CACHE_VERSION})")
        entries = raw.get("entries")
        if not isinstance(entries, dict):
            raise ValueError("cache 'entries' is not a JSON object")
        return entries, False
    # v1: flat {key: [blocks...]} — migrate, loudly
    if not raw:
        return {}, False
    print(f"block cache: migrating pre-provenance (v1) cache {path} — "
          f"{len(raw)} entr{'y' if len(raw) == 1 else 'ies'} adopted as "
          f"source=sweep (re-save rewrites it as v{CACHE_VERSION})",
          file=sys.stderr)
    return raw, True


def load_json_table(path: str, table: Dict, parse_key: Callable,
                    parse_cfg: Callable,
                    meta: Optional[Dict] = None) -> int:
    """Merge `path`'s JSON into `table`; returns entries read. `parse_key`
    maps the split ':' parts to a table key, `parse_cfg` the stored
    blocks list to a config — either raising ValueError/TypeError skips
    just that entry. Unreadable/garbled files are ignored entirely.
    `meta` (key -> provenance dict), when given, receives each entry's
    {source, capture, ts} — v1 entries and malformed provenance adopt
    DEFAULT_PROVENANCE."""
    try:
        with open(path) as f:
            raw = json.load(f)
        entries, _ = _parse_raw(raw, path)
    except (OSError, ValueError):
        return 0
    n = 0
    for key, val in entries.items():
        blocks = val.get("blocks") if isinstance(val, dict) else val
        try:
            k = parse_key(key.split(":"))
            cfg = parse_cfg(blocks)
        # IndexError: a key with too few ':' parts (the parse_key
        # lambdas index into the split) — malformed like the rest, and
        # this load runs lazily inside kernel dispatch, so one bad
        # entry must never crash a run
        except (ValueError, TypeError, IndexError):
            continue  # skip malformed entries, keep the rest
        table[k] = cfg
        if meta is not None:
            if isinstance(val, dict) and val.get("source") in ("sweep",
                                                               "online"):
                meta[k] = {"source": val["source"],
                           "capture": val.get("capture"),
                           "ts": val.get("ts")}
            else:
                meta[k] = dict(DEFAULT_PROVENANCE)
        n += 1
    return n


def save_json_table(path: str, table: Dict[Tuple, object],
                    meta: Optional[Dict] = None) -> str:
    """Write `table` (key tuple -> config with .as_tuple()) to `path`
    atomically as a v2 document; returns the path. Provenance comes
    from `meta` (key -> {source, capture, ts}); entries without one
    adopt DEFAULT_PROVENANCE."""
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    meta = meta or {}
    entries = {}
    for key, cfg in sorted(table.items()):
        prov = meta.get(key) or dict(DEFAULT_PROVENANCE)
        entries[":".join(str(p) for p in key)] = {
            "blocks": list(cfg.as_tuple()),
            "source": prov.get("source", "sweep"),
            "capture": prov.get("capture"),
            "ts": prov.get("ts"),
        }
    raw = {"version": CACHE_VERSION, "entries": entries}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(raw, f, indent=1)
    os.replace(tmp, path)  # atomic publish, like training/checkpoint.py
    return path


def write_online_entry(path: str, key: Tuple, cfg, parse_key: Callable,
                       parse_cfg: Callable, capture: Optional[str] = None,
                       force: bool = False) -> str:
    """Persist ONE online-retuned entry into the cache at `path`
    (read-modify-write against the file, not a caller's in-memory
    table, so concurrent sweeps elsewhere in the file survive).

    Refuses (ValueError) to shadow an existing `source: sweep` entry
    unless `force` — an online heuristic overruling a measured hardware
    sweep must be an explicit operator decision (--force), never a
    silent table write."""
    table: Dict = {}
    meta: Dict = {}
    load_json_table(path, table, parse_key, parse_cfg, meta=meta)
    prev = meta.get(key)
    if prev is not None and prev.get("source") == "sweep" and not force:
        raise ValueError(
            f"refusing to shadow swept block-cache entry "
            f"{':'.join(str(p) for p in key)} in {path} with an online "
            f"retune (swept entries are measured ground truth; pass "
            f"--force to overrule)")
    table[key] = cfg
    meta[key] = {"source": "online", "capture": capture,
                 "ts": int(time.time())}
    return save_json_table(path, table, meta=meta)
