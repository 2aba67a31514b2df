"""Fleet collector: tail every process's metrics jsonl chain, fold the
streams into per-process state, and emit versioned `fleet_rollup` events
(ISSUE 12).

The per-process exporters (obs/telemetry.py) answer "how is THIS proc
doing"; the fleet questions — cross-replica SLO attainment, which rank
is the straggler, how many KV pages the fleet has left — need one reader
over every proc's stream. This module is that reader, built for the two
ways a stream can be consumed:

* **live tail**: `JsonlTailer.poll()` reads whatever bytes the producer
  has flushed so far. A torn trailing line (the producer mid-flush, or
  a hard kill) is HELD as the pending tail and resynced on the next
  poll — never dropped, never double-counted (the satellite's exact
  contract, pinned in tests/test_telemetry.py). Records that parse but
  fail `obs/schema.validate_record` are counted invalid and excluded
  from rollups instead of poisoning them.
* **rotation chain**: a `rotated` continuation event (MetricsWriter
  size-based rotation) switches the tailer to the named next file, so a
  bounded-growth serving run reads as one stream.

`FleetCollector` folds the records by tag (telemetry_snapshot /
serving_summary / paged_kv_stats / rank_phase_stats / goodput_summary)
and computes the rollup: fleet tokens/s, aggregate pool utilization,
completion-weighted cross-proc SLO attainment
(telemetry.fleet_slo_attainment), and ONLINE rank skew through the same
`obs/attribution.rank_skew` the post-hoc summary uses. Rollups append to
`fleet_rollup.jsonl` (its own file — the collector must never write into
a producer's metrics.jsonl) and render live in `scripts/obs_top.py`.

Deliberately jax-free: importable from a standalone script on a box
where jax is broken (the graftcheck layer-1 precedent); `rank_skew` is
a lazy import because obs/attribution is pure host math but lives in
the package namespace.
"""

from __future__ import annotations

import glob
import json
import os
import re
import threading
import time
from typing import Callable, Dict, List, Optional

from .schema import EVENT_SCHEMA_VERSION, validate_record
from .telemetry import fleet_slo_attainment

# rotated generations (metrics.001.jsonl, metrics.proc2.003.jsonl) are
# reached by FOLLOWING the chain from the base file, never discovered
# directly — double-tailing a generation would double-count its records
_ROTATED_GEN = re.compile(r"\.\d{3}\.jsonl$")


class JsonlTailer:
    """Incremental reader of one metrics jsonl chain (base file plus any
    `rotated` continuations). Not thread-safe; one collector thread owns
    each tailer."""

    def __init__(self, path: str):
        self.path = path
        self._f = None
        self._buf = ""      # the held partial tail (torn-line resync)
        self._visited = {os.path.realpath(path)}  # rotation-cycle guard
        self.records = 0    # complete, schema-valid records yielded
        self.invalid = 0    # parse failures / schema-invalid records
        self.torn_holds = 0  # polls that ended holding a partial tail
        self.rotations = 0  # `rotated` continuations followed

    def poll(self) -> List[dict]:
        """Every complete record flushed since the last poll, following
        rotation hops in the same call. A trailing partial line stays in
        the hold buffer until a later flush completes it."""
        out: List[dict] = []
        while True:
            if self._f is None:
                if not os.path.exists(self.path):
                    return out
                self._f = open(self.path, errors="replace")
            chunk = self._f.read()
            if chunk:
                self._buf += chunk
            rotated_to = None
            while "\n" in self._buf:
                line, self._buf = self._buf.split("\n", 1)
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    self.invalid += 1
                    continue
                if validate_record(rec):
                    self.invalid += 1
                    continue
                self.records += 1
                if rec.get("tag") == "rotated":
                    rotated_to = rec["next"]
                    break   # the rotated event is the file's last line
                out.append(rec)
            if rotated_to is None:
                if self._buf:
                    self.torn_holds += 1
                return out
            nxt = os.path.join(os.path.dirname(self.path), rotated_to)
            if os.path.realpath(nxt) in self._visited:
                # a corrupt/hand-edited chain that cycles back to a file
                # already read must not spin this poll (and re-yield its
                # records) forever — treat the cycle as drift and stop
                self.invalid += 1
                return out
            self._visited.add(os.path.realpath(nxt))
            self._f.close()
            self.path = nxt
            self._f = None
            self._buf = ""
            self.rotations += 1


# sentinel: a scrape that missed the liveness deadline (vs None, a fast
# failure) — poll() counts the two differently
_HUNG = object()


class FleetCollector:
    """Fold every proc's stream under `log_dirs` into fleet rollups.

    `endpoints`: optional `http://host:port` exporter URLs to scrape in
    addition to (or instead of) the jsonl tails — the live path for
    procs on other hosts whose filesystems this process cannot read."""

    def __init__(self, log_dirs, endpoints=None, out_path: Optional[str] = None,
                 clock: Callable[[], float] = time.monotonic,
                 wall: Callable[[], float] = time.time,
                 scrape_timeout: float = 0.5):
        if scrape_timeout <= 0:
            raise ValueError(f"scrape_timeout must be > 0, got "
                             f"{scrape_timeout}")
        self.log_dirs = [log_dirs] if isinstance(log_dirs, str) \
            else list(log_dirs)
        self.endpoints = list(endpoints or [])
        self.out_path = out_path
        self._clock = clock
        self._wall = wall
        self._t0 = clock()
        self._tailers: Dict[str, JsonlTailer] = {}
        # (source key) -> latest per-tag state this proc reported
        self.procs: Dict[str, Dict[str, dict]] = {}
        self._lock = threading.Lock()
        self.rollups = 0
        self.scrape_errors = 0
        # liveness bound per endpoint scrape (ISSUE 19): a HUNG replica —
        # accepts the connection, never answers — must not block the
        # whole collector tick. The scrape runs in a worker joined with
        # this deadline; endpoints that miss it count here (the scrape
        # -side mirror of the hbm block's `procs_unavailable`).
        self.scrape_timeout = float(scrape_timeout)
        self.procs_unresponsive = 0     # endpoints past deadline, last poll
        self.unresponsive_scrapes = 0   # cumulative across polls

    # -- discovery --------------------------------------------------------
    def discover(self) -> List[str]:
        """Base metrics files under the log dirs (recursive — train's
        per-proc `logs/procN/` layout included), excluding rotated
        generations (the chain reaches them)."""
        found = []
        for d in self.log_dirs:
            for p in sorted(glob.glob(os.path.join(d, "**",
                                                   "metrics*.jsonl"),
                                      recursive=True)):
                if _ROTATED_GEN.search(p):
                    continue
                found.append(p)
                if p not in self._tailers:
                    self._tailers[p] = JsonlTailer(p)
        return found

    # -- folding ----------------------------------------------------------
    _KEEP_TAGS = ("telemetry_snapshot", "serving_summary", "paged_kv_stats",
                  "rank_phase_stats", "goodput_summary", "hbm_watermark",
                  "tuning_decision", "controller_decision")

    def poll(self) -> int:
        """One collection pass: tail every discovered file and scrape
        every endpoint; returns the number of new records folded."""
        self.discover()
        n = 0
        for key, tailer in self._tailers.items():
            for rec in tailer.poll():
                self._fold(key, rec)
                n += 1
        unresponsive = 0
        for url in self.endpoints:
            snap = self._scrape(url)
            if snap is _HUNG:
                unresponsive += 1
            elif snap is not None:
                self._fold(url, {"tag": "telemetry_snapshot",
                                 "schema_version": EVENT_SCHEMA_VERSION,
                                 "gauges": snap.get("gauges", {}),
                                 "counters": snap.get("counters", {}),
                                 "process": snap.get("process", 0)})
                n += 1
        self.procs_unresponsive = unresponsive
        self.unresponsive_scrapes += unresponsive
        return n

    def _scrape(self, url: str):
        """One endpoint fetch under a HARD liveness deadline: the HTTP
        round trip runs in a worker thread joined with `scrape_timeout`.
        The socket-level timeout alone is not a liveness bound — a
        replica that accepts the connection and then drips (or just
        hangs inside accept/headers) can hold a blocking urlopen for the
        full socket timeout per endpoint, serially stalling every tick.
        Returns the parsed snapshot, None on a FAST failure (connection
        refused: a dead replica is a fleet fact, counted in
        scrape_errors), or _HUNG past the deadline (counted by poll as
        procs_unresponsive; the abandoned worker dies on its own socket
        timeout)."""
        import urllib.request

        box: list = []

        def fetch():
            try:
                # the socket's own timeout only ends the abandoned worker;
                # twice the deadline, so that it can never fire first and
                # turn a hung endpoint into a fast failure
                with urllib.request.urlopen(
                        url.rstrip("/") + "/metrics.json",
                        timeout=2 * self.scrape_timeout) as r:
                    box.append(json.loads(r.read()))
            except Exception:
                box.append(None)

        w = threading.Thread(target=fetch, daemon=True)
        w.start()
        w.join(self.scrape_timeout)
        if w.is_alive():
            return _HUNG
        if box and box[0] is not None:
            return box[0]
        self.scrape_errors += 1
        return None

    def _fold(self, key: str, rec: dict) -> None:
        tag = rec.get("tag")
        if tag not in self._KEEP_TAGS:
            return
        with self._lock:
            self.procs.setdefault(key, {})[tag] = rec

    # -- the rollup -------------------------------------------------------
    @staticmethod
    def _slo_counts(state: dict) -> Optional[dict]:
        """{class: (completed, hit)} from a proc's freshest source: live
        exporter counters (`slo/<class>/completed|hit`) win over the
        post-run serving_summary attainment."""
        snap = state.get("telemetry_snapshot")
        if snap is not None:
            counts = {}
            for name, v in snap.get("counters", {}).items():
                m = re.fullmatch(r"slo/(.+)/(completed|hit)", name)
                if m:
                    c = counts.setdefault(m.group(1), [0, 0])
                    c[0 if m.group(2) == "completed" else 1] = int(v)
            if counts:
                return {cls: (c, h) for cls, (c, h) in counts.items()}
        summary = state.get("serving_summary")
        if summary is not None and summary.get("slo_attainment"):
            return {cls: (d["completed"],
                          round(d["attained"] * d["completed"]))
                    for cls, d in summary["slo_attainment"].items()}
        return None

    _HBM_DEV_GAUGE = re.compile(r"hbm/d\d+/bytes_in_use")

    @classmethod
    def _hbm_counts(cls, state: dict):
        """(available, bytes_in_use, peak_bytes) from a proc's freshest
        HBM source: live exporter gauges (`hbm/...`, ISSUE 15) win over
        the last `hbm_watermark` event. None when the proc never
        published either. bytes_in_use SUMS the per-device gauges when
        present (the aggregate `hbm/bytes_in_use` gauge is the
        worst-device watermark — summing semantics must match the event
        path, or a multi-device proc undercounts in the fleet total)."""
        snap = state.get("telemetry_snapshot")
        if snap is not None:
            g = snap.get("gauges", {})
            if "hbm/available" in g:
                if not g["hbm/available"]:
                    return (False, 0, 0)
                per_dev = [int(v) for k, v in g.items()
                           if cls._HBM_DEV_GAUGE.fullmatch(k)]
                in_use = (sum(per_dev) if per_dev
                          else int(g.get("hbm/bytes_in_use", 0)))
                return (True, in_use, int(g.get("hbm/peak_bytes", 0)))
        ev = state.get("hbm_watermark")
        if ev is not None:
            if not ev.get("available"):
                return (False, 0, 0)
            devs = ev.get("devices") or []
            return (True,
                    sum(int(d.get("bytes_in_use", 0)) for d in devs),
                    max((int(d.get("peak_bytes", 0)) for d in devs),
                        default=0))
        return None

    def rollup(self) -> dict:
        """The fleet view from the latest folded state (pure read)."""
        with self._lock:
            procs = {k: dict(v) for k, v in self.procs.items()}
        tokens_per_sec = 0.0
        pages_total = pages_used = 0
        kv_utils = []
        slo_inputs = []
        skew_recs = []
        # fleet HBM (ISSUE 15): per-proc watermark -> fleet peak gauge.
        # A proc that REPORTS unavailability still counts (loudly) — the
        # silent-zero fix must survive aggregation, so 'unavailable' is a
        # fleet fact, never a 0-byte proc folded into the sum.
        hbm_in_use = hbm_peak = 0
        hbm_procs = hbm_unavailable = 0
        # control plane (ISSUE 16): per-proc mode/decision gauges + the
        # freshest folded ledger event -> fleet controller state. A proc
        # that never published ctl/mode counts as off — pre-v5 streams
        # produce no block at all (the rollup shape is unchanged)
        ctl_modes = {"advise": 0, "act": 0}
        ctl_decisions = 0
        ctl_last = None
        for state in procs.values():
            snap = state.get("telemetry_snapshot")
            if snap is not None:
                g = snap.get("gauges", {})
                m = g.get("ctl/mode")
                if m is not None and 0 <= int(m) < 3:
                    mode = ("off", "advise", "act")[int(m)]
                    if mode in ctl_modes:
                        ctl_modes[mode] += 1
                    ctl_decisions += int(g.get("ctl/decisions", 0))
            d = (state.get("controller_decision")
                 or state.get("tuning_decision"))
            if d is not None and (ctl_last is None
                                  or d.get("t", 0) >= ctl_last.get("t", 0)):
                ctl_last = d
        for state in procs.values():
            snap = state.get("telemetry_snapshot")
            if snap is not None:
                g = snap.get("gauges", {})
                tokens_per_sec += g.get("serve/tokens_per_sec",
                                        g.get("train/tokens_per_sec", 0.0))
                if "serve/num_pages" in g:
                    pages_total += int(g["serve/num_pages"])
                    pages_used += int(g.get("serve/pages_in_use", 0))
                if "serve/kv_util" in g:
                    kv_utils.append(g["serve/kv_util"])
            hbm = self._hbm_counts(state)
            if hbm is not None:
                avail, in_use, peak = hbm
                if avail:
                    hbm_procs += 1
                    hbm_in_use += in_use
                    hbm_peak = max(hbm_peak, peak)
                else:
                    hbm_unavailable += 1
            kv = state.get("paged_kv_stats")
            if kv is not None and snap is None:
                pages_total += int(kv.get("num_pages", 0))
                pages_used += int(round(kv.get("pages_in_use_mean", 0.0)))
                kv_utils.append(kv.get("kv_util_mean", 0.0))
            counts = self._slo_counts(state)
            if counts is not None:
                slo_inputs.append(counts)
            rps = state.get("rank_phase_stats")
            if rps is not None:
                skew_recs.append(rps)
        out = {
            "procs": len(procs),
            "window_s": round(self._clock() - self._t0, 3),
            "tokens_per_sec": round(tokens_per_sec, 2),
            "slo_attainment": fleet_slo_attainment(slo_inputs),
        }
        if self.endpoints:
            # scrape liveness (ISSUE 19): endpoints that missed the last
            # poll's deadline — the procs_unavailable convention, applied
            # to the scrape path
            out["procs_unresponsive"] = self.procs_unresponsive
        if pages_total:
            out["pool"] = {
                "pages_in_use": pages_used,
                "num_pages": pages_total,
                "util": round(pages_used / pages_total, 4),
                "kv_util_mean": round(sum(kv_utils) / len(kv_utils), 4)
                if kv_utils else None,
            }
        if hbm_procs or hbm_unavailable:
            out["hbm"] = {
                "bytes_in_use_total": hbm_in_use,
                "peak_bytes_max": hbm_peak,
                "procs_reporting": hbm_procs,
                "procs_unavailable": hbm_unavailable,
            }
        if any(ctl_modes.values()) or ctl_last is not None:
            out["control"] = {
                "procs": {**ctl_modes,
                          "off": len(procs) - sum(ctl_modes.values())},
                "decisions": ctl_decisions,
            }
            if ctl_last is not None:
                out["control"]["last"] = {
                    "tag": ctl_last.get("tag"),
                    "knob": ctl_last.get("knob"),
                    "old": ctl_last.get("old"),
                    "new": ctl_last.get("new"),
                    "mode": ctl_last.get("mode"),
                    "applied": ctl_last.get("applied"),
                }
        if len(skew_recs) >= 2:
            try:
                from .attribution import rank_skew
                skew = rank_skew(skew_recs)
            except ImportError:
                skew = None
            if skew is not None:
                out["rank_skew"] = {
                    "suspects": skew["suspects"][:5],
                    "persistent": skew["persistent"],
                }
        return out

    def emit(self) -> dict:
        """Roll up and append one versioned `fleet_rollup` event to
        `out_path` (no-op write when out_path is None). The collector
        owns this file alone — producer metrics files are read-only to
        it by construction."""
        rec = {"tag": "fleet_rollup", "ts": self._wall(),
               "schema_version": EVENT_SCHEMA_VERSION, **self.rollup()}
        self.rollups += 1
        if self.out_path:
            os.makedirs(os.path.dirname(self.out_path) or ".",
                        exist_ok=True)
            with open(self.out_path, "a") as f:
                f.write(json.dumps(rec) + "\n")
        return rec
