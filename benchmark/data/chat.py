"""Data kind `chat`: an open loop of independent requests, from a workload
file's `data` group:

    {"kind": "chat",
     "arrivals": {"process": "poisson", "rate_rps": 12.0, "window_share": 1.0},
     "prompt_len": {"dist": "lognormal", "median": 192, "sigma": 0.9,
                    "min": 16, "max": 768},
     "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                    "min": 8, "max": 256},
     "shared_prefix": 0, "reserved_ids": 3}

`plan(spec, vocab, n_positions, seconds, seed)` gives the requests due in
`[0, span)`, where span is `seconds` times the file's `window_share`
(1 if absent): for each its due time, its prompt ids and the number of
tokens it must produce. The program gets nothing else. A cell whose engine
needs tens of seconds for a long answer offers its load in the first part
of the window, so that every request can run to its end inside the window
and its drain.

Everything is drawn from `--seed`: one schedule a seed. The number of
requests is not: `n = round(rate_rps * span)` in every run, so the rate
offered is exactly `rate_rps`. Arrivals are a Poisson process given that
count: n exponential gaps scaled to sum to the span (the gaps of n uniform
order statistics); the first request is due at 0. Lengths are log-normal
with the stated median and sigma, rounded and clipped to [min, max].
`prompt max + output max` may not pass `n_positions`. A tail of such a loop
is made by its few worst bursts, which differ from schedule to schedule:
a cell needs some hundreds of requests in its window before the 95th
percentile of two seeds agree (PERF.md section 6, PR 27).

Prompt ids are uniform over the vocabulary past `reserved_ids`;
`shared_prefix` tokens (0 here) are common to all prompts, and the first
token after them is distinct in every request of a run, so that no two
prompts share a prefix by accident (the paged engine would find it).
"""

from __future__ import annotations

from typing import List, NamedTuple

import numpy as np


class Planned(NamedTuple):
    rid: int
    due_s: float            # seconds after the window opens
    prompt: List[int]
    output_len: int         # tokens the request must produce


def draw_lengths(spec: dict, n: int, rng) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"chat: unknown length dist {spec['dist']!r}")
    raw = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], size=n))
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def draw_gaps(n: int, seconds: float, rng) -> np.ndarray:
    """n exponential gaps that sum to `seconds`; gap i lies between request
    i and i+1 (the last one runs to the end of the span)."""
    gaps = rng.exponential(1.0, size=n)
    return gaps * (seconds / gaps.sum())


def plan(spec: dict, vocab: int, n_positions: int, seconds: float,
         seed: int) -> List[Planned]:
    if spec["arrivals"]["process"] != "poisson":
        raise ValueError(
            f"chat: unknown arrival process {spec['arrivals']['process']!r}")
    seconds *= float(spec["arrivals"].get("window_share", 1.0))
    n = int(round(spec["arrivals"]["rate_rps"] * seconds))
    if n < 1:
        raise ValueError("chat: the rate sends no request in the window")
    rng = np.random.default_rng(seed)
    gaps = draw_gaps(n, seconds, rng)
    prompts = draw_lengths(spec["prompt_len"], n, rng)
    outputs = draw_lengths(spec["output_len"], n, rng)
    shared = int(spec.get("shared_prefix", 0))
    if (spec["prompt_len"]["max"] + shared + spec["output_len"]["max"]
            > n_positions):
        raise ValueError(f"chat: prompt + output can exceed {n_positions}")
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    reserved = int(spec.get("reserved_ids", 0))
    prefix = rng.integers(reserved, vocab, size=shared).tolist()
    firsts = reserved + rng.choice(vocab - reserved, size=n, replace=False)
    out = []
    for i in range(n):
        rest = rng.integers(reserved, vocab, size=int(prompts[i]) - 1)
        out.append(Planned(i, float(due[i]),
                           prefix + [int(firsts[i])] + rest.tolist(),
                           int(outputs[i])))
    return out
