"""Data kind `zipf`: `{"kind": "zipf", "exponent": a, "reserved_ids": r}` in
a workload file's `data` group. Token ids follow a
Zipf law over the published vocabulary (rank k has weight 1 / k^a; the first
`r` ids, the specials, are never drawn), so that a few hundred optimizer
steps already pull the loss from ln(vocab) toward the unigram entropy and
"the loss fell" says something about the optimizer. Every step gets a fresh
batch of the same shape; the seed changes the tokens, never the sizes.

A data kind is a file `benchmark/data/<kind>.py` with a class
`TokenBatches(spec, vocab, batch, seqlen, seed)`; the runner finds it by the
`kind` in the workload file (benchmark/lib/files.py).

Copied in spirit from `chip_smoke.write_tokens` (PERF.md, Open questions).
"""

from __future__ import annotations

import numpy as np


class TokenBatches:
    """`next()` -> (input_ids, target_ids, position_ids), int32 (batch, seqlen).
    Targets are the inputs shifted by one, drawn as seqlen + 1 tokens a row."""

    def __init__(self, spec: dict, vocab: int, batch: int, seqlen: int,
                 seed: int):
        reserved = int(spec.get("reserved_ids", 0))
        ranks = np.arange(1, vocab - reserved + 1, dtype=np.float64)
        weights = ranks ** -float(spec["exponent"])
        self._cdf = np.cumsum(weights / weights.sum())
        self._reserved = reserved
        self._rng = np.random.default_rng(seed)
        self._shape = (batch, seqlen + 1)
        self._pos = np.tile(np.arange(seqlen, dtype=np.int32), (batch, 1))

    def next(self):
        u = self._rng.random(self._shape)
        ids = np.searchsorted(self._cdf, u, side="right")
        ids = np.minimum(ids, len(self._cdf) - 1) + self._reserved
        ids = ids.astype(np.int32)
        return ids[:, :-1], ids[:, 1:], self._pos
