"""graftcheck — static contract checker for this repo (ISSUE 11).

Two layers:

* **Layer 1 — source lints** (`rules.py`, `lints_source.py`,
  `lints_traced.py`, `report.py`): pure-AST rules for this codebase's
  known failure classes — use-after-donate, host calls inside traced
  code, PRNG key reuse, lock discipline, dead imports/unreachable code.
  Stdlib-only: importing these modules never imports jax, so
  `scripts/graftcheck.py` sweeps the repo without paying the jax import.

* **Layer 2 — trace contracts** (`programs.py`, `contracts.py`): lower
  the canonical programs (train step across the ZeRO × wire matrix,
  paged decode, prefill chunk, speculative verify) on the CPU test mesh
  and assert invariants on the compiled HLO — the collective inventory
  matches what `obs/attribution.expected_collectives` prices, int8 wires
  carry no f32 dp-axis payloads, declared donations actually alias, and
  knobs that shouldn't recompile don't. These modules import jax lazily
  and only when asked.

This package deliberately avoids importing its own parent package at
module scope; layer 2 does so inside functions. That keeps layer 1 loadable
standalone (scripts/graftcheck.py loads it by path for the no-jax sweep).
"""

from .rules import (GRAFTCHECK_SCHEMA_VERSION, RULES, Violation, lint_file,
                    lint_paths)
from .report import build_report, format_report, validate_report

__all__ = ["GRAFTCHECK_SCHEMA_VERSION", "RULES", "Violation", "lint_file",
           "lint_paths", "build_report", "format_report", "validate_report"]
