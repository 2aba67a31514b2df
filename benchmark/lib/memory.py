"""The most HBM the fullest chip held at one time, from `memory_stats()`.

The runtime keeps two peak counters for a process: `peak_bytes_in_use`
(buffers) and `peak_bytes_reserved` (what it sets aside for a loaded
program's temporaries). Their sum is what the `train` runner reports
(`runners/train._peak_bytes`), and it is the most that was held as long as
both peaks belong to one moment: the timed step's. A run whose float32
reference reserves more than the step does breaks that: in
`joyai-llm-flash.train-ep16share-b4-t4096` the reference's phase reserves
11.4 GiB while only the weights exist, the step 6.7 GiB beside the Adam
state, and the counters' sum, 19.2 GiB, is more than the chip has (my chip
runs, PR 33).

`phase_peak_bytes` reads the same quantity by phase. In the cells of `train`
its second term is `train`'s number to the byte, because the loaded step's
reservation is the peak one and still stands when the window ends (ten runs
of the two GPT-2 cells, my chip runs, PR 33: 11,644,997,632 and
10,901,640,704 both ways), so a `benchmark` PR can hand `train` this helper
and change no reading.
"""

from __future__ import annotations


def phase_peak_bytes(after_reference: dict, after_window: dict) -> int:
    """Up to the end of the reference the process's two peak counters belong
    to one phase and add up. After it: the buffers' peak (it only grows once
    the Adam state is made) plus what the runtime holds reserved for the
    loaded step's temporaries when the window ends. The larger of the two."""
    reference = (after_reference["peak_bytes_in_use"]
                 + after_reference["peak_bytes_reserved"])
    step = after_window["peak_bytes_in_use"] + after_window["bytes_reserved"]
    return max(reference, step)
