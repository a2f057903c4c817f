"""Part 3 of `correct`, the part every entry shares: the cell's own
experiment, on the `--seed` of the window's iteration 0, is run once more
with what the entry checks of it captured, and each captured item is
compared with the entry's plain reference. What is wrapped to capture,
which items, the reference and the limits are the entry's
(benchmark/entries/<entry>.py: `captured`, `against_reference`); that the
captured experiment's digest is the timed runs' own, which ties what is
compared to what the window drove, is benchmark/run.py's to see.
"""

from __future__ import annotations

import time


def limited(record: dict) -> dict[str, tuple]:
    """The numbers of a comparison's record that have a limit beside them
    (`<number>` and `limit_<number>`), as {number: (value, limit)}."""
    return {k[len("limit_"):]: (record[k[len("limit_"):]], limit)
            for k, limit in record.items() if k.startswith("limit_")}


def check(cell, seed: int, out_dir: str) -> tuple[object, list[dict], dict]:
    """The captured experiment's outcome, one record per item compared
    (numbers, each beside its limit, and `passed`), and where the seconds
    went (the program's run; the reference, under the key the line has had
    since the DES was the only one)."""
    t0 = time.perf_counter()
    outcome, taken = cell.entry.captured(cell, seed, out_dir)
    t1 = time.perf_counter()
    records = [cell.entry.against_reference(cell, item)
               for item in (taken if outcome.ok else [])]
    return outcome, records, {"program_s": t1 - t0,
                              "des_s": time.perf_counter() - t1}
