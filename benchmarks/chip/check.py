"""The comparison that decides ``correct``: what the timed window produced
against the plain reference, fed the same drive from the same start.

Two numbers are compared, each against its limit:

* ``int_mismatches`` — elements that differ, over every chunk of the
  window and the final state: spike trains, delivered counts, every
  integer ``CommStats`` field (``link_words`` / ``link_backlog`` summed
  per superstep block, when the exchange moves them), bucket utilization
  (off by more than ``UTILIZATION_TOL``), refractory counters, delay-ring
  contents by deadline, merge-queue contents as a multiset, and the
  clocks.  The statistics are stated exact, so the limit is 0.
* ``v_gap`` — the largest absolute difference of a float state: the
  recorded membrane voltage of every step, and the final membrane and
  adaptation state.  Its limit sits between what sound runs read and
  what the lower-precision control reads (``PERF.md`` gives both).
"""

from __future__ import annotations

import numpy as np

LIMITS = {"int_mismatches": 0, "v_gap": 5e-5}
# Utilization is a mean of bucket fills over capacity: its steps are
# 1 / (buckets * capacity), far above float32 rounding of the mean.
UTILIZATION_TOL = 1e-6
FLOAT_RECORDS = ("voltage",)
FLOAT_FINAL = ("v", "w")


def _int_diff(a: np.ndarray, b: np.ndarray) -> int:
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return max(a.size, b.size, 1)
    return int(np.count_nonzero(a != b))


def _float_gap(a: np.ndarray, b: np.ndarray) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    if not a.size:
        return 0.0
    gap = np.abs(a - b)
    return float("inf") if np.isnan(gap).any() else float(gap.max())


def compare(got: dict, want: dict, float_keys: tuple[str, ...]) -> dict:
    """``{"int_mismatches", "v_gap"}`` of one chunk's records or of the
    final state; a key missing on either side mismatches in full."""
    mismatches, gap = 0, 0.0
    for key in sorted(set(got) | set(want)):
        if key not in got or key not in want:
            mismatches += max(np.size(got.get(key, 0)),
                              np.size(want.get(key, 0)), 1)
        elif key in float_keys:
            gap = max(gap, _float_gap(got[key], want[key]))
        elif key == "utilization":
            a, b = np.asarray(got[key]), np.asarray(want[key])
            mismatches += (_int_diff(a, b) if a.shape != b.shape else
                           int(np.count_nonzero(np.abs(a - b) > UTILIZATION_TOL)))
        else:
            mismatches += _int_diff(got[key], want[key])
    return {"int_mismatches": mismatches, "v_gap": gap}


class Tally:
    """Accumulates the numbers over the chunks of a run."""

    def __init__(self):
        self.numbers = {"int_mismatches": 0, "v_gap": 0.0}
        self.failed = 0
        self.compared = 0

    def add(self, result: dict, *, chunk: bool = True) -> None:
        self.numbers["int_mismatches"] += result["int_mismatches"]
        self.numbers["v_gap"] = max(self.numbers["v_gap"], result["v_gap"])
        if chunk:
            self.compared += 1
            self.failed += int(not passes(result))

    def correct(self, attempted: int) -> bool:
        return self.compared == attempted and passes(self.numbers)

    def report(self) -> dict:
        """Each number beside its limit, for the result line."""
        return {name: {"value": self.numbers[name], "limit": LIMITS[name]}
                for name in LIMITS}


def passes(numbers: dict) -> bool:
    return all(numbers[name] <= LIMITS[name] for name in LIMITS)
