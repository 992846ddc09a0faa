"""The comparison that decides ``correct``: answers against the reference.

Every number compared is printed with its limit.  The numbers:

* ``recall`` — mean share of each checked answer's ids that the exact
  reference also returns (recall@k); its floor is the configuration's
  stated guarantee.
* ``dist_gap`` — the widest gap between a distance the program reports
  and the float32 distance from the query to the row it names.  It holds
  the reported distances to what they claim, and catches an answer whose
  ids were altered or moved to another request.
* ``bad_ids`` — ids that no answer may hold: out of range, repeated
  within an answer, the row itself (graphs) or deleted (serving).  Exact:
  its limit is 0.
* ``readback_miss`` — serving only: reads of an acknowledged insert whose
  answer lacks the inserted id.  Exact: its limit is 0.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


class Check:
    def __init__(self, name: str, value: float, limit: float, op: str):
        if op not in (">=", "<="):
            raise ValueError(op)
        self.name, self.value, self.limit, self.op = name, value, limit, op

    @property
    def ok(self) -> bool:
        if not np.isfinite(self.value):
            return False
        return self.value >= self.limit if self.op == ">=" else (
            self.value <= self.limit)

    def line(self) -> str:
        return (f"check {self.name} {self.value!r} {self.op} {self.limit!r} "
                f"{'ok' if self.ok else 'FAIL'}")


def recall(ids: np.ndarray, ref: np.ndarray) -> float:
    """Mean |ids[r] & ref[r]| / k over rows; k = ref's width."""
    k = ref.shape[1]
    hits = sum(len(set(a[:k].tolist()) & set(b.tolist()) - {-1})
               for a, b in zip(ids, ref))
    return hits / (len(ref) * k)


def dist_gap(d2: np.ndarray, exact: np.ndarray) -> float:
    """max |reported - exact| over slots with an exact distance (NaN
    slots, id -1, are skipped; no slot at all reads +inf)."""
    diff = np.abs(np.asarray(d2, np.float64) - exact)
    diff = diff[np.isfinite(exact)]
    return float(diff.max()) if diff.size else float("inf")


def bad_ids(ids: np.ndarray, n: int, *, self_rows: Optional[np.ndarray] = None,
            dead: Optional[np.ndarray] = None) -> int:
    """Count slots holding an id out of [0, n), repeated in its row, equal
    to ``self_rows[r]``, or marked in ``dead``."""
    ids = np.asarray(ids)
    bad = (ids < 0) | (ids >= n)
    srt = np.sort(ids, axis=1)
    bad_count = int(bad.sum()) + int((srt[:, 1:] == srt[:, :-1]).sum())
    ok = np.clip(ids, 0, n - 1)
    if self_rows is not None:
        bad_count += int((ids == np.asarray(self_rows)[:, None]).sum())
    if dead is not None:
        bad_count += int((dead[ok] & ~bad).sum())
    return bad_count
