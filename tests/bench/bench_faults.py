"""Faults planted under the benchmark's timed path, for the tests that
show ``correct`` coming out false.  Each wraps a method of the program
that returns ``(ids (m, k), distances (m, k))`` for m query rows (or graph
rows) and breaks its answer:

* ``stale`` — a step that returns its state unchanged: every call
  answers with the first call's answers;
* ``half`` — half of the batch left out: rows past the first half get the
  first half's answers;
* ``altered`` — an answer altered where it is produced: the first row's
  nearest id moves to the next id.

(The cells run on one chip, so there is no exchange between chips to
leave out.)
"""

import numpy as np

FAULTS = ("stale", "half", "altered")


def _break(kind, first, ids, d2, n):
    ids, d2 = np.array(ids), np.array(d2)
    m = len(ids)
    if kind == "stale":
        take = np.arange(m) % len(first[0])
        return first[0][take], first[1][take]
    if kind == "half":
        h = -(-m // 2)
        ids[h:], d2[h:] = ids[:m - h], d2[:m - h]
    elif kind == "altered":
        ids[0, 0] = (ids[0, 0] + 1) % n
    return ids, d2


def plant(monkeypatch, cls, method, kind, size):
    """Break ``cls.method``'s answers with fault ``kind``; ``size(self)``
    is the number of valid ids."""
    import jax.numpy as jnp

    original = getattr(cls, method)
    first = []

    def broken(self, *args, **kwargs):
        ids, d2 = (np.asarray(a) for a in original(self, *args, **kwargs))
        if not first:
            first.append((ids, d2))
        ids, d2 = _break(kind, first[0], ids, d2, size(self))
        return jnp.asarray(ids), jnp.asarray(d2)

    monkeypatch.setattr(cls, method, broken)
