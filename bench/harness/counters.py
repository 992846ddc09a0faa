"""The program's own latency recorders, read for the measured window.

``repro.obs.default_registry()`` outlives the engine a driver frees, and
each recorder sample carries its ``time.perf_counter()`` stamp; the window
is every sample stamped at or after the end of set-up.  A program whose
recorders carry no stamps (no ``MetricsRegistry.find``) reads as nothing,
never as an error.
"""

from __future__ import annotations

from typing import Optional


def _recorder(name: str, **labels: str):
    from repro.obs import default_registry

    find = getattr(default_registry(), "find", None)
    return find(name, **labels) if find is not None else None


def window_start(run) -> float:
    """The end of set-up on ``perf_counter``."""
    return run.t0 + run.setup_s


def window_percentile(run, name: str, p: float, **labels: str
                      ) -> Optional[float]:
    """The nearest-rank ``p``-th percentile of ``name``'s window samples,
    or ``None`` when there are none."""
    from repro.obs.registry import percentile_label

    rec = _recorder(name, **labels)
    if rec is None:
        return None
    return rec.snapshot((p,), since=window_start(run)).get(
        percentile_label(p))


def last_before_window(run, name: str, **labels: str) -> Optional[float]:
    """``name``'s last sample recorded before the window, or ``None``."""
    rec = _recorder(name, **labels)
    if rec is None:
        return None
    samples = rec.samples(before=window_start(run))
    return float(samples[-1]) if samples.size else None
