"""Production mesh construction (a FUNCTION — importing never touches jax
device state; jax locks the device count on first backend init)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, **kwargs):
    """``jax.make_mesh`` with Auto axes.  ``make_mesh`` defaults to Explicit
    axes, which put shardings into array types and make every gather of a
    sharded array outside ``shard_map`` (``x[s]``, ``take_along_axis``)
    demand an ``out_sharding``; this codebase places data with
    ``NamedSharding`` and lets XLA propagate."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         **kwargs)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_dev_mesh(model_axis: int = 1):
    """Whatever this host has (tests / CPU smoke): (n_dev/model, model)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"))


def data_mesh(n: int | None = None):
    """1-D ``('data',)`` mesh over ``n`` devices (default: all local).

    The ONE way row-partitioned index work builds its mesh — the sample
    sort in ``core/distributed.py``, the sharded facade in
    ``index/sharded.py``, and the distributed self-checks all call this
    instead of hand-rolling ``Mesh``/``make_mesh`` shapes, so the axis
    name and device order can never drift between build and serve.
    """
    devs = jax.devices()
    if n is None:
        n = len(devs)
    if not 1 <= n <= len(devs):
        raise ValueError(f"data_mesh(n={n}): host has {len(devs)} devices")
    return _auto_mesh((n,), ("data",), devices=devs[:n])
