"""Out-of-process LSM compaction: the shadow compacts in a child process.

The in-thread shadow compact (PR 6) kept maintenance off the query
*path*, but on a CPU host — where the "device" IS the host cores — it
still contends with serving for silicon and for the GIL.  This module
moves the expensive step out of the serving process entirely, the way
real LSM stores do, using the format_version-5 bundle machinery as the
handoff (PR 8 made save/load bit-exact and crash-verifiable, which is
what makes this protocol provable rather than hopeful):

parent (serving process)                 child (``python -m repro.serve.compactor``)
------------------------                 ------------------------------------------
snapshot() the serving index
save(workdir/in)           ── spawn ──►  load(workdir/in)
keep serving + logging writes            compact()
                                         save(workdir/out)
                                         atomically commit result marker
load(workdir/out)          ◄── exit ──
verify marker vs loaded state
replay write log, swap epoch  (the engine's existing protocol)

Safety properties, each exercised by the ``compactor`` lane of
``scripts/crash_check.py`` (SIGKILL at every registered fault point in
the child):

* the parent NEVER trusts ``workdir/out`` unless the child exited 0 AND
  the result marker — written atomically, after the bundle — is present
  and matches the reloaded index (a partially-written bundle is
  indistinguishable from a missing one: both fail the cycle);
* a failed/killed/hung child fails ONLY that maintenance cycle: the
  serving index received every write first and stays authoritative, and
  the engine's capped-exponential backoff schedules the retry;
* the snapshot is saved WITHOUT a WAL (snapshots never carry one), so
  nothing is ever double-logged across the process boundary; the live
  WAL transfers old → new index at swap time exactly as before.

Fault-point arming crosses the process boundary via dedicated variables:
``REPRO_COMPACTOR_FAULTS`` / ``REPRO_COMPACTOR_FAULT_TRACE`` in the
parent's environment become the child's ``REPRO_FAULTS`` /
``REPRO_FAULT_TRACE`` (and the parent's own are stripped from the child),
so the crash matrix can kill the child deterministically without the
arming leaking into the serving process.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from typing import Any, Dict, Optional, Tuple

__all__ = [
    "CompactionChildError",
    "compact_in_child",
    "child_main",
]

_RESULT_MARKER = "compact_result.json"
_MUTABLE_MANIFEST = "mutable_manifest.json"
_SHARDED_MANIFEST = "sharded_mutable_manifest.json"


class CompactionChildError(RuntimeError):
    """The compaction child failed/died/produced an unverifiable bundle.

    Raised in the PARENT; the maintenance cycle fails, the shadow is
    abandoned, and the serving index (which received every write first)
    stays authoritative.  The engine's maintainer backs off and retries.
    """


def _detect_layout(path: str) -> str:
    if os.path.exists(os.path.join(path, _SHARDED_MANIFEST)):
        return "sharded_mutable"
    if os.path.exists(os.path.join(path, _MUTABLE_MANIFEST)):
        return "mutable"
    raise FileNotFoundError(
        f"no mutable/sharded-mutable manifest under {path!r}"
    )


def _summary(index) -> Dict[str, int]:
    """The identity a compaction must preserve: the live set and the id
    cursor.  Compared parent-side against the child's marker AND against
    the reloaded bundle (three-way agreement before a swap is allowed)."""
    stats = index.maintenance_stats()
    return {
        "n_live": int(stats["n_live"]),
        "n_deleted": int(stats["n_deleted"]),
        "next_id": int(index._lsm.next_id),
    }


def _load(path: str, layout: str, mesh=None):
    if layout == "sharded_mutable":
        from repro.index.sharded_mutable import ShardedMutableHilbertIndex

        if mesh is None:
            from repro.launch.mesh import data_mesh

            with open(os.path.join(path, _SHARDED_MANIFEST)) as f:
                mesh = data_mesh(int(json.load(f)["n_shards"]))
        return ShardedMutableHilbertIndex.load(path, mesh=mesh)
    from repro.index.mutable import MutableHilbertIndex

    return MutableHilbertIndex.load(path)


# -- child entry point -------------------------------------------------------


def child_main(argv=None) -> int:
    """``python -m repro.serve.compactor IN_DIR OUT_DIR``: load, compact,
    save, then atomically commit the result marker (the commit point the
    parent keys on — bundle files without a marker are never trusted)."""
    args = list(sys.argv[1:] if argv is None else argv)
    if len(args) != 2:
        print("usage: python -m repro.serve.compactor IN_DIR OUT_DIR",
              file=sys.stderr)
        return 2
    in_dir, out_dir = args
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from repro.checkpoint import atomic_write_json
    from repro.testing.faults import fault_point

    t0 = time.perf_counter()
    layout = _detect_layout(in_dir)
    index = _load(in_dir, layout)
    fault_point("compactor.child.loaded", path=in_dir)
    pre = _summary(index)
    load_s = time.perf_counter() - t0

    t1 = time.perf_counter()
    index.compact()
    fault_point("compactor.child.compacted", path=out_dir)
    compact_s = time.perf_counter() - t1

    t2 = time.perf_counter()
    index.save(out_dir)
    # marker LAST: its atomic rename is the child's commit point.  A kill
    # anywhere above leaves out_dir unmarked (or partial) and the parent
    # refuses it wholesale.
    fault_point("compactor.child.pre_marker", path=out_dir)
    atomic_write_json(os.path.join(out_dir, _RESULT_MARKER), {
        "layout": layout,
        "summary": _summary(index),
        "pre_compact_summary": pre,
        "n_segments": int(index.n_segments),
        "pid": os.getpid(),
        "phases_s": {
            "load": load_s,
            "compact": compact_s,
            "save": time.perf_counter() - t2,
        },
    })
    fault_point("compactor.child.post_marker", path=out_dir)
    return 0


# -- parent-side driver ------------------------------------------------------


def _child_env() -> Dict[str, str]:
    env = dict(os.environ)
    # the parent's own armed faults must not replicate into the child
    # (the serving process's kill plan is the serving process's);
    # REPRO_COMPACTOR_* is the dedicated cross-process arming channel
    env.pop("REPRO_FAULTS", None)
    env.pop("REPRO_FAULT_TRACE", None)
    if "REPRO_COMPACTOR_FAULTS" in env:
        env["REPRO_FAULTS"] = env.pop("REPRO_COMPACTOR_FAULTS")
    if "REPRO_COMPACTOR_FAULT_TRACE" in env:
        env["REPRO_FAULT_TRACE"] = env.pop("REPRO_COMPACTOR_FAULT_TRACE")
    # make `repro` importable in the child regardless of install mode
    src_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    env["PYTHONPATH"] = (
        src_root + os.pathsep + env["PYTHONPATH"]
        if env.get("PYTHONPATH") else src_root
    )
    import jax

    # The child always runs on the CPU: on an accelerator the serving
    # parent holds the chip, and a child that opened it would fail or hang.
    # The handoff is a bundle on disk, so the platform is the child's own.
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        # a sharded bundle needs as many child devices as shards
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{jax.device_count()}"
        ).strip()
    return env


def compact_in_child(
    index,
    workdir: str,
    *,
    timeout: Optional[float] = None,
    mesh=None,
) -> Tuple[Any, Dict[str, Any]]:
    """Save ``index`` (the engine's shadow), compact it in a child
    process, and return ``(compacted_index, phase_timings)``.

    Raises :class:`CompactionChildError` if the child exits nonzero, dies
    on a signal, or the result bundle fails three-way verification
    (marker summary vs reloaded state vs the pre-save live set), and
    ``subprocess.TimeoutExpired`` is mapped by the caller's watchdog
    policy.  ``workdir`` is reused across cycles (``in``/``out`` are
    cleared first); callers own its lifetime.
    """
    phases: Dict[str, Any] = {}
    in_dir = os.path.join(workdir, "in")
    out_dir = os.path.join(workdir, "out")
    for d in (in_dir, out_dir):
        shutil.rmtree(d, ignore_errors=True)
    os.makedirs(workdir, exist_ok=True)

    expect = _summary(index)
    t0 = time.perf_counter()
    index.save(in_dir)
    phases["save_in_ms"] = 1000.0 * (time.perf_counter() - t0)

    t1 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-m", "repro.serve.compactor", in_dir, out_dir],
        env=_child_env(), timeout=timeout,
        capture_output=True, text=True,
    )
    phases["child_ms"] = 1000.0 * (time.perf_counter() - t1)
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip().splitlines()[-8:]
        raise CompactionChildError(
            f"compactor child exited {proc.returncode}"
            + (f" (signal {-proc.returncode})" if proc.returncode < 0 else "")
            + (": " + " | ".join(tail) if tail else "")
        )

    marker_path = os.path.join(out_dir, _RESULT_MARKER)
    if not os.path.exists(marker_path):
        raise CompactionChildError(
            "compactor child exited 0 but committed no result marker — "
            "refusing the bundle"
        )
    with open(marker_path) as f:
        marker = json.load(f)

    t2 = time.perf_counter()
    layout = _detect_layout(out_dir)
    compacted = _load(out_dir, layout, mesh=mesh)
    phases["load_out_ms"] = 1000.0 * (time.perf_counter() - t2)
    phases["child_phases_s"] = marker.get("phases_s", {})

    got = _summary(compacted)
    if not (got == marker.get("summary") and got == expect):
        raise CompactionChildError(
            "compacted bundle failed verification: "
            f"expected {expect}, marker {marker.get('summary')}, "
            f"loaded {got} — refusing to swap it in"
        )
    return compacted, phases


if __name__ == "__main__":  # pragma: no cover - exercised via subprocess
    sys.exit(child_main())
