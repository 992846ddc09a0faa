"""Capture a profiler trace of the window and reduce it to numbers.

The reduction reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData`` and nothing else:

* device planes are those named ``/device:TPU:<n>``; on each, the line
  ``XLA Modules`` holds one event per program execution and ``XLA Ops``
  one per operation (loop bodies once per iteration);
* the harness's own host spans are ``jax.profiler.TraceAnnotation`` events
  whose names start with ``bench.``; ``bench.window`` bounds the traced
  window.

Busy time is the union of program executions (``XLA Modules``) inside the
window, averaged over the device planes; idle is the rest of the window.
Host and device timestamps share the trace's clock to within about a
millisecond.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

WINDOW = "bench.window"
# Device timestamps run up to about a millisecond early against the host's
# spans.
EDGE_NS = 5e6
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_MODULE_NAME = re.compile(r"^(.*?)(\(\d+\))?$")
_OP_NAME = re.compile(r"^%([\w.\-]+) = ")
_RESULT = re.compile(r"^%\S+ = (\S+?)\{")
_KERNEL = 'custom_call_target="tpu_custom_call"'
_SIGNATURE = re.compile(r"^%\S+ = (\w+)\[.*? custom-call\((.*)\), custom_call")
_OPERAND = re.compile(r"(\w+)\[[\d,]*\]\{[^}]*\} %")
# Control-flow ops span their bodies' ops; the breakdown counts the bodies.
_CONTAINERS = {"while", "conditional", "call"}

Interval = Tuple[float, float]


def module_base(name: str) -> str:
    """``jit_merge_order(3026558858167966956)`` -> ``jit_merge_order``."""
    return _MODULE_NAME.match(name).group(1)


def op_base(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``: the op's name
    without its numeric suffix, or for a Mosaic kernel
    ``tpu_custom_call->`` and its result type.  The breakdown groups by
    it."""
    if is_kernel(name):
        m = _RESULT.match(name)
        return "tpu_custom_call->" + (m.group(1) if m else "?")
    m = _OP_NAME.match(name)
    if not m:
        return name[:80]
    return re.sub(r"\.\d+$", "", m.group(1))


def is_kernel(name: str) -> bool:
    """Whether an op's HLO text is a Mosaic (Pallas) kernel call."""
    return _KERNEL in name


def kernel_signature(name: str) -> Optional[Tuple[str, List[str]]]:
    """(result dtype, operand dtypes) of a Mosaic kernel's HLO text, or
    None for any other op: ``%x = s32[8,128]{..} custom-call(u32[8,4,1]{..}
    %a, u32[8,4,128]{..} %b), custom_call_target="tpu_custom_call"`` ->
    ``("s32", ["u32", "u32"])``."""
    if not is_kernel(name):
        return None
    m = _SIGNATURE.match(name)
    if not m:
        return None
    return m.group(1), _OPERAND.findall(m.group(2))


def union_length(intervals: List[Interval]) -> float:
    """Total length covered by a set of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that no interval covers, in time order."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


class Trace:
    """The reduced trace: device programs and ops, host spans, the window.

    Times are nanoseconds on the trace's clock.  ``modules`` holds every
    program execution per device plane, named without its fingerprint;
    ``op_time`` sums operation time by ``<program>/<op>``; ``ops`` keeps
    the events that ``keep_op`` accepts (none by default), since a traced
    search loop can record millions of tiny operations.
    """

    def __init__(self, planes, keep_op=None):
        self.modules: List[List[Tuple[str, float, float]]] = []
        self.ops: List[List[Tuple[str, float, float]]] = []
        self.spans: List[Tuple[str, float, float]] = []
        self.op_time: Dict[str, float] = defaultdict(float)
        for plane in planes:
            if not _DEVICE_PLANE.match(plane.name):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("bench."):
                            self.spans.append((e.name, e.start_ns, e.end_ns))
                continue
            lines = {line.name: line for line in plane.lines}
            mods = sorted(((module_base(e.name), e.start_ns, e.end_ns)
                           for e in lines["XLA Modules"].events),
                          key=lambda m: m[1]) if "XLA Modules" in lines else []
            ops: List[Tuple[str, float, float]] = []
            if "XLA Ops" in lines:
                starts = [m[1] for m in mods]
                for e in lines["XLA Ops"].events:
                    if keep_op is not None and keep_op(e.name):
                        ops.append((e.name, e.start_ns, e.end_ns))
                    base = op_base(e.name)
                    if base in _CONTAINERS:
                        continue
                    i = bisect.bisect_right(starts, e.start_ns) - 1
                    mod = mods[i][0] if i >= 0 and e.start_ns < mods[i][2] \
                        else "?"
                    self.op_time[f"{mod}/{base}"] += e.duration_ns
            self.modules.append(mods)
            self.ops.append(ops)
        wins = [(s, e) for n, s, e in self.spans if n == WINDOW]
        if not self.modules or not wins:
            raise ValueError("trace has no TPU device plane or no "
                             f"{WINDOW!r} span")
        self.window: Interval = wins[0]

    @classmethod
    def from_file(cls, path: str, keep_op=None) -> "Trace":
        from jax.profiler import ProfileData

        return cls(ProfileData.from_file(path).planes, keep_op)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _clip(self, events) -> List[Interval]:
        lo, hi = self.window
        return [(max(s, lo), min(e, hi)) for _, s, e in events
                if e > lo and s < hi]

    def busy_s(self) -> float:
        """Seconds of the window in which a program ran, averaged over the
        device planes."""
        per = [union_length(self._clip(m)) for m in self.modules]
        return sum(per) / len(per) / 1e9

    def module_stats(self, base: str) -> Tuple[float, int]:
        """(seconds, executions) of programs named ``base`` (``jit_<fn>``)
        run inside the window, summed over the device planes.

        The last program on each plane is left out: the trace's stop may
        have cut it short.  A program may appear to start up to
        ``EDGE_NS`` before the window, and is kept.
        """
        lo, hi = self.window
        total, count = 0.0, 0
        for mods in self.modules:
            last = max((e for _, _, e in mods), default=None)
            for name, s, e in mods:
                if name == base and s >= lo - EDGE_NS and e < last:
                    total += e - s
                    count += 1
        return total / 1e9, count

    def op_stats(self, match) -> Tuple[float, int]:
        """(seconds, events) of kept operations whose HLO text satisfies
        ``match``, overlapping the window widened by ``EDGE_NS``."""
        lo, hi = self.window
        total, count = 0.0, 0
        for ops in self.ops:
            for name, s, e in ops:
                if e > lo - EDGE_NS and s < hi + EDGE_NS and match(name):
                    total += e - s
                    count += 1
        return total / 1e9, count

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The device operations that took most time, and the longest idle
        gaps named by the innermost harness span around their midpoint."""
        ops = sorted(self.op_time.items(), key=lambda kv: -kv[1])[:top]
        idle: List[Tuple[float, str]] = []
        for mods in self.modules:
            for s, e in gaps(self._clip(mods), *self.window):
                mid = (s + e) / 2
                inside = [(se - ss, n) for n, ss, se in self.spans
                          if ss <= mid <= se and n != WINDOW]
                idle.append((e - s, min(inside)[1] if inside else WINDOW))
        idle.sort(key=lambda t: -t[0])
        return {
            "device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": [[n, t / 1e9] for t, n in idle[:top]],
        }


@contextlib.contextmanager
def capture(enabled: bool) -> Iterator[dict]:
    """Profile the body when ``enabled``; yields a dict that holds the
    ``.xplane.pb`` path after the body (the directory is a temporary one,
    removed by :func:`discard`)."""
    import jax

    out: dict = {"path": None, "dir": None}
    if not enabled:
        yield out
        return
    out["dir"] = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out["dir"], profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield out
    finally:
        jax.profiler.stop_trace()
        found = glob.glob(out["dir"] + "/**/*.xplane.pb", recursive=True)
        out["path"] = found[0] if found else None


def discard(out: dict) -> None:
    if out.get("dir"):
        shutil.rmtree(out["dir"], ignore_errors=True)


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)
