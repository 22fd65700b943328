"""Reduce a profiler trace of the window to what the per-layer metrics read.

The trace is JAX's own (``jax.profiler``), read with
``jax.profiler.ProfileData``.  Device operations come from each TPU
plane's "XLA Ops" line and program executions from its "XLA Modules"
line.  A CPU run records its operations on host threads instead, each
event carrying ``hlo_op``/``hlo_module`` and ``device_ordinal`` stats;
that form is read too, so that the reduction can be checked on a trace
recorded here.  The window is the host span ``bench.window``: busy time
is the union of operation intervals clipped to it.  On the TPU an
operation's event is named by its HLO instruction
(``%decode_attention_pallas.1 = bf16[...] custom-call(...)``); a Pallas
kernel's custom call takes the name of the kernel's jitted wrapper, and
that name finds the kernel.  Control flow (``while``, ``conditional``)
appears as events enclosing their bodies' operations: they count in the
busy union once, and the breakdown lists leaf operations only.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

WINDOW = "bench.window"


@dataclasses.dataclass(frozen=True)
class Span:
    start: int   # ns
    end: int     # ns
    name: str

    @property
    def instruction(self) -> str:
        """The HLO instruction's name without ``%`` and its ``.N`` suffix."""
        head = self.name.split(" = ", 1)[0].lstrip("%")
        return re.sub(r"\.\d+$", "", head)

    @property
    def label(self) -> str:
        """The instruction and its result shape, without layouts."""
        head, _, rest = self.name.partition(" = ")
        return f"{head} {re.sub(r'{[^}]*}', '', rest.split(' ', 1)[0])}".strip()


def _stats(event) -> dict:
    try:
        return {str(k): v for k, v in event.stats}
    except (TypeError, ValueError):
        return {}


def _span(event) -> Span:
    start = int(event.start_ns)
    return Span(start, start + int(event.duration_ns), event.name)


def _union(spans, lo, hi):
    """Total length of the union of ``spans`` clipped to [lo, hi]."""
    total, cur_s, cur_e = 0, None, None
    for s in sorted(spans, key=lambda x: x.start):
        a, b = max(s.start, lo), min(s.end, hi)
        if b <= a:
            continue
        if cur_e is None or a > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = a, b
        else:
            cur_e = max(cur_e, b)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(spans, lo, hi):
    """Idle intervals between ``spans`` inside [lo, hi]."""
    out, cursor = [], lo
    for s in sorted(spans, key=lambda x: x.start):
        if s.start > cursor:
            out.append((cursor, min(s.start, hi)))
        cursor = max(cursor, s.end)
        if cursor >= hi:
            break
    if cursor < hi:
        out.append((cursor, hi))
    return [(a, b) for a, b in out if b > a]


class Trace:
    """Device operations and program executions per device, and the host
    spans, on one clock; ``window`` is (start, end) in ns."""

    def __init__(self, ops: dict, modules: dict, host: list, window: tuple):
        self.ops = ops
        self.modules = modules
        self.host = host
        self.window = window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_share(self, device) -> float:
        lo, hi = self.window
        return _union(self.ops.get(device, []), lo, hi) / (hi - lo)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return self.window_s * sum(self.busy_share(d) for d in self.ops) / len(self.ops)

    def executions(self, part: str) -> dict:
        """Executions of programs whose name contains ``part``, per device,
        that began inside the window."""
        lo, hi = self.window
        return {d: [m for m in ms if part in m.name and lo <= m.start < hi]
                for d, ms in self.modules.items()}

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of the operations of kernel ``kernel`` (by
        instruction name), summed over devices, clipped to the window."""
        lo, hi = self.window
        return sum(max(0, min(s.end, hi) - max(s.start, lo))
                   for ops in self.ops.values() for s in ops if s.instruction == kernel) / 1e9

    def kernel_calls(self, kernel: str) -> int:
        lo, hi = self.window
        return sum(lo <= s.start < hi for ops in self.ops.values() for s in ops
                   if s.instruction == kernel)

    def breakdown(self, top: int = 10) -> dict:
        """The leaf device operations that took most time, and the idle time
        by the host span the host was in (its innermost span at the middle
        of each idle gap)."""
        lo, hi = self.window
        by_op = collections.Counter()
        for ops in self.ops.values():
            ordered = sorted(ops, key=lambda x: (x.start, -x.end))
            for i, s in enumerate(ordered):
                if i + 1 < len(ordered) and ordered[i + 1].start < s.end:
                    continue  # encloses the next operation: control flow
                by_op[s.label] += max(0, min(s.end, hi) - max(s.start, lo)) / 1e9
        times, labels = _innermost(self.host)
        by_host = collections.Counter()
        for ops in self.ops.values():
            for a, b in _gaps(ops, lo, hi):
                i = bisect.bisect_right(times, (a + b) // 2) - 1
                by_host[labels[i] if i >= 0 else "(none)"] += (b - a) / 1e9
        n = max(1, len(self.ops))
        return {
            "device_ops": [[k, v / n] for k, v in by_op.most_common(top)],
            "idle_gaps": [[k, v / n] for k, v in by_host.most_common(top)],
        }


def _innermost(host):
    """Change points of the innermost open span of one host thread's
    nested spans: (times, labels), sorted by time."""
    points = sorted([(s.start, 1, i) for i, s in enumerate(host)]
                    + [(s.end, 0, i) for i, s in enumerate(host)])
    stack, times, labels = [], [], []
    for t, opening, i in points:
        if opening:
            stack.append(i)
        elif stack and stack[-1] == i:
            stack.pop()
        elif i in stack:
            stack.remove(i)
        times.append(t)
        labels.append(host[stack[-1]].name if stack else "(none)")
    return times, labels


def from_profile(pd) -> Trace:
    ops = collections.defaultdict(list)
    modules = collections.defaultdict(list)
    host = []
    cpu_runs = collections.defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            dev = plane.name
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev].extend(_span(e) for e in line.events)
                elif line.name == "XLA Modules":
                    modules[dev].extend(_span(e) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = []
                for e in line.events:
                    stats = _stats(e)
                    if "hlo_op" in stats:  # a CPU device's operation
                        dev = f"cpu:{stats.get('device_ordinal', 0)}"
                        s = _span(e)
                        ops[dev].append(s)
                        cpu_runs[(dev, stats.get("run_id"), str(stats.get("hlo_module")))].append(s)
                    elif e.duration_ns > 0:
                        spans.append(_span(e))
                # Host spans of the thread that drove the window.
                if any(s.name == WINDOW for s in spans):
                    host.extend(spans)
    for (dev, _, name), spans in cpu_runs.items():
        modules[dev].append(Span(min(s.start for s in spans), max(s.end for s in spans), name))
    windows = [s for s in host if s.name == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    w = windows[0]
    return Trace(dict(ops), dict(modules), host, (w.start, w.end))


def load(directory) -> Trace:
    """The trace that ``jax.profiler`` wrote under ``directory``."""
    import warnings

    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one trace under {directory}, found {paths}")
    with warnings.catch_warnings():
        # Reading the stats of an event warns once per event type.
        warnings.simplefilter("ignore", DeprecationWarning)
        return from_profile(ProfileData.from_file(paths[0]))
