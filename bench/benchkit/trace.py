"""From a JAX profiler trace to what the per-layer metrics read.

Two steps, each testable alone:

1. ``load_events(path)`` reads an ``.xplane.pb`` with
   ``jax.profiler.ProfileData`` into plain data: the benchmark's own
   spans (``bench.window``, ``bench.call``), the host events of the thread
   that made the calls, and every device operation with its category.
2. ``reduce_events(events, chips)`` cuts them to the window and computes
   busy time, idle gaps and operation times on the trace's one clock.

On a TPU each chip is a plane ``/device:TPU:<i>`` whose ``XLA Ops`` line
holds one event per operation run.  On the CPU (the tests) operations run
on host threads and carry an ``hlo_op`` stat; they count as device 0.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from benchkit import xplane

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW, CALL = "bench.window", "bench.call"
HOST_LOOKBACK = 256     # host events searched back for the one covering t


def load_events(path: str) -> Dict[str, object]:
    """Plain data from one ``.xplane.pb``:

    ``{"spans": [[name, start_ns, end_ns]...],
       "host": [[name, start_ns, end_ns]...],      # the calling thread
       "ops": {device: [[name, category, start_ns, end_ns]...]}}``

    A device operation is named ``<module>/<instruction>``; its category
    is its HLO opcode, with the notable opcodes of the computations a
    fusion calls (``fusion[gather]``), read from the module's HLO in the
    trace (``benchkit.xplane``).
    """
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, host, ops = [], [], {}
    cpu_ops = []
    module_cats: Dict[str, Dict[str, str]] = {}
    protos = None
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            if protos is None:
                protos = xplane.hlo_protos(path)
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = [(ev.start_ns, ev.end_ns, ev.name)
                       for ev in lines.get(MODULES_LINE, [])]
            out = ops.setdefault(int(m.group(1)), [])
            k = 0
            for ev in lines.get(OPS_LINE, []):
                while k < len(modules) and modules[k][1] < ev.start_ns:
                    k += 1
                module = modules[k][2] if k < len(modules) else ""
                name, cat = _op_name(ev.name, module, protos, module_cats)
                out.append([name, cat, ev.start_ns, ev.end_ns])
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = list(line.events)
            if any(ev.name == CALL for ev in evs):
                for ev in evs:
                    if ev.name in (WINDOW, CALL):
                        spans.append([ev.name, ev.start_ns, ev.end_ns])
                    else:
                        host.append([ev.name, ev.start_ns, ev.end_ns])
            for ev in evs:
                if any(k == "hlo_op" for k, _ in ev.stats):
                    cpu_ops.append([ev.name, "", ev.start_ns, ev.end_ns])
    if not ops and cpu_ops:
        ops[0] = cpu_ops
    return {"spans": spans, "host": host,
            "ops": {str(d): v for d, v in sorted(ops.items())}}


def _op_name(text: str, module: str, protos, cache) -> Tuple[str, str]:
    """(``<module>/<instruction>``, category) of one ``XLA Ops`` event,
    whose name is the instruction's HLO text."""
    instr, _, rhs = text.partition("=")
    instr = instr.strip().lstrip("%")
    if module not in cache:
        cache[module] = {}
        if module in protos:
            try:
                cache[module] = xplane.categories(
                    xplane.hlo_text(protos[module]))
            except (ImportError, AttributeError, RuntimeError, ValueError):
                pass
    cat = cache[module].get(instr) or xplane.opcode(rhs)
    short = module.split("(")[0]
    return (f"{short}/{instr}" if short else instr), cat


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def covered(merged_intervals, lo: float, hi: float) -> float:
    return sum(b - a for a, b in clip(merged_intervals, lo, hi))


@dataclass
class Reduced:
    """A trace cut to the benchmark's window (times in ns)."""
    start: float
    end: float
    chips: int
    calls: List[Tuple[float, float]]
    host: List[Tuple[str, float, float]]
    ops: Dict[int, List[Tuple[str, str, float, float]]]

    def __post_init__(self):
        self.host_starts = [a for _, a, _ in self.host]

    @property
    def window_s(self) -> float:
        return (self.end - self.start) * 1e-9

    def busy(self, dev: int) -> List[Tuple[float, float]]:
        return union(clip([(a, b) for _, _, a, b in self.ops.get(dev, [])],
                          self.start, self.end))

    def busy_s(self, dev: int) -> float:
        return sum(b - a for a, b in self.busy(dev)) * 1e-9

    def busy_s_mean(self) -> float:
        return sum(self.busy_s(d) for d in range(self.chips)) / self.chips

    def op_seconds(self, match) -> float:
        """Device seconds per chip, averaged over the chips, of the
        operations for which ``match(name, category)`` holds."""
        total = 0.0
        for dev in range(self.chips):
            for name, cat, a, b in self.ops.get(dev, []):
                if match(name, cat):
                    lo, hi = max(a, self.start), min(b, self.end)
                    if hi > lo:
                        total += hi - lo
        return total * 1e-9 / self.chips

    def gaps(self, dev: int = 0) -> List[Tuple[float, float]]:
        out, t = [], self.start
        for a, b in self.busy(dev):
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if self.end > t:
            out.append((t, self.end))
        return out


def reduce_events(events: Dict[str, object], chips: int) -> Optional[Reduced]:
    """Cut ``load_events`` output to the ``bench.window`` span; None where
    the trace holds no window or no device operation."""
    windows = [(a, b) for n, a, b in events["spans"] if n == WINDOW]
    ops = {int(d): [tuple(o) for o in v]
           for d, v in events["ops"].items()}
    if not windows or not any(ops.values()):
        return None
    start, end = windows[0]
    calls = sorted((a, b) for n, a, b in events["spans"] if n == CALL)
    host = sorted(((n, a, b) for n, a, b in events["host"]),
                  key=lambda e: (e[1], -e[2]))
    return Reduced(start=start, end=end, chips=chips, calls=calls,
                   host=host, ops=ops)


def reduce_dir(trace_dir: str, chips: int) -> Optional[Reduced]:
    path = find_xplane(trace_dir)
    return None if path is None else reduce_dir_file(path, chips)


def reduce_dir_file(path: str, chips: int) -> Optional[Reduced]:
    return reduce_events(load_events(path), chips)


def host_label(r: Reduced, t: float) -> str:
    """What the calling thread was doing at ``t``: its innermost host
    event, else the benchmark span, else ``between calls``."""
    i = bisect.bisect_right(r.host_starts, t)
    for name, a, b in reversed(r.host[max(0, i - HOST_LOOKBACK):i]):
        if b >= t:
            return name
    for a, b in r.calls:
        if a <= t <= b:
            return "bench.call"
    return "between calls"


def breakdown(r: Reduced, top: int = 10) -> Dict[str, list]:
    """The device operations that took most time (seconds per chip) and
    the idle time of chip 0 by what the host was doing."""
    per_op: Dict[str, float] = {}
    for dev in range(r.chips):
        for name, cat, a, b in r.ops.get(dev, []):
            lo, hi = max(a, r.start), min(b, r.end)
            if hi > lo:
                key = f"{name} [{cat}]" if cat else name
                per_op[key] = per_op.get(key, 0.0) + (hi - lo) * 1e-9
    ops = sorted(((k, v / r.chips) for k, v in per_op.items()),
                 key=lambda kv: -kv[1])[:top]
    idle: Dict[str, float] = {}
    for a, b in r.gaps(0):
        label = host_label(r, (a + b) / 2)
        idle[label] = idle.get(label, 0.0) + (b - a) * 1e-9
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
