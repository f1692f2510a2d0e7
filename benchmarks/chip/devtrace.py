"""Reduction of one profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Device planes are those named ``/device:TPU:<n>``; on each, the line of XLA
operations (``XLA Ops``) is what ran.  An event there is named by its HLO
instruction (``%flash_decode_paged.9 = bf16[...] custom-call(...)``), so an
op's label is the instruction's name without ``%`` and its ``.<n>`` suffix
(``flash_decode_paged``), and a Pallas kernel's label is the name the
kernel's wrapper gives it.  Control-flow ops (a scan's ``while``) are events
too, spanning the ops of their body.

The traced window is the span from the start of the first to the end of
the last ``bench:step`` host span (the benchmark's annotation around
``Cluster.step``): every step ends in a host read, so the device work of
those steps lies inside it.  Busy time is the union of the operations'
intervals in the window, averaged over the chips; a kernel's time is the
summed duration of the operations whose label holds the kernel's name; the
top operations are ranked by self time (an op's time not covered by the ops
nested in it).  An idle gap is labelled by the innermost ``bench:`` host
span open at its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN = "bench:"


@dataclasses.dataclass
class Op:
    label: str                # HLO instruction name, without %, .<n>
    start: float              # ns
    end: float


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]             # ns
    ops: Dict[str, List[Op]]                # device plane -> ops in window
    spans: List[Tuple[str, float, float]]   # bench host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Union of op intervals in the window, averaged over the devices."""
        if not self.ops:
            return 0.0
        tot = sum(sum(b - a for a, b in _union(ops)) for ops in self.ops.values())
        return tot / len(self.ops) * 1e-9

    def kernel_s(self, key: str) -> float:
        """Summed device time of the ops whose label holds ``key`` (over
        devices)."""
        return sum(o.end - o.start for ops in self.ops.values() for o in ops
                   if key in o.label) * 1e-9

    def top_ops(self, n: int = 10) -> List[list]:
        """The n labels with the most self time, seconds over devices."""
        tot: Dict[str, float] = {}
        for ops in self.ops.values():
            for o, t in _self_times(ops):
                tot[o.label] = tot.get(o.label, 0.0) + t * 1e-9
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The n longest device-idle gaps of the first device, each
        labelled by the host span open at its middle."""
        if not self.ops:
            return []
        ops = self.ops[sorted(self.ops)[0]]
        lo, hi = self.window
        gaps, t = [], lo
        for a, b in _union(ops):
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if hi > t:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.host_at((a + b) / 2), (b - a) * 1e-9] for a, b in gaps[:n]]

    def host_at(self, t: float) -> str:
        best = None
        for name, a, b in self.spans:
            if a <= t <= b and (best is None or b - a < best[1] - best[0]):
                best = (a, b, name)
        return best[2] if best else "host:other"


def label(event_name: str) -> str:
    """``%moe_gemm.23 = bf16[...] custom-call(...)`` -> ``moe_gemm``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head) or head


def _self_times(ops: List[Op]) -> List[Tuple[Op, float]]:
    """Each op with its duration less the time of the ops nested in it."""
    out: List[list] = []
    stack: List[list] = []            # [op, child time] of open ops
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1][0].end <= o.start:
            out.append(stack.pop())
        if stack and o.end <= stack[-1][0].end:
            stack[-1][1] += o.end - o.start
        stack.append([o, 0.0])
    out.extend(stack)
    return [(o, (o.end - o.start) - child) for o, child in out]


def _union(ops: List[Op]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for o in sorted(ops, key=lambda o: o.start):
        if out and o.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], o.end)
        else:
            out.append([o.start, o.end])
    return [(a, b) for a, b in out]


def find(trace_dir: Path) -> Optional[Path]:
    hits = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True))
    return Path(hits[-1]) if hits else None


def reduce(planes) -> Optional[Trace]:
    """``planes``: an iterable of objects with ``name`` and ``lines`` (each
    with ``name`` and ``events``, each with ``name``, ``start_ns``,
    ``duration_ns``), as ``jax.profiler.ProfileData`` gives.
    None when the trace holds no step span or no device."""
    spans: List[Tuple[str, float, float]] = []
    raw: Dict[str, List[Op]] = {}
    for pl in planes:
        if DEVICE_PLANE.match(pl.name):
            for ln in pl.lines:
                if ln.name == OPS_LINE:
                    raw[pl.name] = [
                        Op(label(ev.name), ev.start_ns,
                           ev.start_ns + ev.duration_ns) for ev in ln.events]
        elif pl.name.startswith("/host"):
            for ln in pl.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    steps = [s for s in spans if s[0] == SPAN + "step"]
    if not steps or not raw:
        return None
    lo, hi = min(s[1] for s in steps), max(s[2] for s in steps)
    ops = {k: [Op(o.label, max(o.start, lo), min(o.end, hi))
               for o in v if o.end > lo and o.start < hi]
           for k, v in raw.items()}
    return Trace((lo, hi), ops, [s for s in spans if s[2] > lo and s[1] < hi])


def load(trace_dir: Path) -> Optional[Trace]:
    path = find(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(str(path)).planes)
