"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle gaps,
per-operation and per-kernel device time.

Device work is read from each TPU plane's ``XLA Ops`` line (asynchronous
copies on ``Async XLA Ops`` are not counted as busy); each operation is
named ``<program>/<instruction>`` after the ``XLA Modules`` event that holds
it.  The measured window and the harness's own spans are the ``chipbench.*``
host events that ``jax.profiler.TraceAnnotation`` wrote on the same clock.
Busy time is the union of the operation intervals inside the window; an idle
gap is a hole in that union, named by the harness span open at its midpoint.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "chipbench.window"
SPAN_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Chip:
    name: str
    busy_s: float
    gaps: List[Tuple[float, float]]  # idle intervals inside the window (ns)


@dataclasses.dataclass
class Summary:
    window_s: float
    chips: List[Chip]
    op_seconds: Dict[str, float]  # device time per <program>/<instruction>, all chips
    op_labels: Dict[str, str]  # <program>/<instruction> -> the instruction's full text
    idle_by_span: Dict[str, Tuple[int, float]]  # span -> (gaps, seconds), all chips

    def kernel_seconds(self, patterns: Sequence[str]) -> float:
        """Device seconds of the operations whose name or stats contain any of
        ``patterns``."""
        return sum(
            s for name, s in self.op_seconds.items()
            if any(p in self.op_labels[name] for p in patterns)
        )

    @property
    def busy_s(self) -> float:
        return sum(c.busy_s for c in self.chips) / len(self.chips)

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1][1])[:top]
        return {
            "device_ops": [[name, s] for name, s in ops],
            "idle_gaps": [[f"{span} ({n} gaps)", s] for span, (n, s) in gaps],
        }


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def reduce_trace(path: str, device_prefix: str = "/device:TPU:") -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    window: Optional[Tuple[float, float]] = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(SPAN_PREFIX):
                    continue
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                else:
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name[len(SPAN_PREFIX):]))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW} span")
    if not devices:
        raise ValueError(f"{path}: no {device_prefix}* plane")
    w0, w1 = window
    spans.sort()
    span_starts = [s[0] for s in spans]

    def span_at(t: float) -> str:
        # the harness's spans follow one another without nesting
        i = bisect.bisect_right(span_starts, t) - 1
        return spans[i][2] if i >= 0 and t <= spans[i][1] else "between calls"

    op_seconds: Dict[str, float] = {}
    op_labels: Dict[str, str] = {}
    idle: Dict[str, Tuple[int, float]] = {}
    chips: List[Chip] = []
    for plane in devices:
        modules = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name.split("(")[0])
            for ln in plane.lines if ln.name == MODULES_LINE for ev in ln.events
        )
        mod_starts = [m[0] for m in modules]
        intervals = []
        for line in (ln for ln in plane.lines if ln.name == OPS_LINE):
            for ev in line.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                if b <= w0 or a >= w1:
                    continue
                i = bisect.bisect_right(mod_starts, a) - 1
                prog = modules[i][2] if i >= 0 and a <= modules[i][1] else "?"
                key = f"{prog}/{ev.name.split(' = ')[0].lstrip('%')}"
                a, b = max(a, w0), min(b, w1)
                intervals.append((a, b))
                op_seconds[key] = op_seconds.get(key, 0.0) + (b - a) * 1e-9
                if key not in op_labels:
                    op_labels[key] = ev.name
        busy = _union(intervals)
        gaps = []
        prev = w0
        for a, b in busy + [(w1, w1)]:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        for a, b in gaps:
            name = span_at(0.5 * (a + b))
            n, s = idle.get(name, (0, 0.0))
            idle[name] = (n + 1, s + (b - a) * 1e-9)
        chips.append(Chip(plane.name, sum(b - a for a, b in busy) * 1e-9, gaps))
    return Summary((w1 - w0) * 1e-9, chips, op_seconds, op_labels, idle)
