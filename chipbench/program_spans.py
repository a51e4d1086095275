"""The program's own spans in a traced run: the ``janus.*`` host events that
the engine's ``jax.profiler.TraceAnnotation``s wrote on the device trace's
clock, with their keyword arguments (event stats).

The readers of ``decode_host_ms``, ``decode_device_ms`` and
``prefill_chunk_ms`` take them from here.  The trace is the one ``cell.py``
recorded for the run (``cell.TRACE_DIR/<workload>``); it is read once per
run.  A program without such spans yields none, and those readers then
report nothing.

``gap_names`` is the rule for naming an idle gap after the engine phase open
in it (``decode > janus.decode.advance``); the trace reduction in
``tracing.py`` still names gaps by the harness span alone.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

PREFIX = "janus."

HarnessSpan = Tuple[float, float, str]  # start (ns), end (ns), name after "chipbench."


@dataclasses.dataclass
class ProgramSpan:
    name: str  # janus.<phase>
    start_ns: float
    end_ns: float
    stats: Dict[str, object]  # the span's keyword arguments

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


def host_spans(pd) -> Tuple[Optional[Tuple[float, float]], List[HarnessSpan], List[ProgramSpan]]:
    """From the host planes of a ``ProfileData``: the window (None if the
    trace has none), the harness's spans and the program's spans inside the
    window, each list sorted by start."""
    from chipbench.tracing import SPAN_PREFIX, WINDOW

    spans: List[HarnessSpan] = []
    program: List[ProgramSpan] = []
    window: Optional[Tuple[float, float]] = None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(PREFIX):
                    program.append(ProgramSpan(
                        name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats)))
                elif name.startswith(SPAN_PREFIX):
                    a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                    if name == WINDOW:
                        window = (a, b)
                    else:
                        spans.append((a, b, name[len(SPAN_PREFIX):]))
    if window is not None:
        program = [s for s in program if window[0] <= s.start_ns and s.end_ns <= window[1]]
    program.sort(key=lambda s: (s.start_ns, -s.end_ns))
    return window, sorted(spans), program


def of_run(res) -> List[ProgramSpan]:
    """The program spans inside the window of the run ``res`` describes
    (``cell.Results`` of a traced run), read from its trace once."""
    if "program_spans" not in vars(res):
        from jax.profiler import ProfileData

        from chipbench import cell, tracing

        path = tracing.find_xplane(str(cell.TRACE_DIR / res.workload["name"]))
        res.program_spans = host_spans(ProfileData.from_file(path))[2]
    return res.program_spans


def named(res, name: str) -> List[ProgramSpan]:
    """The run's program spans called ``name``, in order; none where the run
    was not traced."""
    if res.trace is None:
        return []
    return [s for s in of_run(res) if s.name == name]


def gap_names(
    times: Sequence[float], spans: Sequence[HarnessSpan], program: Sequence[ProgramSpan]
) -> List[str]:
    """The name of an idle gap at each of the ascending ``times``: the
    harness span open there ("between calls" where none is), then, where a
    program span is open too, `` > `` and the innermost one.  ``spans`` are
    sorted and follow one another; ``program`` spans come from one thread,
    sorted by start, each either holding the next or ending before it
    starts."""
    span_starts = [s[0] for s in spans]
    out: List[str] = []
    stack: List[ProgramSpan] = []
    i = 0
    for t in times:
        k = bisect.bisect_right(span_starts, t) - 1
        name = spans[k][2] if k >= 0 and t <= spans[k][1] else "between calls"
        while i < len(program) and program[i].start_ns <= t:
            while stack and stack[-1].end_ns < program[i].start_ns:
                stack.pop()
            stack.append(program[i])
            i += 1
        while stack and stack[-1].end_ns < t:
            stack.pop()
        out.append(f"{name} > {stack[-1].name}" if stack else name)
    return out
