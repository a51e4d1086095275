"""Metric readers: ``<name>.py`` holds ``read(results)`` for the metric of
that name in ``BENCHMARK.json``.  A reader that finds nothing to read returns
None and the metric is left out of the result line.  Helpers shared by the
readers live here."""

from __future__ import annotations

from typing import List


def in_window(res, t: float) -> bool:
    return res.window.t_open <= t <= res.window.t_close


def spans(res, name: str) -> List:
    """The harness's ``name`` spans that ended inside the window."""
    return [s for s in res.window.spans if s.name == name and in_window(res, s.t1)]


def clipped_seconds(res, names) -> float:
    """Seconds of the named spans that fall inside the window."""
    w = res.window
    return sum(
        max(0.0, min(s.t1, w.t_close) - max(s.t0, w.t_open))
        for s in w.spans if s.name in names
    )
