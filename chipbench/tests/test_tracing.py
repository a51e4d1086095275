"""The trace reduction on a small trace recorded on a TPU v5e: a 512 x 512
bf16 matmul-and-tanh program called three times, each call inside a
``chipbench.decode`` span followed by a 2 ms ``chipbench.admit`` span, all
inside ``chipbench.window`` (``tests/data/tiny_tpu.xplane.pb``)."""

from pathlib import Path

import pytest

from chipbench import tracing

TINY = str(Path(__file__).parent / "data" / "tiny_tpu.xplane.pb")


@pytest.fixture(scope="module")
def tiny():
    return tracing.reduce_trace(TINY)


def test_window_and_chips(tiny):
    assert tiny.window_s == pytest.approx(0.010661149)
    assert [c.name for c in tiny.chips] == ["/device:TPU:0"]


def test_busy_is_the_union_of_op_intervals(tiny):
    # three calls of one fusion plus its copy-start/copy-done, no overlap
    assert tiny.busy_s == pytest.approx(sum(tiny.op_seconds.values()))
    assert set(tiny.op_seconds) == {"jit__lambda/fusion", "jit__lambda/copy-start", "jit__lambda/copy-done"}
    chip = tiny.chips[0]
    idle = sum(b - a for a, b in chip.gaps) * 1e-9
    assert idle + chip.busy_s == pytest.approx(tiny.window_s)


def test_idle_gaps_are_named_by_the_open_span(tiny):
    # the 2 ms sleeps in the admit spans hold most of the idle time
    n_admit, s_admit = tiny.idle_by_span["admit"]
    assert s_admit > 0.006 and s_admit > tiny.idle_by_span["decode"][1]
    assert sum(n for n, _ in tiny.idle_by_span.values()) == len(tiny.chips[0].gaps)


def test_kernel_seconds_matches_instruction_text(tiny):
    assert tiny.kernel_seconds(["fusion("]) == pytest.approx(tiny.op_seconds["jit__lambda/fusion"])
    assert tiny.kernel_seconds(["no_such_kernel"]) == 0.0


def test_breakdown_lists_ops_and_gaps(tiny):
    b = tiny.breakdown()
    assert b["device_ops"][0][0] == "jit__lambda/fusion"
    assert b["idle_gaps"][0][0].startswith("admit")
