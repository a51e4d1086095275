"""The program's ``janus.*`` spans in a traced run: reading them from a trace
(``program_spans.host_spans``), the rule that names an idle gap after the
innermost one (``gap_names``), and the readers of ``decode_host_ms``,
``decode_device_ms`` and ``prefill_chunk_ms``."""

import types

import jax
import pytest

from chipbench import program_spans, tracing
from chipbench.metrics import decode_device_ms, decode_host_ms, prefill_chunk_ms

MS = 1e6  # ns


def span(name, a_ms, b_ms, **stats):
    return program_spans.ProgramSpan(name, a_ms * MS, b_ms * MS, stats)


# two decode iterations and one admission, in ns
PROGRAM = [
    span("janus.decode", 0, 100, step=0, active=2),
    span("janus.decode.prepare", 5, 10, dirty=1),
    span("janus.decode.device", 10, 60),
    span("janus.decode.sample", 60, 65),
    span("janus.decode.advance", 70, 95, completed=0),
    span("janus.admit", 120, 190, rid=7, slot=1, prompt_len=64),
    span("janus.prefill.chunk", 125, 145, rid=7, lo=0, n=64),
    span("janus.prefill.handoff", 150, 160, slot=1, lo=0, n=64),
    span("janus.decode", 200, 300, step=1, active=2),
    span("janus.decode.device", 210, 280),
]
HARNESS = [(0, 100 * MS, "decode"), (110 * MS, 195 * MS, "admit"), (200 * MS, 300 * MS, "decode")]


def test_gaps_without_program_spans_keep_the_harness_name():
    times = [t * MS for t in (2, 50, 105, 130, 250, 350)]
    assert program_spans.gap_names(times, HARNESS, []) == [
        "decode", "decode", "between calls", "admit", "decode", "between calls"]


def test_gaps_take_the_innermost_program_span():
    times = [t * MS for t in (2, 7, 30, 67, 80, 99, 105, 115, 130, 155, 170, 205, 250, 290, 350)]
    assert program_spans.gap_names(times, HARNESS, PROGRAM) == [
        "decode > janus.decode",
        "decode > janus.decode.prepare",
        "decode > janus.decode.device",
        "decode > janus.decode",
        "decode > janus.decode.advance",
        "decode > janus.decode",
        "between calls",
        "admit",
        "admit > janus.prefill.chunk",
        "admit > janus.prefill.handoff",
        "admit > janus.admit",
        "decode > janus.decode",
        "decode > janus.decode.device",
        "decode > janus.decode",
        "between calls",
    ]


def test_phase_names_split_the_harness_name_and_nothing_else():
    times = [t * MS + 0.5 for t in range(0, 360, 3)]
    plain = program_spans.gap_names(times, HARNESS, [])
    split = program_spans.gap_names(times, HARNESS, PROGRAM)
    assert [s.split(" > ")[0] for s in split] == plain


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A trace recorded on the CPU, as the harness records one: a window
    holding a harness decode span and a program decode iteration, and one
    program span after the window."""
    d = tmp_path_factory.mktemp("cpu_trace")
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(d), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("chipbench.window"):
            with jax.profiler.TraceAnnotation("chipbench.decode"):
                with jax.profiler.TraceAnnotation("janus.decode", step=3, active=2):
                    with jax.profiler.TraceAnnotation("janus.decode.device"):
                        jax.numpy.ones(8).sum().block_until_ready()
                    with jax.profiler.TraceAnnotation("janus.decode.advance") as adv:
                        adv.set_metadata(completed=1)
        with jax.profiler.TraceAnnotation("janus.decode", step=4, active=2):
            pass
    finally:
        jax.profiler.stop_trace()
    return tracing.find_xplane(str(d))


def test_cpu_trace_program_spans(cpu_trace):
    from jax.profiler import ProfileData

    window, spans, program = program_spans.host_spans(ProfileData.from_file(cpu_trace))
    assert window is not None
    assert [s[2] for s in spans] == ["decode"]
    # the span after the window is left out
    assert [p.name for p in program] == ["janus.decode", "janus.decode.device", "janus.decode.advance"]
    assert program[0].stats == {"step": 3, "active": 2}
    assert program[2].stats == {"completed": 1}
    assert all(window[0] <= p.start_ns <= p.end_ns <= window[1] for p in program)
    assert program[0].start_ns <= program[1].start_ns and program[1].end_ns <= program[0].end_ns


def test_cpu_trace_has_no_tpu_plane(cpu_trace):
    # the device reduction refuses a trace without the chip's plane
    with pytest.raises(ValueError, match="TPU"):
        tracing.reduce_trace(cpu_trace)


def test_a_run_reads_its_own_trace_once(cpu_trace, tmp_path, monkeypatch):
    from chipbench import cell

    run_dir = tmp_path / "dsv2-decode"
    run_dir.mkdir()
    (run_dir / "t.xplane.pb").write_bytes(open(cpu_trace, "rb").read())
    monkeypatch.setattr(cell, "TRACE_DIR", tmp_path)
    res = types.SimpleNamespace(workload={"name": "dsv2-decode"}, trace=object())
    assert [s.name for s in program_spans.named(res, "janus.decode")] == ["janus.decode"]
    (run_dir / "t.xplane.pb").unlink()  # read once: the spans stay with the run
    assert len(program_spans.named(res, "janus.decode.device")) == 1


def results(program):
    return types.SimpleNamespace(trace=object(), program_spans=program)


def test_decode_host_ms_is_parent_less_device_child():
    # (100 - 50) and (100 - 70) ms
    assert decode_host_ms.read(results(PROGRAM)) == pytest.approx(40.0)
    assert decode_device_ms.read(results(PROGRAM)) == pytest.approx(60.0)
    mean_call = 1e3 * sum(s.seconds for s in PROGRAM if s.name == "janus.decode") / 2
    assert decode_host_ms.read(results(PROGRAM)) + decode_device_ms.read(results(PROGRAM)) == \
        pytest.approx(mean_call)


def test_prefill_chunk_ms_is_the_mean_chunk():
    more = PROGRAM + [span("janus.prefill.chunk", 400, 440, rid=8, lo=0, n=64)]
    assert prefill_chunk_ms.read(results(more)) == pytest.approx(30.0)


@pytest.mark.parametrize("reader", [decode_host_ms, decode_device_ms, prefill_chunk_ms])
def test_readers_find_nothing_without_program_spans(reader):
    # a program without spans, or an untraced run, leaves the metric out
    assert reader.read(results([])) is None
    assert reader.read(types.SimpleNamespace(trace=None)) is None
