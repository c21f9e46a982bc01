"""The program's spans in a trace and the engine tick they show
(``bench/spans.py``).  A synthetic trace checks the arithmetic;
``data/cpu-rehearsal-spans.xplane.pb`` (eight engine steps of a CPU
rehearsal of glm4-9b-8l at smoke size, the main thread's host events
alone) checks the reading of a real file; the older
``data/cpu-rehearsal.xplane.pb``, recorded before the program had spans,
still reduces as it did and shows no tick."""

import json
import os

import pytest

from bench import spans, tracing
from bench.tracing import Event, Span, Trace

DATA = os.path.join(os.path.dirname(__file__), "data")
WITH_SPANS = os.path.join(DATA, "cpu-rehearsal-spans.xplane.pb")
MS = 1e-6  # one ns in ms


def _trace(module_start=58):
    """One decode tick (30-170 ns) and one tick without a decode step
    (172-178) in a 200-ns window; device busy 55-95, 125-130, 140-145."""
    ops = [Event(55, 95, "fusion"), Event(125, 130, "argmax"),
           Event(140, 145, "argmax")]
    harness = [Event(0, 20, "bench.submit"), Event(20, 180, "bench.step"),
               Event(180, 200, "bench.wait")]
    program = [Span(30, 170, "engine.step", {"step_num": 1}, "L"),
               Span(31, 34, "engine.prefill_chunk", {"uid": 7}, "L"),
               Span(32, 33, "engine.fetch", {"bytes": 4}, "L"),
               Span(35, 45, "engine.publish_table", {"live": 2}, "L"),
               Span(50, 60, "engine.decode", {"B": 2, "T": 1}, "L"),
               Span(60, 100, "engine.device_wait", {}, "L"),
               Span(100, 120, "engine.fetch", {"bytes": 64}, "L"),
               Span(120, 160, "engine.sample", {"live": 2}, "L"),
               Span(172, 178, "engine.step", {"step_num": 2}, "L")]
    modules = [Event(module_start, 95, "jit__decode_impl#1")]
    return Trace(0, 200, 1, ops, modules, harness), program


def test_decode_ticks_and_time_per_tick():
    t, p = _trace()
    assert [s.args["step_num"] for s in spans.decode_ticks(t, p)] == [1]
    assert spans.per_tick_ms(t, p, "engine.step") == pytest.approx(140 * MS)
    assert spans.per_tick_ms(t, p, "engine.fetch") == pytest.approx(21 * MS)
    assert spans.per_tick_ms(t, p, "engine.fetch", parent="engine.step") == \
        pytest.approx(20 * MS)
    assert spans.per_tick_ms(t, p, "engine.sample") == pytest.approx(40 * MS)
    assert spans.mean_ms(t, p, "engine.prefill_chunk") == pytest.approx(3 * MS)
    assert spans.mean_ms(t, p, "engine.draft") is None


def test_self_time_is_the_part_no_child_covers():
    _, p = _trace()
    tick, chunk = p[0], p[1]
    assert spans.self_ms(p, tick) == pytest.approx(17 * MS)
    assert spans.self_ms(p, chunk) == pytest.approx(2 * MS)
    up = spans.parents(p)
    assert up[id(p[2])] is chunk and up[id(chunk)] is tick
    assert up[id(tick)] is None


def test_idle_goes_to_the_innermost_span_then_to_the_harness():
    t, p = _trace()
    idle = spans.idle_by_span(t, p)
    assert sum(idle.values()) * 1e9 + t.busy_s * 1e9 == pytest.approx(200)
    expect = {"bench.submit": 20, "bench.step": 14, "bench.wait": 20,
              "engine.step": 23, "engine.prefill_chunk": 2,
              "engine.fetch": 21, "engine.publish_table": 10,
              "engine.decode": 5, "engine.device_wait": 5,
              "engine.sample": 30}
    assert idle == {k: pytest.approx(v * 1e-9) for k, v in expect.items()}


def test_idle_outside_every_span_is_none():
    t, p = _trace()
    t.spans = [Event(0, 20, "bench.submit"), Event(20, 180, "bench.step")]
    t.t1 = 190  # 180-190: no harness span, no program span
    assert spans.idle_by_span(t, p)["none"] == pytest.approx(10e-9)


@pytest.mark.parametrize("start,share", [(58, 100.0), (45, 0.0), (171, 0.0)])
def test_decode_executions_start_after_their_dispatch(start, share):
    assert spans.decode_in_step_share(*_trace(start)) == share


def test_tick_of_the_synthetic_trace():
    out = spans.tick(*_trace())
    assert out["tick_host_ms"] == pytest.approx(100 * MS)
    assert out["by_span"]["engine.step.self"] == pytest.approx(17 * MS)
    assert out["by_span"]["engine.sample"] == pytest.approx(40 * MS)
    assert out["decode_ticks"] == 1 and out["decode_in_step"] == 100.0
    assert sum(out["idle_by_span"].values()) == pytest.approx(150e-9)
    assert out["idle_s"] == pytest.approx(150e-9)
    assert out["tick_fetch_ms"] == pytest.approx(20 * MS)
    assert out["tick_sample_ms"] == pytest.approx(40 * MS)
    assert out["prefill_chunk_ms"] == pytest.approx(3 * MS)


def test_recorded_rehearsal_with_program_spans():
    t, p = spans.read(WITH_SPANS)
    assert len(spans.decode_ticks(t, p)) == 8
    chunks = [s for s in p if s.name == "engine.prefill_chunk"]
    assert chunks and all({"uid", "slot", "start", "stop"} <= set(s.args)
                          for s in chunks)
    steps = [s.args["step_num"] for s in p if s.name == "engine.step"]
    assert steps == list(range(steps[0], steps[0] + 8))
    out = spans.tick(t, p)
    assert all(out[k] > 0 for k in ("tick_host_ms", "tick_fetch_ms",
                                    "tick_sample_ms", "prefill_chunk_ms"))
    assert sum(out["idle_by_span"].values()) == pytest.approx(t.window_s - t.busy_s)
    assert out["decode_in_step"] is None  # no device here


def test_program_spans_leave_the_harness_reduction_alone():
    """The harness's reduction of a trace with program spans keeps its own
    spans alone, so its idle gaps stay labelled by them."""
    t, p = spans.read(WITH_SPANS)
    assert p and all(s.name.startswith("bench.") for s in t.spans)
    assert {label for label, _ in t.idle_gaps()} <= {s.name for s in t.spans} | {"none"}


def test_trace_without_program_spans():
    """Recorded before the program had spans: the same reduction as
    before, and no tick."""
    t, p = spans.read(os.path.join(DATA, "cpu-rehearsal.xplane.pb"))
    assert (t.t0, t.t1) == (70323, 31323217)
    assert [s.name for s in t.spans].count("bench.step") == 3
    assert t.idle_gaps() == [("bench.step", pytest.approx(0.031252894))]
    assert p == []
    assert spans.tick(t, p) is None


def test_command_line(capsys):
    assert spans.main([WITH_SPANS]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["decode_ticks"] == 8 and out["tick_host_ms"] > 0
    assert spans.main([os.path.join(DATA, "cpu-rehearsal.xplane.pb")]) == 1
