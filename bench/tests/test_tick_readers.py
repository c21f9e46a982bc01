"""The engine tick's readers: ``tick_host_ms``, ``tick_fetch_ms`` and
``prefill_chunk_ms`` read what ``bench.spans.tick`` reads from the same
trace (``data/cpu-rehearsal-spans.xplane.pb``, through the harness's own
reduction); ``syncs_per_step`` reads the engine's counters as the harness
snapshots them around the window."""

import dataclasses
import os
import types

import pytest

from bench import run, spans, spec, tracing

DATA = os.path.join(os.path.dirname(__file__), "data")
WITH_SPANS = os.path.join(DATA, "cpu-rehearsal-spans.xplane.pb")
SPAN_READERS = ("tick_host_ms", "tick_fetch_ms", "prefill_chunk_ms")


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_span_readers_equal_the_tick(metric):
    want = spans.tick(*spans.read(WITH_SPANS))
    got = spec.reader(metric)(run.Run(trace=tracing.reduce_file(WITH_SPANS)))
    if isinstance(got, dict):
        assert got["by_span"] == want["by_span"]
        assert got["idle_by_span"] == want["idle_by_span"]
        got = got["value"]
    assert got == want[metric] and got > 0


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_span_readers_read_nothing_without_program_spans(metric):
    old = tracing.reduce_file(os.path.join(DATA, "cpu-rehearsal.xplane.pb"))
    assert old.program == []
    assert spec.reader(metric)(run.Run(trace=old)) is None
    assert spec.reader(metric)(run.Run(trace=None)) is None


def test_syncs_per_step_over_a_rehearsal_window():
    workload = spec.benchmark()["workloads"][0]["name"]
    s = run.Session(workload, 2**33 + 41, 2.0, rehearse=True)
    s.warm()
    s.seat()
    before = run._stats(s.engine)
    s.window()
    after = run._stats(s.engine)
    fields = {f.name for f in dataclasses.fields(s.engine.stats)}
    assert {"host_syncs", "decode_steps", "prefill_chunks"} <= set(before) <= fields
    steps = after["decode_steps"] - before["decode_steps"]
    assert steps > 0
    got = spec.reader("syncs_per_step")(s.run_record())
    assert got == (after["host_syncs"] - before["host_syncs"]) / steps
    assert got >= 1.0  # each decode tick reads its result once


def test_a_counter_the_program_lacks_reads_nothing():
    @dataclasses.dataclass
    class Stats:  # a program's stats without ``host_syncs``
        decode_steps: int = 0
        decode_tokens: int = 0
        mean: float = 0.0

    before = run._stats(types.SimpleNamespace(stats=Stats(3, 24, 8.0)))
    after = run._stats(types.SimpleNamespace(stats=Stats(9, 72, 8.0)))
    assert before == {"decode_steps": 3, "decode_tokens": 24}
    assert spec.reader("syncs_per_step")(run.Run(stats0=before, stats1=after)) is None
