"""Spans and the host-sync counter of ``ServingEngine.step``.

The engine marks each phase of its tick with ``jax.profiler``
annotations (``engine.step`` and the spans nested in it) and counts every
blocking device->host read in ``EngineStats.host_syncs``.  These tests
read both on the CPU at smoke size: the count per decode tick and per
prefill chunk, the spans of a recorded trace and their nesting, the
request id on each chunk, and that tracing changes no token and no
counter.
"""

import dataclasses
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.configs as C
from repro.models.api import get_api
from repro.serving.config import EngineConfig
from repro.serving.engine import Request, RequestState, ServingEngine
from repro.serving.scheduler import chunk_spans

TICK_CHILDREN = {"engine.publish_table", "engine.decode",
                 "engine.device_wait", "engine.fetch", "engine.sample"}


@pytest.fixture(scope="module")
def model():
    cfg = C.get_config("tinyllama-1.1b", smoke=True)
    return cfg, get_api(cfg).init_params(cfg, jax.random.key(0))


def _engine(model, **kw):
    cfg, params = model
    base = dict(max_len=96, max_batch=4, page_size=16)
    base.update(kw)
    return ServingEngine(cfg, params, config=EngineConfig.of(**base))


def _reqs(model, lens, max_new=8, seed=0):
    cfg, _ = model
    rng = np.random.default_rng(seed)
    return [Request(uid=100 + i,
                    prompt=rng.integers(0, cfg.vocab, n).astype(np.int32),
                    max_new_tokens=max_new)
            for i, n in enumerate(lens)]


def _drain(eng, reqs):
    for r in reqs:
        eng.submit(r)
    for _ in range(200):
        if not eng.queue and not eng._live_slots():
            break
        eng.step()
    assert all(r.state is RequestState.FINISHED for r in reqs)
    return [list(r.output) for r in reqs]


def _engine_spans(trace_dir):
    """(name, start_ns, end_ns, args) of every ``engine.*`` host event."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    return out


@pytest.mark.parametrize("tempered", [0, 1, 3])
@pytest.mark.parametrize("chunked", [False, True])
def test_a_decode_tick_syncs_once_plus_once_per_live_row(model, chunked,
                                                         tempered):
    """A plain tick reads the greedy rows' ids and every row's finite flag
    in one sync; each tempered row adds the read of its own sample."""
    kw = dict(prefill_chunk=8, prefill_budget=64) if chunked else {}
    eng = _engine(model, **kw)
    reqs = _reqs(model, (5, 9, 12), max_new=10)
    for r in reqs[:tempered]:
        r.temperature = 0.8
    for r in reqs:
        eng.submit(r)
    while eng.queue or any(j is not None for j in eng.slot_prefill):
        eng.step()
    live = eng._decoding_slots()
    assert len(live) == 3
    before = eng.stats.host_syncs
    eng.step()
    assert eng.stats.host_syncs - before == 1 + tempered


def test_a_prefill_chunk_syncs_once(model):
    eng = _engine(model, prefill_chunk=8, prefill_budget=8)
    eng.submit(_reqs(model, (30,))[0])
    for n in (1, 2, 3):  # three of the prompt's four chunks, none the last
        eng.step()
        assert eng.stats.prefill_chunks == n
        assert eng.stats.host_syncs == n
    # the last chunk, the first token's sample, then the request's first
    # decode step in the same tick: its ids and flags in one read
    eng.step()
    assert eng.stats.prefill_chunks == 4 and eng.stats.decode_steps == 1
    assert eng.stats.host_syncs == 3 + 1 + 1 + 1


def test_a_synchronous_prefill_syncs_once(model):
    eng = _engine(model)
    eng.submit(_reqs(model, (7,), max_new=1)[0])
    eng.step()  # admission samples the only token: no decode step runs
    assert eng.stats.prefills == 1 and eng.stats.decode_steps == 0
    assert eng.stats.host_syncs == 1


def test_profiled_ticks_nest_their_phases_in_engine_step(model, tmp_path):
    eng = _engine(model)
    for r in _reqs(model, (5, 9), max_new=10):
        eng.submit(r)
    eng.step()  # admission
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            eng.step()
    spans = _engine_spans(str(tmp_path))
    steps = [s for s in spans if s[0] == "engine.step"]
    assert [s[3]["step_num"] for s in steps] == [2, 3, 4]
    for name, start, end, _ in steps:
        inside = {n for n, a, b, _ in spans
                  if n != "engine.step" and start <= a and b <= end}
        assert inside >= TICK_CHILDREN
    decodes = [s for s in spans if s[0] == "engine.decode"]
    assert [(s[3]["B"], s[3]["T"]) for s in decodes] == [(4, 1)] * 3
    fetch = [s for s in spans if s[0] == "engine.fetch"]
    assert all(s[3]["bytes"] > 0 for s in fetch)
    assert [s[3]["live"] for s in spans if s[0] == "engine.sample"] == [2] * 3


def test_each_prefill_chunk_carries_its_request_id(model, tmp_path):
    eng = _engine(model, prefill_chunk=8, prefill_budget=16)
    reqs = _reqs(model, (20, 11, 30), max_new=3)
    with jax.profiler.trace(str(tmp_path)):
        _drain(eng, reqs)
    chunks = [s[3] for s in _engine_spans(str(tmp_path))
              if s[0] == "engine.prefill_chunk"]
    assert len(chunks) == eng.stats.prefill_chunks
    for r in reqs:
        mine = [(c["start"], c["stop"]) for c in chunks if c["uid"] == r.uid]
        assert mine == chunk_spans(len(r.prompt), 8)
    finishes = [s[3] for s in _engine_spans(str(tmp_path))
                if s[0] == "engine.prefill_finish"]
    assert sorted(f["uid"] for f in finishes) == [r.uid for r in reqs]


def test_tracing_changes_no_token_and_no_counter(model, tmp_path):
    kw = dict(prefill_chunk=8, prefill_budget=16)
    lens = (20, 6, 13)
    plain = _engine(model, **kw)
    out_plain = _drain(plain, _reqs(model, lens, seed=3))
    traced = _engine(model, **kw)
    with jax.profiler.trace(str(tmp_path)):
        out_traced = _drain(traced, _reqs(model, lens, seed=3))
    assert out_traced == out_plain
    assert dataclasses.asdict(traced.stats) == dataclasses.asdict(plain.stats)
    assert plain.stats.host_syncs > 0
