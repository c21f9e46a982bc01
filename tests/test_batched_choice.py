"""The plain decode tick's batched greedy choice.

``ServingEngine._plain_step`` keeps the decode step's logits on the
device, chooses every greedy row's token in one batched argmax, and reads
back only the (B,) ids and finite flags.  Tempered rows sample their own
device row, one by one, in slot order.  These tests hold the greedy
streams to the sequential-decode reference, the tick's read to its
(B,) size, the tempered streams to their seed, and ``batched_choices`` to
the rows it counts.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models.api import get_api
from repro.serving.config import EngineConfig
from repro.serving.engine import Request, RequestState, ServingEngine

MAX_LEN = 64


@functools.lru_cache(maxsize=None)
def _model(arch):
    cfg = C.get_config(arch, smoke=True)
    return cfg, get_api(cfg).init_params(cfg, jax.random.key(0))


@pytest.fixture(scope="module")
def model():
    return _model("tinyllama-1.1b")


def _engine(model, **kw):
    cfg, params = model
    base = dict(max_len=MAX_LEN, max_batch=4, page_size=16,
                prefill_chunk=8, prefill_budget=16, seed=0)
    base.update(kw)
    return ServingEngine(cfg, params, config=EngineConfig.of(**base))


def _reqs(model, lens, max_new=6, seed=0, temperatures=None):
    cfg, _ = model
    rng = np.random.default_rng(seed)
    temps = temperatures or [0.0] * len(lens)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab, n).astype(np.int32),
                    max_new_tokens=max_new, temperature=t)
            for i, (n, t) in enumerate(zip(lens, temps))]


def _drain(eng, reqs):
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.state is RequestState.FINISHED for r in reqs)
    return [list(r.output) for r in reqs]


def _sequential(model, req):
    """Greedy prefill + decode of one request, batch 1, no engine."""
    cfg, params = model
    api = get_api(cfg)
    cache = api.init_cache(cfg, 1, MAX_LEN, jnp.dtype(cfg.compute_dtype))
    logits, cache = api.prefill(
        cfg, params, {"tokens": jnp.asarray(req.prompt)[None]}, cache)
    toks = [int(jnp.argmax(logits[0, -1]))]
    pos = len(req.prompt)
    for _ in range(req.max_new_tokens - 1):
        lg, cache = api.decode_step(
            cfg, params, cache, jnp.asarray([[toks[-1]]], jnp.int32),
            jnp.asarray([pos], jnp.int32))
        toks.append(int(jnp.argmax(lg[0, 0])))
        pos += 1
    return toks


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "granite-moe-1b-a400m"])
def test_paged_chunked_greedy_streams_equal_sequential_decode(arch):
    model = _model(arch)
    reqs = _reqs(model, (5, 20, 9, 13, 8))
    eng = _engine(model)
    out = _drain(eng, reqs)
    assert eng.stats.decode_steps > 0
    for r, got in zip(reqs, out):
        assert got == _sequential(model, r), f"request {r.uid} diverged"


def test_a_greedy_tick_reads_ids_and_flags_not_logits(model):
    cfg, _ = model
    eng = _engine(model)
    for r in _reqs(model, (5, 9, 12), max_new=10):
        eng.submit(r)
    while eng.queue or any(j is not None for j in eng.slot_prefill):
        eng.step()
    reads = []
    real = eng._to_host

    def counted(*arrays, span=True):
        reads.append(sum(a.nbytes for a in arrays))
        return real(*arrays, span=span)

    eng._to_host = counted
    eng.step()
    B = eng.max_batch
    assert reads == [B * 4 + B]
    assert B * 4 + B < B * cfg.vocab * 2


def test_a_tempered_stream_ignores_greedy_neighbours(model):
    """The tempered request, seated first, draws the same tokens whether or
    not greedy rows share its ticks, and on two engines of one seed."""
    def tempered_stream(lens):
        temps = [0.9] + [0.0] * (len(lens) - 1)
        reqs = _reqs(model, lens, max_new=8, seed=5, temperatures=temps)
        return _drain(_engine(model), reqs)[0]

    alone = tempered_stream((7,))
    first, second = tempered_stream((7, 11, 6, 9)), tempered_stream((7, 11, 6, 9))
    assert first == second == alone


def test_batched_choices_count_the_greedy_rows_only(model):
    eng = _engine(model)
    _drain(eng, _reqs(model, (5, 20, 9), max_new=7))
    assert eng.stats.decode_tokens > 0
    assert eng.stats.batched_choices == eng.stats.decode_tokens

    eng = _engine(model)
    reqs = _reqs(model, (5, 20, 9), max_new=7, temperatures=[0.0, 0.7, 0.0])
    _drain(eng, reqs)
    # the tempered request's first token comes from its prefill
    tempered_decoded = len(reqs[1].output) - 1
    assert tempered_decoded > 0
    assert (eng.stats.batched_choices
            == eng.stats.decode_tokens - tempered_decoded)
