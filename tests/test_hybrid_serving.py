"""A hybrid stack of Mamba-2 and attention layers served through the paged
engine with chunked prefill (granite-4.0-h smoke: two periods of
[mamba, global]).

The engine's logits are compared with the model's full forward pass over
each prompt and its served tokens.  The Mamba parameters are set for a long
memory (dt * |A| about 0.01), so that a state carried wrongly from one
chunk to the next, from prefill to decode, or from a slot's previous
occupant moves later logits well past the tolerance (the last test shows
by how much)."""

import collections
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import transformer as T
from repro.models.api import get_api
from repro.serving.config import EngineConfig
from repro.serving.engine import Request, ServingEngine
from repro.serving.scheduler import chunk_spans

CFG = C.get_config("granite-4.0-h-micro", smoke=True)
CHUNK = 16
PROMPTS = (5, 16, 37, 50)
NEW = 9  # the prefill's token and 8 decode steps
# float32 model, float KV: the served path and the forward pass differ by
# the order of float32 sums (SSD scan per chunk vs per sequence, the decode
# kernel vs the scan, paged vs whole-sequence attention): 3.6e-7 measured
TOL = 2e-5


def _long_memory(params):
    def fix(group):
        if "mamba" not in group:
            return group
        m = dict(group["mamba"])
        m["A_log"] = jnp.zeros_like(m["A_log"])
        m["dt_bias"] = jnp.full_like(m["dt_bias"], float(np.log(np.expm1(0.01))))
        return {**group, "mamba": m}

    return {**params, "unit": [fix(g) for g in params["unit"]],
            "rem": [fix(g) for g in params["rem"]]}


@pytest.fixture(scope="module")
def params():
    return _long_memory(get_api(CFG).init_params(CFG, jax.random.key(0)))


def _prompts(seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab, n).astype(np.int32) for n in PROMPTS]


class _Recording(ServingEngine):
    """Keeps, per request, the logits row each of its tokens came from."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.rows = collections.defaultdict(list)
        self._live = None

    def _finish_prefill_job(self, slot, job):
        self.rows[job.req.uid].append(np.asarray(job.last_row[0]))
        super()._finish_prefill_job(slot, job)

    def _plain_step(self, live):
        self._live = {s: self.slot_req[s].uid for s in live}
        return super()._plain_step(live)

    def _sample(self, logits, temperature):
        if self._live is not None and logits.ndim == 3:
            block = np.asarray(logits[:, 0])
            for slot, uid in self._live.items():
                self.rows[uid].append(block[slot])
            self._live = None
        return super()._sample(logits, temperature)


def _engine(params, max_batch):
    return _Recording(CFG, params, config=EngineConfig.of(
        max_len=64, max_batch=max_batch, page_size=8, prefill_chunk=CHUNK,
        prefill_budget=32))


def _forward_rows(params, req):
    toks = np.concatenate([req.prompt, np.asarray(req.output[:-1], np.int32)])
    ref, _ = jax.jit(lambda t: T.forward(CFG, params, t))(jnp.asarray(toks)[None])
    return np.asarray(ref[0, len(req.prompt) - 1:])


@pytest.fixture(scope="module")
def served(params):
    """Each prompt alone through one slot, in turn (the slot is reused),
    and all four at once (rows at different positions in every step)."""
    alone = _engine(params, 1)
    reqs_alone = [Request(uid=i, prompt=p, max_new_tokens=NEW)
                  for i, p in enumerate(_prompts())]
    for r in reqs_alone:
        alone.submit(r)
        alone.run_until_done()
    batch = _engine(params, 4)
    reqs_batch = [Request(uid=i, prompt=p, max_new_tokens=NEW)
                  for i, p in enumerate(_prompts())]
    for r in reqs_batch:
        batch.submit(r)
    batch.run_until_done()
    return alone, reqs_alone, batch, reqs_batch


@pytest.mark.parametrize("i", range(len(PROMPTS)), ids=[f"S{n}" for n in PROMPTS])
def test_served_logits_equal_the_forward_pass(params, served, i):
    alone, reqs_alone, batch, reqs_batch = served
    for engine, reqs in ((alone, reqs_alone), (batch, reqs_batch)):
        req = reqs[i]
        assert req.state.value == "FINISHED" and len(req.output) == NEW
        rows = np.stack(engine.rows[req.uid])
        assert rows.shape == (NEW, CFG.vocab)
        np.testing.assert_allclose(rows, _forward_rows(params, req), atol=TOL,
                                   rtol=0)


def test_a_reused_slot_serves_as_a_fresh_one(served):
    """The one-slot engine served prompts 2-4 in a slot that held the
    previous request's state; the batch engine gave each a fresh slot."""
    alone, reqs_alone, batch, reqs_batch = served
    for a, b in zip(reqs_alone[1:], reqs_batch[1:]):
        assert a.output == b.output
        np.testing.assert_allclose(np.stack(alone.rows[a.uid]),
                                   np.stack(batch.rows[b.uid]), atol=TOL, rtol=0)


def test_every_prefill_inserts_its_state_once(served):
    alone, _, batch, _ = served
    for engine in (alone, batch):
        assert engine.stats.state_inserts == engine.stats.prefills == len(PROMPTS)
        # whole padded chunks: one chunk program for every prompt length
        assert engine.stats.prefill_chunks == sum(-(-n // CHUNK) for n in PROMPTS)


def test_the_state_carries_memory_across_chunks(params):
    """Zeroing the carried state at the chunk boundary moves the last
    logits of a 37-token prompt by far more than the tolerance: the
    parity above would catch a broken carry."""
    api = get_api(CFG)
    toks = jnp.asarray(_prompts()[2])[None]
    step = jax.jit(lambda c, t, p: api.decode_step(CFG, params, c, t, p))
    results = []
    for zero in (False, True):
        cache = api.init_cache(CFG, 1, 64, jnp.float32)
        for start, stop in chunk_spans(toks.shape[1], CHUNK, carry_state=True):
            if zero and start == CHUNK:
                cache = {**cache, "ssm": jnp.zeros_like(cache["ssm"])}
            span = jnp.pad(toks[:, start:stop], ((0, 0), (0, CHUNK - (stop - start))),
                           constant_values=-1)
            logits, cache = step(cache, span, jnp.asarray([start], jnp.int32))
        results.append(np.asarray(logits[0, stop - start - 1]))
    ref, _ = T.forward(CFG, params, toks)
    np.testing.assert_allclose(results[0], np.asarray(ref[0, -1]), atol=TOL, rtol=0)
    assert np.abs(results[1] - np.asarray(ref[0, -1])).max() >= 10 * TOL


def test_carried_state_spans_do_not_overlap():
    for S in (1, 5, 16, 17, 37, 50, 64):
        spans = chunk_spans(S, CHUNK, carry_state=True)
        assert spans[0][0] == 0 and spans[-1][1] == S
        assert all(b == c for (_, b), (c, _) in zip(spans, spans[1:]))
        assert all(a % CHUNK == 0 and 0 < b - a <= CHUNK for a, b in spans)


def test_speculation_and_prefix_sharing_are_refused(params):
    with pytest.warns(UserWarning, match="speculative decode needs"):
        spec = ServingEngine(CFG, params, config=EngineConfig.of(
            max_len=64, max_batch=2, page_size=8, prefill_chunk=CHUNK,
            spec_k=2, draft_cfg=CFG, draft_params=params))
    assert spec.spec_k == 0 and not spec.spec_active
    with pytest.warns(UserWarning, match="prefix sharing needs"):
        shared = ServingEngine(CFG, params, config=EngineConfig.of(
            max_len=64, max_batch=2, page_size=8, prefill_chunk=CHUNK,
            share_prefix=True))
    assert shared.registry is None
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # chunked prefill is allowed, silently
        plain = ServingEngine(CFG, params, config=EngineConfig.of(
            max_len=60, max_batch=2, page_size=8, prefill_chunk=CHUNK))
    assert plain.prefill_chunk == CHUNK and plain.max_len == 64
