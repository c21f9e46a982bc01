"""Batched serving engine with continuous batching (the paper's batch
processing at the request level).

The engine keeps a fixed pool of `max_batch` decode slots backed by one
static KV cache (static shapes => one compiled decode step).  Requests
join free slots (prefill writes their KV into the slot), every engine tick
runs ONE decode step for all live slots — each streamed weight byte is
reused `live` times, which is exactly the paper's batch-processing reuse —
and finished sequences free their slots immediately (continuous batching:
no head-of-line blocking on long generations).

``BatchSizer`` (core/batching.py) picks max_batch at the machine-balance
point n_opt unless the caller overrides it, tying the serving layer to the
paper's throughput model.

``params`` may be a *compressed* pytree from ``core.weight_plan.compress``
(int8 and/or block-sparse weights): every model matmul routes through the
plan dispatch, so prefill and the one compiled decode step serve pruned +
quantized weights unchanged.  Passing the ``plan`` corrects the sizer's
machine-balance point for the shrunken weight stream — the paper's
combined-optimization claim (batching x pruning) at the engine level.

Paged KV cache (``page_size=...``)
----------------------------------
The contiguous cache reserves ``max_len`` tokens per slot, so pool bytes =
``max_batch * max_len * kv_bytes_per_token`` even when requests are short —
after the weight stream is compressed (PR 1/2) this reservation is the
per-sequence cost that caps the batch.  Paged mode replaces it with a
global pool of ``num_pages`` fixed-size pages per attention layer plus an
int32 page table; sequences are charged for the pages they actually use
(``ceil((S + max_new) / page_size)``), allocated at admission and freed at
completion, so the same pool bytes sustain ``max_len / mean_context`` times
more concurrent sequences and the sizer's kv term is charged at the
*actual* expected context (``expected_context=...``) rather than max_len.

Page-table ownership rules (see ``serving/paged.py``):

* the host-side engine is the ONLY allocator/writer of the table; the
  compiled decode step reads it (and scatters the new token's K/V through
  it) but never changes the mapping;
* physical page 0 is the null page: free slots map there so dead-slot
  scatters in the always-full-batch decode step are harmless;
* a page with refcount > 1 (prefix-shared) is read-only; every write goes
  through ``_ensure_private`` which copies it first (copy-on-write).

Sharded serving (``mesh=...``)
------------------------------
Passing a mesh (plus optional rule overrides) serves the same plan sharded:
params and caches are placed ONCE through the axis-rules registry
(``distributed/shardlib``) — dense weights by their logical axes, packed
blocks/scales on the output-feature axis with walks replicated, int8 KV
scale leaves alongside their payloads, page pools over the model axis on
``kv_heads`` — and both compiled steps trace under ``use_mesh`` so the
in-step ``shard_pinned`` constraints resolve against the same rules.  The
page table and the allocator remain host-side per replica (every chip of a
model group reads the identical mapping).  The sizer's balance point
divides the weight stream by the model-parallel degree and the kv term by
the degree the cache leaves *actually* shard by (``shardlib.shard_degree``
— 1 when divisibility drops the mapping, e.g. whisper-tiny's 6 heads on a
16-way model axis).

Speculative decode (``draft_cfg=..., draft_params=..., spec_k=k``)
-------------------------------------------------------------------
A small draft model proposes k tokens per tick (k cheap single-token
steps), and the target verifies all k+1 positions in ONE multi-token
decode step — draft positions are extra samples of the paper's batch
processing: one pass of the target's (compressed) weight stream serves
``live * (k+1)`` rows instead of ``live``, so a latency-capped engine
reaches the machine-balance point with (k+1)x fewer concurrent sequences
(``perf_model.spec_decode_n_opt``).  The accepted prefix commits under
standard rejection sampling (greedy degenerates to longest argmax-prefix
match, so greedy committed streams are identical to the non-speculative
engine's); every tick commits at least the one resampled token.

Rollback is free by construction — no cache snapshot, no undo scatter:
every tick writes positions [frontier, frontier + k], the frontier
advances by >= 1, so one tick's rejected tail (<= k entries) always lies
inside the next tick's write range; between ticks the absolute-position
masks in ``models/layers.decode_attention`` (and the paged kernel) keep
stale entries invisible.  This is why speculation is gated on
positionally-addressed caches (``api.supports_spec_decode``): attention
KV — contiguous ring (sliding windows get ``window + k`` rings), int8,
paged, sharded — qualifies; O(1) recurrent/xLSTM integrator states do
not.

Prefix sharing (``share_prefix=True``) maps the *full* pages of a common
prompt prefix (same system prompt, speculative drafts) into the new
sequence's table with a refcount bump — one physical copy serves every
concurrent reader.  The partially-filled boundary page is copied at
admission (eager COW: the new sequence is about to write into it), so a
donor never sees its writable tail page shared and decode-time COW is a
defended-against invariant rather than a steady-state cost.  Admission
under pool exhaustion queues (back-pressure) instead of crashing.

Continuous batching (``prefill_chunk=C`` / ``prefill_budget=T``)
-----------------------------------------------------------------
Synchronous admission runs a whole prompt's prefill inline, stalling
every decoding neighbor for the full prompt — head-of-line blocking that
caps the weight reuse the batch exists for.  With ``prefill_chunk`` set,
admission only reserves the slot; the prompt then advances at most
``prefill_budget`` tokens per tick (FIFO across in-flight prefills, no
overtaking) as ``(1, C)`` multi-token decode steps on a private batch-1
cache, interleaved with the batched decode step — decode ticks continue
while long prompts stream in, and tokens reach the caller per-request the
tick they commit (``Request.on_token``).  In paged mode the slot's pages
grow chunk by chunk but its published table row stays all-NULL until the
DECODING transition, so batched-decode scatters from a prefilling slot
are absorbed by the null page (docs/memory_model.md).  Chunked prefill
is bit-exact versus the one-shot prefill (causal attention over a prefix
is a pure function of tokens/positions/params, and the ragged tail is
covered by an overlapped — identically recomputed — final chunk:
serving/scheduler.py), so greedy streams match the synchronous engine
token for token.  ``serving/loadgen.py`` drives the engine under seeded
open-loop arrival traces and reports TTFT / latency percentiles.

Failure model (``request_timeout_s`` / ``evict_policy`` / ...)
---------------------------------------------------------------
One misbehaving request in a shared batch threatens every neighbor's
throughput — the blast-radius concern of any shared-state engine.  The
engine therefore runs an explicit per-request state machine
(``RequestState``: QUEUED → PREFILLING → DECODING → {FINISHED, FAILED,
EVICTED, TIMED_OUT}) with TTFT and total-latency deadlines enforced every
tick, bounded retry-with-backoff on transient faults, priority-based
preemption-safe eviction (snapshot committed tokens, free pages
refcount-correctly, re-admit by prefill-from-prefix), a device-side
``isfinite`` guard folded into the compiled decode step that quarantines a
NaN-poisoned slot instead of letting it poison the batch, and a
degradation ladder (speculative → plain decode; Pallas paged kernel →
pure-JAX reference attention).  ``serving/faultinject.py`` drives every
rung deterministically.  See docs/architecture.md § "Failure model".
"""

from __future__ import annotations

import dataclasses
import enum
import functools
import math
import time
from collections import deque
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.core.batching import BatchSizer
from repro.distributed import shardlib as sl
from repro.distributed.fault import HeartbeatMonitor
from repro.models.api import (
    carries_state,
    get_api,
    kv_bytes_per_token,
    supports_int8_kv,
    supports_paged_kv,
)
from repro.models.layers import finite_rows, write_pages
from repro.serving.config import (
    EngineConfig,
    chunked_prefill_gate,
    positional_state_gate,
)
from repro.serving.faultinject import FaultInjected
from repro.serving.paged import (
    NULL_PAGE,
    PageAllocator,
    PageAuditError,
    PoolExhausted,
    PrefixRegistry,
)
from repro.serving.scheduler import PrefillJob, TickBudget, chunk_spans

# paged pool leaf -> its name in a contiguous (prefill) cache
_PAGED_KEYS = (
    ("k_pages", "k"),
    ("v_pages", "v"),
    ("k_scale_pages", "k_scale"),
    ("v_scale_pages", "v_scale"),
)


class RequestState(enum.Enum):
    """Request lifecycle states.  QUEUED → PREFILLING → DECODING is the
    happy path; FINISHED / FAILED / TIMED_OUT are terminal; EVICTED is the
    snapshot-and-requeue detour (the request re-enters PREFILLING via
    prefill-from-prefix, its committed tokens replayed as prompt)."""

    QUEUED = "QUEUED"
    PREFILLING = "PREFILLING"
    DECODING = "DECODING"
    FINISHED = "FINISHED"
    FAILED = "FAILED"
    EVICTED = "EVICTED"
    TIMED_OUT = "TIMED_OUT"


TERMINAL_STATES = frozenset(
    {RequestState.FINISHED, RequestState.FAILED, RequestState.TIMED_OUT})

# legal transitions — anything else is an engine bug and raises loudly
# (a silently-wrong lifecycle is exactly the failure mode this machine
# exists to prevent).  QUEUED re-entry from PREFILLING/DECODING is the
# bounded-retry path; EVICTED re-enters PREFILLING at readmission.
_TRANSITIONS = {
    RequestState.QUEUED: {RequestState.PREFILLING, RequestState.TIMED_OUT,
                          RequestState.FAILED},
    # PREFILLING → EVICTED: under continuous batching a chunked prefill
    # spans ticks and occupies a slot, so priority preemption can land on
    # it mid-prefill (nothing committed yet: readmission recomputes).
    RequestState.PREFILLING: {RequestState.DECODING, RequestState.QUEUED,
                              RequestState.FAILED, RequestState.TIMED_OUT,
                              RequestState.EVICTED},
    RequestState.DECODING: {RequestState.FINISHED, RequestState.FAILED,
                            RequestState.EVICTED, RequestState.TIMED_OUT,
                            RequestState.QUEUED},
    RequestState.EVICTED: {RequestState.PREFILLING, RequestState.QUEUED,
                           RequestState.TIMED_OUT, RequestState.FAILED},
    RequestState.FINISHED: frozenset(),
    RequestState.FAILED: frozenset(),
    RequestState.TIMED_OUT: frozenset(),
}


class InvalidTransition(RuntimeError):
    """An engine bug drove a request through an illegal lifecycle edge."""


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int
    temperature: float = 0.0
    extras: Optional[dict] = None  # patches / frames for VLM / audio
    # failure-model knobs (per request; engine-level defaults apply when
    # None): priority orders preemption under evict_policy="priority",
    # deadlines are budgets relative to submit time on the engine clock.
    priority: int = 0
    ttft_deadline_s: Optional[float] = None  # queue-to-first-token budget
    deadline_s: Optional[float] = None  # total-latency budget
    # streaming: called as on_token(request, token) the tick each token
    # commits (first token included) — continuous-serving consumers read
    # streams, not end-of-run transcripts.  Callbacks run on the engine
    # thread and must not raise.
    on_token: Optional[Callable[["Request", int], None]] = None
    # filled by the engine:
    output: Optional[List[int]] = None
    done: bool = False
    state: RequestState = RequestState.QUEUED
    error: Optional[str] = None
    retries: int = 0  # transient-failure retries consumed
    evictions: int = 0  # preemptions survived (do not consume retries)
    submit_t: Optional[float] = None
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    not_before: float = 0.0  # retry backoff gate (engine-clock time)
    history: List[RequestState] = dataclasses.field(
        default_factory=lambda: [RequestState.QUEUED])

    @property
    def terminal(self) -> bool:
        return self.state in TERMINAL_STATES

    def transition(self, new: RequestState, *, error: Optional[str] = None):
        if new not in _TRANSITIONS[self.state]:
            raise InvalidTransition(
                f"request {self.uid}: {self.state.value} -> {new.value}")
        self.state = new
        self.history.append(new)
        if error is not None:
            self.error = error
        if new in TERMINAL_STATES:
            self.done = True


@functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(0,))
def _set_rows(pool, one, slot, axes):
    """Each leaf of ``pool`` with the matching leaf of ``one`` written at
    ``slot`` along its batch axis (``axes``, in leaf order)."""
    leaves, tree = jax.tree.flatten(pool)
    return tree.unflatten([
        jax.lax.dynamic_update_slice_in_dim(p, o.astype(p.dtype), slot, axis=a)
        for p, o, a in zip(leaves, tree.flatten_up_to(one), axes)])


@dataclasses.dataclass
class EngineStats:
    prefills: int = 0
    decode_steps: int = 0
    decode_tokens: int = 0  # COMMITTED tokens (speculative rejects excluded)
    completed: int = 0
    context_tokens: int = 0  # sum over admitted requests of (S + max_new)
    pages_shared: int = 0  # full prefix pages mapped by refcount (no copy)
    cow_copies: int = 0  # pages copied before a write (copy-on-write)
    # speculative decode: positions the target streamed weights for vs
    # tokens that actually landed.  decode_tokens/mean_batch/mean_context
    # stay in COMMITTED tokens so throughput numbers remain comparable with
    # the non-speculative engine (a verified-but-rejected draft position is
    # occupancy, not serving output).
    verified_positions: int = 0  # target positions run per verify step
    draft_proposed: int = 0  # draft tokens offered to verification
    draft_accepted: int = 0  # draft tokens committed by verification
    # failure model: terminal outcomes besides completion, plus recovery
    # traffic.  None of these feed mean_batch/accept_rate — decode_steps
    # only counts executed decode steps and draft_proposed only counts
    # drafts whose verification was numerically sound, so throughput and
    # acceptance stay comparable with the fault-free plain engine.
    failed: int = 0  # terminal FAILED (retries exhausted / cancelled)
    evicted: int = 0  # preemptions (snapshot + requeue, not terminal)
    timed_out: int = 0  # TTFT or total-latency deadline exceeded
    retried: int = 0  # transient-failure requeues (bounded by max_retries)
    fallback_ticks: int = 0  # ticks served in any degraded mode
    # continuous batching: prefill traffic.  ``prefill_tokens`` counts
    # prompt tokens advanced through the model in BOTH modes (chunked and
    # synchronous inline), so prefill_tokens + decode_tokens is a
    # mode-comparable work-unit counter; ``prefill_chunks`` counts only
    # chunked-prefill decode-step calls.
    prefill_chunks: int = 0
    prefill_tokens: int = 0
    # blocking device->host reads (``ServingEngine._to_host``): the decode
    # step's result, each chunk's finite flag, each tempered row's sample,
    # each synchronous prefill and each draft step — one each
    host_syncs: int = 0
    # plain-tick rows whose token came from the tick's one batched greedy
    # choice (equals decode_tokens when every request is greedy)
    batched_choices: int = 0
    # finished prefills whose per-slot rows (recurrent state, contiguous
    # caches) were written into their slot in place, one per
    # ``engine.state_insert`` span
    state_inserts: int = 0

    @property
    def mean_batch(self) -> float:
        """Mean committed tokens per decode step — the realized weight-reuse
        factor in *useful* tokens.  Speculation's extra verified positions
        are reported separately (``verified_positions``), so this stays
        comparable with the non-speculative engine."""
        return self.decode_tokens / max(1, self.decode_steps)

    @property
    def accept_rate(self) -> float:
        """Fraction of proposed draft tokens committed by verification."""
        return self.draft_accepted / max(1, self.draft_proposed)

    @property
    def mean_context(self) -> float:
        """Mean admitted *total* context (S + max_new): what a sequence
        occupies in the paged pool at completion.  Note this is the
        allocation quantity, not the sizer's kv charge — the per-step read
        averages ``batching.mean_decode_context`` = S + max_new/2, since
        early decode steps read a shorter cache."""
        return self.context_tokens / max(1, self.prefills)


class ServingEngine:
    """Continuous-batching engine around one model's prefill/decode fns."""

    def __init__(
        self,
        cfg,
        params,
        *,
        config: Optional[EngineConfig] = None,  # the ONE configuration object
        plan=None,  # WeightPlan: sizes the batch for the compressed stream
        sizer: Optional[BatchSizer] = None,
        **legacy,  # deprecated loose kwargs -> EngineConfig.from_legacy
    ):
        # the serving surface is EngineConfig (serving/config.py): every
        # knob lives in one of its four subsystem dataclasses.  Loose
        # kwargs route through the deprecation shim; tools/
        # check_engine_api.py lints this signature so new knobs cannot
        # re-grow it.
        if legacy:
            if config is not None:
                raise TypeError(
                    "pass either config=EngineConfig(...) or legacy keyword "
                    f"arguments, not both (got {sorted(legacy)})")
            config = EngineConfig.from_legacy(**legacy)
        elif config is None:
            config = EngineConfig()
        self.config = config
        cc, sc, pc, fc = (config.cache, config.scheduler, config.spec,
                          config.fault)
        max_len = int(config.max_len)
        max_batch = config.max_batch
        mesh = config.mesh
        rules = config.rules
        seed = config.seed
        kv_dtype = cc.kv_dtype
        page_size = cc.page_size
        num_pages = cc.num_pages
        share_prefix = cc.share_prefix
        expected_context = cc.expected_context
        prefill_chunk = sc.prefill_chunk
        prefill_budget = sc.prefill_budget
        evict_policy = sc.evict_policy
        draft_cfg = pc.draft_cfg
        draft_params = pc.draft_params
        clock = fc.clock
        self.cfg = cfg
        self.mesh = mesh
        self.rules = None
        if mesh is not None:
            self.rules = dict(sl.DEFAULT_RULES)
            if rules:
                self.rules.update(rules)
        if plan is not None and params is None:
            params = plan.params
        self.params = params
        self.plan = plan
        self.api = get_api(cfg)
        self.max_len = max_len
        self.kv_dtype = jnp.dtype(jnp.int8) if kv_dtype in ("int8",) else (
            jnp.dtype(kv_dtype) if kv_dtype is not None else None
        )
        if self.kv_dtype == jnp.dtype(jnp.int8) and not supports_int8_kv(cfg):
            # some families ignore kv_dtype (encdec keeps an fp cache): only
            # charge the int8 stream if the cache actually materializes one,
            # so the sizer never models a cache that was not allocated.
            import warnings

            warnings.warn(
                f"{cfg.name}: kv_dtype=int8 requested but the "
                f"{cfg.family} cache does not support it; serving fp",
                stacklevel=2)
            self.kv_dtype = None
        self.paged = page_size is not None
        if self.paged and not supports_paged_kv(cfg):
            import warnings

            warnings.warn(
                f"{cfg.name}: paged KV cache requested but the {cfg.family} "
                f"decode path does not thread a page table; serving the "
                f"contiguous cache", stacklevel=2)
            self.paged = False
        self.page_size = page_size if self.paged else None
        # speculative decode: a draft model proposes spec_k tokens per tick
        # and the target verifies all spec_k + 1 positions in ONE
        # multi-token decode step (draft positions amortize the weight
        # stream exactly like batch samples).  Needs positionally-addressed
        # caches on BOTH models so rejected writes are masked-then-
        # overwritten instead of rolled back — SpecConfig.validated_k is
        # the single validated check (config.positional_state_gate, which
        # prefix sharing shares below).
        self.spec_k = pc.validated_k(cfg)
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params
        # continuous batching: chunked prefill runs each chunk as a (1, C)
        # multi-token decode step on a private batch-1 cache — positions
        # [done, done + C) of the prompt — which needs exactly the
        # positionally-addressed-cache property speculation needs (stale
        # ring entries invisible until overwritten, multi-position decode).
        # Attention over a causal prefix is a pure function of (tokens,
        # positions, params), so the chunked logits — and the first sampled
        # token — are bit-identical to the one-shot prefill's.  A stack that
        # carries state (Mamba-2) qualifies too: its chunks run without
        # overlap from the state the previous chunk left, the last padded
        # to the chunk width (serving/scheduler.py).
        self.carries_state = carries_state(cfg)
        self.prefill_chunk = self.prefill_budget = None
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if prefill_chunk <= 0:
                raise ValueError(
                    f"prefill_chunk must be positive, got {prefill_chunk}")
            reason = chunked_prefill_gate(cfg)
            if reason is not None:
                import warnings

                warnings.warn(
                    reason + "; serving synchronous prefill", stacklevel=2)
            else:
                if any(k == "local" for k in cfg.layer_kinds):
                    # a chunk wider than a sliding-window ring would
                    # scatter duplicate ring indices within ONE step
                    # (nondeterministic winner): clamp to the window
                    prefill_chunk = min(prefill_chunk, cfg.local_window)
                self.prefill_chunk = prefill_chunk
                self.prefill_budget = max(
                    int(prefill_budget or 0), prefill_chunk)
                if self.carries_state:
                    # whole chunks, so that a padded tail never wraps the
                    # private prefill cache onto its first positions
                    max_len = -(-max_len // prefill_chunk) * prefill_chunk
                    self.max_len = max_len
        # the cache stream the sizer charges: per-token bytes at this
        # engine's cache dtype and the *expected* context — max_len for the
        # contiguous cache (the reservation is real traffic: ring length ==
        # max_len), the caller's mean (S + max_new) for the paged cache,
        # where short requests read only what they wrote.  int8 halves it;
        # both corrections move n_opt exactly as perf_model.decode_n_opt
        # predicts.
        ctx = int(expected_context) if expected_context else max_len
        ctx = min(ctx, max_len)
        self.expected_context = ctx
        kv_tok = kv_bytes_per_token(cfg, self.kv_dtype, context_len=ctx)
        # multi-chip accounting for the sizer: the model axis divides the
        # weight stream; the kv term divides by the degree the cache leaves
        # *actually* shard by (divisibility may leave them replicated); the
        # data axes replicate the whole analysis over batch shards.
        self.data_parallel = self.model_parallel = self.kv_parallel = 1
        if mesh is not None:
            (self.data_parallel, self.model_parallel,
             self.kv_parallel) = sl.parallelism_degrees(
                mesh, self.rules, int(getattr(cfg, "n_kv_heads", 0) or 0))
        # the sizer is built even when the caller fixes max_batch: beyond
        # picking n_opt it is the engine's live throughput model — the
        # speculative acceptance EMA (observe_accept) and the acceptance-
        # collapse fallback (spec_worthwhile) both read it every tick.
        if sizer is None:
            mp_kw = dict(model_parallel=self.model_parallel,
                         kv_parallel=self.kv_parallel,
                         spec_k=self.spec_k)
            if self.spec_k:
                mp_kw["draft_n_params"] = get_api(
                    draft_cfg).n_params_exact(draft_cfg)
            if plan is not None:
                # pruning + quantization shrink t_mem: the plan knows the
                # achieved (b_weight, q_prune, q_overhead), so n_opt
                # lands where Section 5.6 predicts for this model.
                sizer = plan.sizer(
                    n_params=self.api.n_params_exact(cfg),
                    kv_bytes_per_token=kv_tok, context_len=ctx, **mp_kw,
                )
            else:
                sizer = BatchSizer(
                    n_params=self.api.n_params_exact(cfg),
                    kv_bytes_per_token=kv_tok, context_len=ctx, **mp_kw,
                )
        if max_batch is None:
            # the sizer's n_opt is the balance point of ONE model group
            # (data parallelism replicates the whole analysis, see
            # decode_n_opt): the engine's global batch must feed every data
            # replica its n_opt sequences or each chip decodes below the
            # balance point the model just computed.
            max_batch = min(64, sizer.n_opt * self.data_parallel)
        self.max_batch = max_batch
        self.sizer = sizer
        self.dtype = jnp.dtype(cfg.compute_dtype)
        # slot state (host-side)
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        self.slot_pos = np.zeros((max_batch,), np.int32)  # next position to write
        self.slot_remaining = np.zeros((max_batch,), np.int32)
        self.slot_last_tok = np.zeros((max_batch,), np.int32)
        self.slot_admit_seq = np.zeros((max_batch,), np.int64)  # admission order
        # continuous batching: slot -> in-flight chunked prefill (or None).
        # A slot with a job is live (occupies pages, sees deadlines, can be
        # evicted) but is NOT in the decode batch (_decoding_slots).
        self.slot_prefill: List[Optional[PrefillJob]] = [None] * max_batch
        self.last_tick_prefill_tokens = 0  # budget spent by the last tick
        self.queue: deque = deque()
        self.stats = EngineStats()
        # -- failure model -------------------------------------------------
        if evict_policy not in ("fifo", "priority"):
            raise ValueError(f"evict_policy must be fifo|priority, got {evict_policy!r}")
        self.request_timeout_s = sc.request_timeout_s
        self.ttft_deadline_s = sc.ttft_deadline_s
        self.max_retries = int(sc.max_retries)
        self.retry_backoff_s = float(sc.retry_backoff_s)
        self.evict_policy = evict_policy
        self.deadline_slack_s = float(sc.deadline_slack_s)
        self.clock = clock
        self.fault_injector = fc.fault_injector
        self.spec_fallback_accept = pc.fallback_accept
        self.spec_fallback_min_ticks = int(pc.fallback_min_ticks)
        self.audit_every_step = bool(fc.audit_every_step)
        self.tick = 0  # 1-based after the first step()
        self._admit_seq = 0
        self._spec_ticks = 0
        # degradation ladder: rung name -> reason.  A populated dict means
        # the engine is serving in a degraded mode (counted per tick in
        # stats.fallback_ticks); rungs are one-way within an engine's life.
        self.degraded: dict = {}
        self.spec_active = self.spec_k > 0
        self.watchdog = (
            HeartbeatMonitor(n_hosts=1, timeout_s=fc.watchdog_timeout_s,
                             clock=clock)
            if fc.watchdog_timeout_s is not None else None)
        self._rng = jax.random.key(seed)
        # host-side RNG for the speculative draft/accept chain (per-slot
        # temperatures; the jax stream above stays the non-spec sampler)
        self._np_rng = np.random.default_rng(seed)
        # enc-dec paged serving: encoder-frame page lists / table, created
        # below when the family's paged cache carries an ``xpage_table``.
        self.xpages_per_seq = 0
        self.slot_xpages: Optional[List[List[int]]] = None
        self._xtable = None
        if self.paged:
            self.pages_per_seq = math.ceil(max_len / page_size)
            if cc.allocator is not None:
                # mixed-family serving: several engines draw from ONE
                # allocator (shared capacity, disjoint page ownership);
                # pool arrays are sized to its id space and the owning
                # MixedServingEngine runs the cross-engine audit.
                self.allocator = cc.allocator
                self._owns_allocator = False
                self.num_pages = self.allocator.num_pages
            else:
                # default pool: byte parity with the contiguous reservation
                # (max_batch * pages_per_seq pages + the null page) —
                # callers shrink it to realize the paged saving, or keep it
                # and raise max_batch under the same bytes.
                self.num_pages = num_pages or (
                    1 + max_batch * self.pages_per_seq)
                self.allocator = PageAllocator(self.num_pages)
                self._owns_allocator = True
            if share_prefix and self.carries_state:
                # a shared prefix maps pages: the recurrent state those
                # tokens built is not in them, and the new sequence would
                # start from a state that never saw its prefix.
                import warnings

                warnings.warn(
                    positional_state_gate(cfg, "prefix sharing")
                    + ": its recurrent state is not held in pages; serving "
                    "without prefix sharing", stacklevel=2)
                share_prefix = False
            if share_prefix and self.api.extra_keys:
                # prefix sharing keys on prompt tokens only; this family's
                # KV also depends on per-request frames/patches, so equal
                # token prefixes are NOT equal cache entries.
                import warnings

                warnings.warn(
                    f"{cfg.name}: share_prefix keys on prompt tokens but "
                    f"this family's cache also depends on "
                    f"{self.api.extra_keys}; serving without prefix "
                    f"sharing", stacklevel=2)
                share_prefix = False
            self.registry = PrefixRegistry() if share_prefix else None
            self.slot_pages: List[List[int]] = [[] for _ in range(max_batch)]
            self._table = np.full(
                (max_batch, self.pages_per_seq), NULL_PAGE, np.int32)
            self.cache = self.api.init_cache(
                cfg, max_batch, max_len, self.dtype, kv_dtype=self.kv_dtype,
                page_size=page_size, num_pages=self.num_pages,
                **self._spec_cache_kw(),
            )
            if isinstance(self.cache, dict) and "xpage_table" in self.cache:
                self.xpages_per_seq = int(self.cache["xpage_table"].shape[1])
                self.slot_xpages = [[] for _ in range(max_batch)]
                self._xtable = np.full(
                    (max_batch, self.xpages_per_seq), NULL_PAGE, np.int32)
        else:
            self.allocator = None
            self._owns_allocator = False
            self.registry = None
            # one shared cache for the pool; per-slot prefill uses a batch-1 cache
            self.cache = self.api.init_cache(
                cfg, max_batch, max_len, self.dtype, kv_dtype=self.kv_dtype,
                **self._spec_cache_kw(),
            )
        if mesh is not None:
            # sharded serving: params and caches are placed ONCE by the
            # axis-rules registry (dense, PackedLinear, int8 scales, page
            # pools — no leaf kind falls back to ad-hoc annotations), and
            # both compiled steps trace under use_mesh so the in-step
            # shard_pinned constraints resolve against the same rules.
            self.params = jax.device_put(self.params, self._param_shardings())
            self.cache = jax.device_put(self.cache, self._cache_shardings())
        # draft side of speculative decode: its own (dense, contiguous-
        # cache) prefill + single-token decode steps.  The verify step
        # needs no extra compile plumbing — self._decode re-specializes on
        # the (B, k+1) token shape, keeping the one-signature-per-step
        # invariant (one T=k+1 verify signature, one prefill signature,
        # plus the draft pair).
        self.draft_api = None
        self.draft_cache = None
        if self.spec_k:
            self.draft_api = get_api(draft_cfg)
            self.draft_dtype = jnp.dtype(draft_cfg.compute_dtype)
            self.draft_cache = self.draft_api.init_cache(
                draft_cfg, max_batch, max_len, self.draft_dtype,
                spec_k=self.spec_k,
            )
            if mesh is not None:
                # draft params/cache placed once through the same registry;
                # both draft steps trace under use_mesh like the target's.
                self.draft_params = jax.device_put(
                    self.draft_params,
                    sl.tree_shardings(
                        self.draft_params,
                        self.draft_api.param_axes(draft_cfg),
                        mesh=self.mesh, rules=self.rules))
                self.draft_cache = jax.device_put(
                    self.draft_cache,
                    sl.tree_shardings(
                        self.draft_cache,
                        self.draft_api.cache_axes(draft_cfg),
                        mesh=self.mesh, rules=self.rules))
        self._build_steps()

    @classmethod
    def from_tuned(cls, cfg, params, tuned: dict, *, plan=None, **overrides):
        """Build an engine from a TunedPlan artifact (core/autotune).

        The artifact's serving knobs (kv_dtype, page geometry, max_batch,
        expected context, spec_k) become constructor kwargs; ``overrides``
        win over the artifact.  ``plan`` is the compressed WeightPlan the
        artifact's PlanConfig materializes (``autotune.plan_config(tuned)``
        + ``api.compress`` or a ``load_plan`` cache) — pass it so the sizer
        charges the tuned weight stream.  spec_k is honored only when a
        draft model is supplied alongside, since the artifact cannot carry
        draft params.
        """
        from repro.core import autotune as AT

        if tuned.get("arch") != cfg.name:
            raise ValueError(
                f"TunedPlan was searched for arch {tuned.get('arch')!r}, "
                f"engine config is {cfg.name!r}")
        return cls(cfg, params, plan=plan,
                   config=AT.engine_config(tuned, **overrides))

    def _build_steps(self):
        """(Re)create the jitted step wrappers.  Called once at init and
        again by the degradation ladder — a fresh ``jax.jit`` cache is what
        makes the flipped ``layers.force_attention_kernel`` override take
        effect (the old traces baked in the old dispatch).

        The decode wrapper folds the numeric guardrail into the ONE
        compiled step: the per-slot ``poison`` mask (the ``nan_logits``
        injection point — normally all-False) lands before a per-slot
        ``layers.finite_rows`` reduction, so the engine's quarantine
        decision costs one (B,) bool fetch per tick instead of a second
        host pass over (B, T, V) logits."""
        cfg, api = self.cfg, self.api

        def _decode_impl(params, cache, tokens, pos, poison):
            logits, cache = api.decode_step(cfg, params, cache, tokens, pos)
            logits = jnp.where(poison[:, None, None], jnp.nan, logits)
            return logits, finite_rows(logits), cache

        if self.mesh is None:
            self._decode = jax.jit(_decode_impl, donate_argnums=(1,))
            self._prefill1 = jax.jit(functools.partial(self._prefill_one_impl, cfg))
        else:
            def _decode_meshed(params, cache, tokens, pos, poison):
                with sl.use_mesh(self.mesh, self.rules):
                    return _decode_impl(params, cache, tokens, pos, poison)

            def _prefill_meshed(params, batch, cache1):
                with sl.use_mesh(self.mesh, self.rules):
                    return self.api.prefill(self.cfg, params, batch, cache1)

            self._decode = jax.jit(_decode_meshed, donate_argnums=(1,))
            self._prefill1 = jax.jit(_prefill_meshed)
        if not self.spec_k:
            return
        draft_cfg = self.draft_cfg
        if self.mesh is None:
            self._draft_decode = jax.jit(
                functools.partial(self.draft_api.decode_step, draft_cfg),
                donate_argnums=(1,),
            )
            self._draft_prefill1 = jax.jit(
                functools.partial(self._prefill_one_impl, draft_cfg))
        else:
            def _draft_decode_meshed(params, cache, tokens, pos):
                with sl.use_mesh(self.mesh, self.rules):
                    return self.draft_api.decode_step(
                        self.draft_cfg, params, cache, tokens, pos)

            def _draft_prefill_meshed(params, batch, cache1):
                with sl.use_mesh(self.mesh, self.rules):
                    return self.draft_api.prefill(
                        self.draft_cfg, params, batch, cache1)

            self._draft_decode = jax.jit(
                _draft_decode_meshed, donate_argnums=(1,))
            self._draft_prefill1 = jax.jit(_draft_prefill_meshed)

    def _spec_cache_kw(self) -> dict:
        """Extra init_cache kwargs for speculative mode: widened local
        rings.  Only passed when speculating — non-transformer families
        (excluded from speculation) don't take the kwarg."""
        return {"spec_k": self.spec_k} if self.spec_k else {}

    # -- sharded placement (axis-rules registry) ------------------------------

    def _param_shardings(self):
        """NamedShardings for the (possibly compressed) params pytree: the
        plan's recorded per-leaf axes when available, the family's dense
        param axes otherwise — both expand through the registry, so packed
        blocks shard on the output-feature axis and walks stay replicated
        with zero engine-side special cases."""
        if self.plan is not None and any(
            l.axes for l in self.plan.leaves.values()
        ):
            return self.plan.param_shardings(mesh=self.mesh, rules=self.rules)
        return sl.tree_shardings(
            self.params, self.api.param_axes(self.cfg),
            mesh=self.mesh, rules=self.rules)

    def _cache_shardings(self):
        """NamedShardings for the cache pytree via the registered cache
        axes — including the int8 scale leaves (``attn_cache_axes(
        quantized=True)``) and the paged pools + page table
        (``paged_attn_cache_axes``), which previously never reached the
        launcher."""
        axes = self.api.cache_axes(
            self.cfg,
            quantized_kv=self.kv_dtype == jnp.dtype(jnp.int8),
            paged=self.paged,
        )
        return sl.tree_shardings(
            self.cache, axes, mesh=self.mesh, rules=self.rules)

    # -- host-side plumbing -------------------------------------------------

    def submit(self, req: Request):
        if req.submit_t is not None or req.state is not RequestState.QUEUED:
            raise ValueError(
                f"request {req.uid} already submitted (state {req.state.value})")
        req.output = []
        req.submit_t = self.clock()
        self.queue.append(req)

    def cancel(self, req: Request) -> bool:
        """Cancel a queued or running request: slot and pages free
        immediately, the request terminates FAILED("cancelled").  Terminal
        requests are a no-op (returns False)."""
        if req.terminal:
            return False
        if req in self.queue:
            self.queue.remove(req)
            req.transition(RequestState.FAILED, error="cancelled")
            req.finish_t = self.clock()
            self.stats.failed += 1
            return True
        for slot, r in enumerate(self.slot_req):
            if r is req:
                self._release_slot(slot)
                req.transition(RequestState.FAILED, error="cancelled")
                req.finish_t = self.clock()
                self.stats.failed += 1
                return True
        return False

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _live_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    def _decoding_slots(self) -> List[int]:
        """Slots in this tick's decode batch: live slots minus in-flight
        chunked prefills (their KV is still private to the job's batch-1
        cache and their published page-table row is all-NULL)."""
        return [i for i, r in enumerate(self.slot_req)
                if r is not None and self.slot_prefill[i] is None]

    def _emit(self, req: Request, toks) -> None:
        """Streaming: deliver just-committed tokens to the request's
        callback (called after ``req.output`` grew by ``toks``)."""
        if req.on_token is not None:
            for t in toks:
                req.on_token(req, int(t))

    @property
    def pages_in_use(self) -> int:
        return self.allocator.used_pages if self.paged else 0

    # -- failure model: deadlines, retries, eviction --------------------------

    def _release_slot(self, slot: int):
        """Free a slot's host state and its pages (refcount-correct through
        shared prefixes).  The device cache rows need no scrub: position
        masks keep stale entries invisible to later occupants, and the
        paged table row reverts to the null page."""
        self.slot_req[slot] = None
        self.slot_prefill[slot] = None  # in-flight chunk job dies with the slot
        if self.paged:
            self._free_slot_pages(slot)

    def _deadline_reason(self, req: Request, now: float) -> Optional[str]:
        """Which deadline (if any) ``req`` has exceeded at ``now``.
        Per-request budgets override the engine defaults; the TTFT budget
        only applies until the first token exists."""
        if req.submit_t is None:
            return None
        total = (req.deadline_s if req.deadline_s is not None
                 else self.request_timeout_s)
        if total is not None and now - req.submit_t > total:
            return f"total-latency deadline {total:g}s exceeded"
        if req.first_token_t is None:
            ttft = (req.ttft_deadline_s if req.ttft_deadline_s is not None
                    else self.ttft_deadline_s)
            if ttft is not None and now - req.submit_t > ttft:
                return f"TTFT deadline {ttft:g}s exceeded"
        return None

    def _time_out(self, req: Request, reason: str, slot: Optional[int] = None):
        if slot is not None:
            self._release_slot(slot)
        req.transition(RequestState.TIMED_OUT, error=reason)
        req.finish_t = self.clock()
        self.stats.timed_out += 1

    def _enforce_deadlines(self, now: float):
        """Deadline sweep, run at the top of every tick: queued requests
        (including evicted ones awaiting readmission) and live slots both
        time out the moment their budget lapses — an expired request never
        occupies a slot or pages past the tick that caught it."""
        for req in [r for r in self.queue if self._deadline_reason(r, now)]:
            self.queue.remove(req)
            self._time_out(req, self._deadline_reason(req, now))
        for slot in self._live_slots():
            reason = self._deadline_reason(self.slot_req[slot], now)
            if reason is not None:
                self._time_out(self.slot_req[slot], reason, slot=slot)

    def _retry_or_fail(self, req: Request, reason: str):
        """Transient-failure policy: bounded retry with exponential backoff
        (``not_before`` gates readmission), resuming from the committed
        prefix exactly like eviction; FAILED once ``max_retries`` is
        spent.  Either way the request keeps moving toward a terminal
        state — nothing retries forever."""
        now = self.clock()
        if req.retries >= self.max_retries:
            req.transition(RequestState.FAILED, error=reason)
            req.finish_t = now
            self.stats.failed += 1
            return
        req.retries += 1
        self.stats.retried += 1
        req.not_before = now + self.retry_backoff_s * (2 ** (req.retries - 1))
        req.transition(RequestState.QUEUED, error=reason)
        self.queue.append(req)

    def _quarantine_slot(self, slot: int, reason: str):
        """Numeric guardrail: a slot whose logits went non-finite is cut
        out of the batch this tick (slot recycled, pages freed) so the
        poison cannot reach neighbors via shared engine state, then
        retried from its committed prefix or failed."""
        req = self.slot_req[slot]
        self._release_slot(slot)
        self._retry_or_fail(req, reason)

    def _evict_slot(self, slot: int, reason: str):
        """Preemption-safe eviction.  The committed tokens already live in
        ``req.output`` (that list *is* the snapshot), private pages free
        refcount-correctly (shared prefix pages just drop one reference —
        the donor's mapping is untouched), and the request re-enters the
        queue front for prefill-from-prefix readmission; under
        ``share_prefix`` its still-live prefix pages are re-mapped instead
        of recomputed.  Evictions do not consume retries: progress was
        preserved, and termination stays bounded by the deadlines."""
        req = self.slot_req[slot]
        self._release_slot(slot)
        req.transition(RequestState.EVICTED, error=reason)
        req.evictions += 1
        self.stats.evicted += 1
        self.queue.appendleft(req)

    def _pick_victim(self, incoming: Request, now: float) -> Optional[int]:
        """Eviction victim under ``evict_policy="priority"``: the lowest-
        priority live slot, ties broken toward the most recently admitted
        (least progress lost).  A victim must rank strictly below the
        incoming request, so same-priority traffic can never thrash
        (A evicts B evicts A); TTFT deadline pressure — the incoming
        request would blow its TTFT budget within ``deadline_slack_s`` —
        is worth one priority level."""
        if self.evict_policy != "priority":
            return None
        live = self._live_slots()
        if not live:
            return None
        slot = min(live, key=lambda s: (
            self.slot_req[s].priority, -int(self.slot_admit_seq[s])))
        boost = 0
        ttft = (incoming.ttft_deadline_s if incoming.ttft_deadline_s is not None
                else self.ttft_deadline_s)
        if (self.deadline_slack_s > 0 and ttft is not None
                and incoming.first_token_t is None
                and incoming.submit_t is not None
                and now - incoming.submit_t >= ttft - self.deadline_slack_s):
            boost = 1
        if self.slot_req[slot].priority < incoming.priority + boost:
            return slot
        return None

    def _next_queued(self, now: float) -> Optional[Request]:
        """Next admissible queued request: highest priority first under the
        priority policy (FIFO among equals), pure FIFO otherwise.
        Retry-backoff-gated requests are invisible until ``not_before``."""
        eligible = [r for r in self.queue if r.not_before <= now]
        if not eligible:
            return None
        if self.evict_policy == "priority":
            return max(eligible, key=lambda r: r.priority)
        return eligible[0]

    def _resume_tokens(self, req: Request) -> np.ndarray:
        """Prefill token stream: the prompt plus any committed output (the
        eviction/retry snapshot) — readmission is prefill-from-prefix, so
        greedy streams continue bit-identically at the committed frontier."""
        out = req.output or []
        if not out:
            return np.asarray(req.prompt, np.int32)
        return np.concatenate([np.asarray(req.prompt, np.int32),
                               np.asarray(out, np.int32)])

    # -- device-side steps ----------------------------------------------------

    @staticmethod
    def _prefill_one_impl(cfg, params, batch, cache1):
        api = get_api(cfg)
        return api.prefill(cfg, params, batch, cache1)

    def _prefill_request(self, req: Request, tokens: np.ndarray):
        """Run the batch-1 prefill over ``tokens`` — the prompt, plus any
        committed output when resuming after eviction/retry.  Returns
        (first sampled token, cache1, logits-finite flag); a non-finite
        prefill row sends the request to the retry path instead of
        admitting a poisoned slot."""
        with TraceAnnotation("engine.prefill", uid=req.uid, tokens=len(tokens)):
            cache1 = self.api.init_cache(
                self.cfg, 1, self.max_len, self.dtype, kv_dtype=self.kv_dtype,
                **self._spec_cache_kw(),
            )
            batch = {"tokens": jnp.asarray(tokens, jnp.int32)[None]}
            for k, v in (req.extras or {}).items():
                batch[k] = jnp.asarray(v)[None]
            logits, cache1 = self._prefill1(self.params, batch, cache1)
            self.stats.prefill_tokens += len(tokens)
            row = logits[:, -1]
            ok, tok = self._to_host(jnp.isfinite(row).all(),
                                    self._sample(row, req.temperature))
        return int(tok[0]), cache1, bool(ok)

    def _draft_prefill_slot(self, slot: int, tokens: np.ndarray):
        """Fill the draft model's KV for this request's prefill tokens into
        its slot of the (always contiguous) draft cache.  The draft's
        prefill logits are discarded — the target's prefill sampled the
        first token; the draft only needs the KV so its per-tick decode
        chain starts from the committed frontier."""
        cache1 = self.draft_api.init_cache(
            self.draft_cfg, 1, self.max_len, self.draft_dtype,
            spec_k=self.spec_k,
        )
        batch = {"tokens": jnp.asarray(tokens, jnp.int32)[None]}
        _, cache1 = self._draft_prefill1(self.draft_params, batch, cache1)
        self.draft_cache = self._insert_rows(self.draft_cache, cache1, slot)

    def _start_slot(self, slot: int, req: Request, S: int, first_tok: int,
                    tokens: np.ndarray, resumed: bool):
        if self.spec_active:
            try:
                with TraceAnnotation("engine.prefill", uid=req.uid,
                                     tokens=len(tokens)):
                    self._draft_prefill_slot(slot, tokens)
            except Exception as e:  # dead draft at admission: rung 1
                self._degrade_speculation(f"draft prefill failed: {e}")
        self.slot_req[slot] = req
        self.slot_pos[slot] = S
        self._admit_seq += 1
        self.slot_admit_seq[slot] = self._admit_seq
        # resumption: already-committed tokens were replayed as prompt, so
        # only the rest of the generation budget remains
        self.slot_remaining[slot] = req.max_new_tokens - len(req.output)
        self.slot_last_tok[slot] = first_tok
        req.transition(RequestState.DECODING)
        req.output.append(first_tok)
        self._emit(req, (first_tok,))
        self.slot_remaining[slot] -= 1
        if req.first_token_t is None:
            req.first_token_t = self.clock()
        self.stats.prefills += 1
        if not resumed:
            # readmissions don't recount context: mean_context stays the
            # admitted-traffic quantity, comparable with the plain engine
            self.stats.context_tokens += S + req.max_new_tokens
        self._finish_if_done(slot)

    def _admit(self):
        """Move queued requests into free slots (prefill); under the
        priority policy a blocked queue may preempt a lower-priority slot."""
        if self.paged:
            return self._admit_paged()
        now = self.clock()
        while self.queue:
            req = self._next_queued(now)
            if req is None:
                break
            free = self._free_slots()
            if not free:
                victim = self._pick_victim(req, now)
                if victim is None:
                    break
                self._evict_slot(victim, "preempted")
                continue  # the evictee re-entered the queue: re-select
            slot = free[0]
            self.queue.remove(req)
            resumed = bool(req.output)
            req.transition(RequestState.PREFILLING)
            tokens = self._resume_tokens(req)
            S = len(tokens) + self.api.prefix_len(self.cfg)
            # spec_k headroom: the last verify tick writes up to spec_k
            # positions past the final committed token; the ring must never
            # wrap (a wrapped speculative write would clobber a live early
            # position that masking cannot recover).  Invariant under
            # resumption: S + remaining == len(prompt) + prefix + max_new.
            remaining = req.max_new_tokens - len(req.output)
            assert S + remaining + self.spec_k <= self.max_len, \
                "request (+ spec_k speculation headroom) exceeds max_len"
            if self.prefill_chunk is not None:
                # continuous batching: admission only reserves the slot and
                # records the job — no model work here.  The chunks run in
                # _run_prefill_chunks under the per-tick token budget.
                self._enqueue_prefill(slot, req, tokens, S, resumed)
                continue
            tok, cache1, ok = self._prefill_request(req, tokens)
            if not ok:
                self._retry_or_fail(req, "non-finite prefill logits")
                continue
            self._write_slot(slot, cache1, req.uid)
            self._start_slot(slot, req, S, tok, tokens, resumed)

    def _admit_paged(self):
        """Paged admission: map shared prefix pages, allocate the rest; on
        exhaustion either preempt a lower-priority slot (priority policy)
        or leave the queue alone (FIFO back-pressure, no crash).  Any
        admission failure after pages were claimed releases them before
        the request re-queues — a torn admission can never leak."""
        ps = self.page_size
        now = self.clock()
        while self.queue:
            req = self._next_queued(now)
            if req is None:
                break
            free = self._free_slots()
            if not free:
                victim = self._pick_victim(req, now)
                if victim is None:
                    break
                self._evict_slot(victim, "preempted (slot pressure)")
                continue  # the evictee re-entered the queue: re-select
            slot = free[0]
            tokens = self._resume_tokens(req)
            S = len(tokens) + self.api.prefix_len(self.cfg)
            remaining = req.max_new_tokens - len(req.output or [])
            total = S + remaining
            capacity = self.pages_per_seq * ps
            if total + self.spec_k > capacity:
                # spec_k headroom keeps the verify scatter's page-table
                # lookups in range; writes past the *allocated* pages land
                # on NULL_PAGE rows and are absorbed by the null page.
                raise ValueError(
                    f"request {req.uid}: S + remaining (+ spec_k) = "
                    f"{total + self.spec_k} exceeds the page-table capacity "
                    f"{capacity} (pages_per_seq * page_size); raise max_len")
            prompt_key = tuple(int(t) for t in tokens)
            shared_len, shared_pages = (
                self.registry.match(prompt_key) if self.registry is not None
                else (0, []))
            n_total = math.ceil(total / ps)
            n_full = shared_len // ps  # full pages mapped by refcount
            boundary = 1 if shared_len % ps else 0  # partial page: eager COW
            # enc-dec: the encoded frames claim their own pages from the
            # same pool — admission back-pressure covers the whole request
            x_need = self.xpages_per_seq if self.slot_xpages is not None else 0
            if not self._can_alloc_pages(n_total - n_full + x_need):
                victim = self._pick_victim(req, now)
                if victim is None:
                    break  # pool exhausted: request stays queued
                self._evict_slot(victim, "preempted (page-pool pressure)")
                continue
            self.queue.remove(req)
            resumed = bool(req.output)
            req.transition(RequestState.PREFILLING)
            retained = shared_pages[:n_full]
            self.allocator.retain(retained)
            if self.prefill_chunk is not None:
                # continuous batching: claim only the shared prefix (plus
                # the boundary-page COW copy) now; the rest of the pages
                # grow chunk by chunk (_grow_slot_pages) and the table row
                # stays all-NULL until the DECODING transition, so batched-
                # decode scatters from this slot land on the null page.
                # The can_alloc gate above still sized the EVENTUAL need —
                # admission keeps its back-pressure semantics; a raced-away
                # pool mid-prefill is a transient fault (retry path).
                try:
                    fresh = self._alloc_pages(boundary)
                except PoolExhausted as e:
                    self.allocator.release(retained)
                    self._retry_or_fail(
                        req, f"page pool exhausted at admission: {e}")
                    continue
                if boundary:
                    self._copy_page(shared_pages[n_full], fresh[0])
                    self.stats.cow_copies += 1
                self.stats.pages_shared += n_full
                self.slot_pages[slot] = retained + fresh
                self._enqueue_prefill(slot, req, tokens, S, resumed,
                                      shared_len=shared_len,
                                      prompt_key=prompt_key)
                continue
            try:
                fresh = self._alloc_pages(n_total - n_full)
                xpages: List[int] = []
                if x_need:
                    try:
                        xpages = self._alloc_pages(x_need)
                    except PoolExhausted:
                        self.allocator.release(fresh)
                        raise
            except PoolExhausted as e:
                # raced an (injected) failure between can_alloc and alloc
                self.allocator.release(retained)
                self._retry_or_fail(req, f"page pool exhausted at admission: {e}")
                continue
            if boundary:
                # the new sequence writes positions [shared_len, ...) into
                # this page, so it cannot share it read-only: copy-on-write
                # at mapping time (the donor's copy is never disturbed).
                self._copy_page(shared_pages[n_full], fresh[0])
                self.stats.cow_copies += 1
            pages = retained + fresh
            self.stats.pages_shared += n_full
            self.slot_pages[slot] = pages
            self._table[slot, :] = NULL_PAGE
            self._table[slot, : len(pages)] = pages
            if x_need:
                self.slot_xpages[slot] = xpages
                self._xtable[slot, :] = NULL_PAGE
                self._xtable[slot, : len(xpages)] = xpages
            try:
                tok, cache1, ok = self._prefill_request(req, tokens)
            except Exception:
                # torn admission: release before propagating, so the
                # allocator stays audit-clean even on unexpected errors
                self._free_slot_pages(slot)
                raise
            if not ok:
                self._free_slot_pages(slot)
                self._retry_or_fail(req, "non-finite prefill logits")
                continue
            # shared positions [0, shared_len) already hold identical KV
            # (same tokens, same positions, same params): write only ours.
            self._write_slot_paged(slot, cache1, start=shared_len, stop=S,
                                   uid=req.uid)
            if self.registry is not None:
                self.registry.register(prompt_key, pages[: math.ceil(S / ps)])
            self._start_slot(slot, req, S, tok, tokens, resumed)

    # -- chunked prefill (continuous batching) --------------------------------

    def _enqueue_prefill(self, slot: int, req: Request, tokens: np.ndarray,
                         S: int, resumed: bool, shared_len: int = 0,
                         prompt_key=None):
        """Reserve ``slot`` for a multi-tick chunked prefill: the slot is
        live from here (deadlines apply, eviction can land on it) but joins
        the decode batch only at the DECODING transition."""
        self.slot_req[slot] = req
        self._admit_seq += 1
        self.slot_admit_seq[slot] = self._admit_seq
        self.slot_prefill[slot] = PrefillJob(
            req=req, tokens=np.asarray(tokens, np.int32), S=S,
            resumed=resumed, shared_len=shared_len, prompt_key=prompt_key)

    def _run_prefill_chunks(self):
        """Advance in-flight chunked prefills, oldest admission first, by
        at most ``prefill_budget`` prompt tokens this tick.  FIFO with no
        overtaking: when the next span of the oldest job doesn't fit the
        remaining budget, the tick's prefill work ends — younger (smaller)
        jobs cannot starve an older one by slipping into the gap."""
        budget = TickBudget(self.prefill_budget)
        jobs = sorted(
            (s for s in range(self.max_batch)
             if self.slot_prefill[s] is not None),
            key=lambda s: int(self.slot_admit_seq[s]))
        for slot in jobs:
            while self.slot_prefill[slot] is not None:
                job = self.slot_prefill[slot]
                start, stop = next(
                    (a, b) for a, b in chunk_spans(
                        job.S, self.prefill_chunk, self.carries_state)
                    if b > job.done)
                if not budget.try_charge(stop - start):
                    self.last_tick_prefill_tokens = budget.used
                    return
                self._run_prefill_chunk(slot, job, start, stop)
        self.last_tick_prefill_tokens = budget.used

    def _run_prefill_chunk(self, slot: int, job: PrefillJob,
                           start: int, stop: int):
        """One chunk: a ``(1, stop - start)`` multi-token decode step over
        the prompt span at positions [start, stop) of the job's private
        batch-1 cache.  The final (possibly overlapped) chunk's last logits
        row is the full prefill's last row bit-for-bit (scheduler.py
        explains why the overlap is a no-op rewrite); a non-finite chunk
        sends the request to the retry path like a poisoned inline prefill.
        In a stack that carries state a short span is padded to the chunk
        width with token -1 (a position that changes no state), and the
        last real row is read."""
        req = job.req
        with TraceAnnotation("engine.prefill_chunk", uid=req.uid, slot=slot,
                             start=start, stop=stop):
            if job.cache1 is None:
                job.cache1 = self.api.init_cache(
                    self.cfg, 1, self.max_len, self.dtype,
                    kv_dtype=self.kv_dtype, **self._spec_cache_kw(),
                )
            span = np.asarray(job.tokens[start:stop], np.int32)
            if self.carries_state:
                span = np.pad(span, (0, self.prefill_chunk - len(span)),
                              constant_values=-1)
            toks = jnp.asarray(span)[None]
            pos = jnp.asarray([start], jnp.int32)
            logits, ok, job.cache1 = self._decode(
                self.params, job.cache1, toks, pos, jnp.zeros((1,), bool))
            job.done = stop
            self.stats.prefill_chunks += 1
            self.stats.prefill_tokens += stop - start
            (ok,) = self._to_host(ok)
        if not bool(ok[0]):
            self._quarantine_slot(
                slot, "non-finite prefill logits (chunked)")
            return
        if self.paged:
            try:
                # in-flight page growth: capacity tracks the prefilled
                # frontier chunk by chunk (allocator-charged, row unpublished
                # — docs/memory_model.md § in-flight prefill accounting)
                self._grow_slot_pages(slot, math.ceil(stop / self.page_size))
            except PoolExhausted as e:
                self._quarantine_slot(
                    slot, f"page pool exhausted during chunked prefill: {e}")
                return
        if job.finished:
            job.last_row = logits[:, stop - start - 1]
            self._finish_prefill_job(slot, job)

    def _finish_prefill_job(self, slot: int, job: PrefillJob):
        """DECODING transition: top the pages up to the decode-complete
        count, scatter the private cache into the slot (pages or row),
        publish the table row, register the prefix, sample the first
        token, and hand the slot to ``_start_slot`` exactly like the
        synchronous admission path."""
        req = job.req
        with TraceAnnotation("engine.prefill_finish", uid=req.uid, S=job.S):
            (tok,) = self._to_host(self._sample(job.last_row, req.temperature))
            tok = int(tok[0])
            if self.paged:
                ps = self.page_size
                remaining = req.max_new_tokens - len(req.output or [])
                try:
                    self._grow_slot_pages(
                        slot, math.ceil((job.S + remaining) / ps))
                except PoolExhausted as e:
                    self._quarantine_slot(
                        slot, f"page pool exhausted at prefill completion: {e}")
                    return
                self._write_slot_paged(slot, job.cache1,
                                       start=job.shared_len, stop=job.S,
                                       uid=req.uid)
                pages = self.slot_pages[slot]
                self._table[slot, :] = NULL_PAGE
                self._table[slot, : len(pages)] = pages
                if self.registry is not None and job.prompt_key is not None:
                    self.registry.register(
                        job.prompt_key, pages[: math.ceil(job.S / ps)])
            else:
                self._write_slot(slot, job.cache1, req.uid)
            self.slot_prefill[slot] = None
            self._start_slot(slot, req, job.S, tok, job.tokens, job.resumed)

    def _grow_slot_pages(self, slot: int, n_pages: int):
        """In-flight prefill page growth: extend this slot's page list to
        ``n_pages``.  The allocator is charged now but the table row stays
        unpublished until the DECODING transition.  Raises ``PoolExhausted``
        — callers abort the one job to the retry path, never the batch."""
        need = n_pages - len(self.slot_pages[slot])
        if need > 0:
            self.slot_pages[slot].extend(self._alloc_pages(need))

    # -- paged-pool plumbing --------------------------------------------------

    def _can_alloc_pages(self, n: int) -> bool:
        """Allocator probe, threaded through the ``alloc_fail`` injection
        point so transient pool pressure is testable deterministically."""
        fi = self.fault_injector
        if fi is not None and fi.alloc_fail(self.tick):
            return False
        return self.allocator.can_alloc(n)

    def _alloc_pages(self, n: int) -> List[int]:
        """Page allocation, threaded through the ``alloc_fail`` injection
        point.  Callers treat ``PoolExhausted`` as a transient fault — the
        affected request retries or fails, never the whole batch."""
        fi = self.fault_injector
        if fi is not None and fi.alloc_fail(self.tick):
            raise PoolExhausted(f"injected allocation failure at tick {self.tick}")
        return self.allocator.alloc(n)

    def audit_pages(self):
        """Invariant check: allocator refcounts and free list must equal
        the live slot→page mapping exactly, and the host page table must
        mirror it.  No-op for contiguous engines.  Raises
        ``paged.PageAuditError`` on the first divergence — the chaos
        harness runs it after every tick (and ``audit_every_step=True``
        folds it into ``step()``), so a leak is caught on the tick that
        caused it.

        Note the audit is engine-relative: pages retained by an *external*
        holder (e.g. a caller pinning prefix pages) are outside the slot
        mapping and would trip it — that is why per-step auditing is
        opt-in rather than always-on."""
        if not self.paged:
            return
        if self._owns_allocator:
            # a shared allocator's refcounts span several engines: the
            # owning MixedServingEngine audits the union of every member's
            # _page_refs; each member still runs its table-mirror checks.
            self.allocator.audit(self._page_refs())
        for slot in range(self.max_batch):
            pages = self.slot_pages[slot]
            row = self._table[slot]
            if self.slot_prefill[slot] is not None:
                # in-flight chunked prefill: pages are allocator-charged
                # (the refs above include them) but the row must stay
                # all-NULL until the DECODING transition — a published row
                # would let batched-decode scatters corrupt real pages.
                if not np.all(row == NULL_PAGE):
                    raise PageAuditError(
                        f"slot {slot}: prefilling slot published table row "
                        f"{row.tolist()} before its DECODING transition")
                continue
            if not (np.array_equal(row[: len(pages)],
                                   np.asarray(pages, np.int32))
                    and np.all(row[len(pages):] == NULL_PAGE)):
                raise PageAuditError(
                    f"slot {slot}: table row {row.tolist()} does not mirror "
                    f"the slot mapping {pages}")
            if self.slot_req[slot] is None and pages:
                raise PageAuditError(
                    f"slot {slot}: free slot still owns pages {pages}")
            if self.slot_xpages is not None:
                xpages = self.slot_xpages[slot]
                xrow = self._xtable[slot]
                if not (np.array_equal(xrow[: len(xpages)],
                                       np.asarray(xpages, np.int32))
                        and np.all(xrow[len(xpages):] == NULL_PAGE)):
                    raise PageAuditError(
                        f"slot {slot}: frame table row {xrow.tolist()} does "
                        f"not mirror the slot mapping {xpages}")
                if self.slot_req[slot] is None and xpages:
                    raise PageAuditError(
                        f"slot {slot}: free slot still owns frame pages "
                        f"{xpages}")

    def _page_refs(self) -> List[int]:
        """Every page reference this engine holds (decoder KV pages plus
        enc-dec frame pages), as the allocator-audit live list."""
        refs = [p for pages in self.slot_pages for p in pages]
        if self.slot_xpages is not None:
            refs += [p for pages in self.slot_xpages for p in pages]
        return refs

    def _cache_entries(self):
        """Yield (container, key, entry) over the per-layer cache dicts so
        pool leaves can be replaced in place (``container[key] = new``).
        Transformer-family caches carry unit/rem layer lists; the enc-dec
        paged cache carries one stacked decoder entry (its ``x`` pools are
        written by ``_write_slot_xpages``, never COWed — frame pages are
        single-owner)."""
        if "unit" in self.cache:
            for lst in (self.cache["unit"], self.cache["rem"]):
                for i in range(len(lst)):
                    yield lst, i, lst[i]
        else:
            yield self.cache, "dec", self.cache["dec"]

    def _c1_entries(self, cache1) -> list:
        """The batch-1 contiguous prefill cache's entries, aligned 1:1 with
        ``_cache_entries`` (enc-dec: the decoder self-attn k/v)."""
        if "unit" in cache1:
            return list(cache1["unit"]) + list(cache1["rem"])
        return [{"k": cache1["k"], "v": cache1["v"]}]

    def _copy_page(self, src: int, dst: int):
        """pool[dst] <- pool[src] across every paged leaf (all layers)."""
        for lst, i, entry in self._cache_entries():
            if isinstance(entry, dict) and "k_pages" in entry:
                new = dict(entry)
                for pk, _ in _PAGED_KEYS:
                    if pk in entry:
                        arr = entry[pk]
                        new[pk] = arr.at[:, dst].set(arr[:, src])
                lst[i] = new

    def _ensure_private(self, slot: int, logical_page: int):
        """Copy-on-write guard: the page about to be written must be
        privately owned.  With eager boundary COW at admission this never
        fires in steady state; it is the enforced invariant that makes
        refcount > 1 pages read-only no matter how sharing evolves."""
        phys = self.slot_pages[slot][logical_page]
        if self.allocator.refcount[phys] > 1:
            # PoolExhausted propagates to the caller: admission paths
            # release-and-retry the request; _publish_table quarantines
            # the slot — the batch itself never crashes on COW pressure.
            new = self._alloc_pages(1)[0]
            self._copy_page(phys, new)
            self.allocator.release([phys])
            self.slot_pages[slot][logical_page] = new
            self._table[slot, logical_page] = new
            self.stats.cow_copies += 1

    def _write_slot_paged(self, slot: int, cache1, start: int, stop: int,
                          uid: int):
        """Scatter a batch-1 contiguous prefill cache into this slot's pages
        (positions [start, stop)); non-paged leaves (sliding-window rings,
        recurrent states) take the per-slot row insert
        (``_insert_slot_rows``)."""
        ps = self.page_size
        pos_w = np.arange(start, stop)
        for lp in sorted({int(p) // ps for p in pos_w}):
            self._ensure_private(slot, lp)
        phys = np.asarray(
            [self.slot_pages[slot][p // ps] for p in pos_w], np.int32)
        off = (pos_w % ps).astype(np.int32)
        c1_entries = self._c1_entries(cache1)
        for n, (lst, i, entry) in enumerate(self._cache_entries()):
            one = c1_entries[n]
            if isinstance(entry, dict) and "k_pages" in entry:
                if len(pos_w) == 0:
                    continue
                new = dict(entry)
                for pk, ck in _PAGED_KEYS:
                    if pk in entry:
                        new[pk] = write_pages(
                            entry[pk], phys, off, one[ck][:, 0, pos_w])
                lst[i] = new
        self._insert_slot_rows(slot, cache1, uid)
        if self.slot_xpages is not None:
            self._write_slot_xpages(slot, cache1)

    def _write_slot_xpages(self, slot: int, cache1):
        """Scatter the prefill's per-layer cross-attention K/V (the encoded
        frames) into this slot's frame pages.  Frame pages are written once
        here and read-only for the sequence's life — single-owner, so no
        COW guard is needed."""
        ps = self.page_size
        nf = int(self.cfg.n_frames)
        pos_w = np.arange(nf)
        phys = np.asarray(
            [self.slot_xpages[slot][p // ps] for p in pos_w], np.int32)
        off = (pos_w % ps).astype(np.int32)
        x = self.cache["x"]
        new = dict(x)
        for pk, ck in (("k_pages", "xk"), ("v_pages", "xv")):
            new[pk] = write_pages(x[pk], phys, off, cache1[ck][:, 0, pos_w])
        self.cache["x"] = new

    def _free_slot_pages(self, slot: int):
        freed = self.allocator.release(self.slot_pages[slot])
        if self.registry is not None:
            self.registry.evict(freed)
        self.slot_pages[slot] = []
        self._table[slot, :] = NULL_PAGE
        if self.slot_xpages is not None:
            self.allocator.release(self.slot_xpages[slot])
            self.slot_xpages[slot] = []
            self._xtable[slot, :] = NULL_PAGE

    # -- contiguous-slot plumbing ---------------------------------------------

    def _insert_rows(self, pool, one, slot: int):
        """``pool`` with the batch-1 tree ``one`` written into row ``slot``,
        in place: one jitted update whose pool is donated, so no leaf is
        copied whole (the stacked Mamba state is 9.7 GB at 128 rows of
        granite-4.0-h-micro).  A leaf's batch axis is the one of size
        ``max_batch`` where ``one``'s is 1: attention caches keep it at -4,
        recurrent states first, the stacked Mamba state second.  The row is
        overwritten whole, so a reused slot keeps nothing of its previous
        occupant.  A buffer can be donated once only, so a leaf that
        shares its buffer with an earlier one (an init cache's k and v
        zeros) is copied first."""
        leaves, tree = jax.tree.flatten(pool)
        seen = set()
        for n, p in enumerate(leaves):
            if id(p) in seen:
                leaves[n] = jnp.copy(p)
            seen.add(id(p))
        pool = tree.unflatten(leaves)
        axes = tuple(
            next(i for i, n in enumerate(p.shape)
                 if n == self.max_batch and o.shape[i] == 1)
            for p, o in zip(leaves, tree.flatten_up_to(one)))
        return _set_rows(pool, one, jnp.int32(slot), axes)

    def _write_slot(self, slot: int, cache1, uid: int):
        """Copy a batch-1 cache into pool slot `slot` (batch axis index)."""
        with TraceAnnotation("engine.state_insert", uid=uid, slot=slot):
            self.cache = self._insert_rows(self.cache, cache1, slot)
            self.stats.state_inserts += 1

    def _insert_slot_rows(self, slot: int, cache1, uid: int):
        """Write a finished prefill's non-paged leaves (the stacked Mamba
        state, conv states, sliding-window rings) into row ``slot`` of the
        paged engine's cache (``_insert_rows``); a stack of paged layers
        alone has none."""
        entries = list(self._cache_entries())
        c1 = self._c1_entries(cache1)
        rows = [n for n, (_, _, e) in enumerate(entries)
                if not (isinstance(e, dict) and "k_pages" in e)]
        pool = {"rows": [entries[n][2] for n in rows]}
        one = {"rows": [c1[n] for n in rows]}
        if "ssm" in self.cache:
            pool["ssm"], one["ssm"] = self.cache["ssm"], cache1["ssm"]
        if not jax.tree.leaves(pool):
            return
        with TraceAnnotation("engine.state_insert", uid=uid, slot=slot):
            new = self._insert_rows(pool, one, slot)
            if "ssm" in new:
                self.cache["ssm"] = new["ssm"]
            for n, entry in zip(rows, new["rows"]):
                lst, i, _ = entries[n]
                lst[i] = entry
            self.stats.state_inserts += 1

    def _sample(self, logits, temperature: float):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self._rng, k = jax.random.split(self._rng)
        return jax.random.categorical(k, logits / temperature, axis=-1).astype(jnp.int32)

    def _finish_if_done(self, slot: int):
        if self.slot_remaining[slot] <= 0:
            req = self.slot_req[slot]
            self._release_slot(slot)
            req.transition(RequestState.FINISHED)
            req.finish_t = self.clock()
            self.stats.completed += 1

    def _publish_table(self, live: List[int], span: int = 0) -> List[int]:
        """COW guard on this tick's write targets (positions
        [pos, pos + span], possibly straddling page boundaries), then
        publish the table to the device-side cache pytree (the step reads
        it; the mapping itself never changes on device).  Returns the
        slots that remain live: a slot whose COW copy cannot be allocated
        (pool pressure, injected failure) is quarantined to the retry path
        instead of crashing the batch."""
        with TraceAnnotation("engine.publish_table", live=len(live)):
            ps = self.page_size
            ok_live: List[int] = []
            for slot in live:
                first = int(self.slot_pos[slot]) // ps
                last = (int(self.slot_pos[slot]) + span) // ps
                # pages past the allocated range map to NULL_PAGE (speculative
                # overrun): nothing to privatize there, the null page absorbs
                try:
                    for lp in range(
                            first, min(last, len(self.slot_pages[slot]) - 1) + 1):
                        self._ensure_private(slot, lp)
                except PoolExhausted as e:
                    self._quarantine_slot(
                        slot, f"copy-on-write allocation failed: {e}")
                    continue
                ok_live.append(slot)
            table = jnp.asarray(self._table)
            if self.mesh is not None:
                # the table is host-owned per replica: commit it to its
                # registered layout so the compiled step never resharding-
                # guesses (the mapping is identical on every model chip)
                table = jax.device_put(table, sl.named_sharding(
                    self.mesh, table.shape, *sl.axes_for("page_table"),
                    rules=self.rules))
            self.cache["page_table"] = table
            if self._xtable is not None:
                xtable = jnp.asarray(self._xtable)
                if self.mesh is not None:
                    xtable = jax.device_put(xtable, sl.named_sharding(
                        self.mesh, xtable.shape,
                        *sl.axes_for("encdec.xpage_table"), rules=self.rules))
                self.cache["xpage_table"] = xtable
            return ok_live

    # -- degradation ladder ---------------------------------------------------

    def _degrade(self, rung: str, reason: str):
        import warnings

        self.degraded[rung] = reason
        warnings.warn(
            f"{self.cfg.name}: degraded serving — {rung}: {reason}",
            stacklevel=3)

    def _degrade_speculation(self, reason: str):
        """Ladder rung 1: speculative → plain decode.  The spec cache
        layout (widened local rings, spec_k admission headroom) stays —
        only the draft/verify tick is switched off, so the fallback is a
        shape-compatible plain T=1 decode through the same compiled-step
        cache, taken mid-flight without dropping a single request."""
        self.spec_active = False
        self._degrade("speculative", reason)

    def _degrade_attention_kernel(self, reason: str):
        """Ladder rung 2: Pallas paged kernel → the pure-JAX gather
        reference (``layers.paged_decode_attention``), via the
        process-global ``force_attention_kernel`` hook plus a rebuild of
        the jitted steps — the override binds at trace time, so the old
        compiled steps must be retired.  Process-global on purpose (the
        fault is in the kernel, not this engine); tests that trigger it
        restore the override in a finally block."""
        from repro.models import layers

        layers.force_attention_kernel(False)
        self._degrade("attention_kernel", reason)
        self._build_steps()

    # -- the tick -------------------------------------------------------------

    def _poison_mask(self) -> jax.Array:
        """(B,) bool operand of the ``nan_logits`` injection point —
        all-False in normal operation, so the compiled step has one
        signature either way and injection costs no retrace."""
        poison = np.zeros((self.max_batch,), bool)
        fi = self.fault_injector
        if fi is not None:
            uids = fi.poison_uids(self.tick)
            if uids is not None:
                for slot, r in enumerate(self.slot_req):
                    if r is not None and (not uids or r.uid in uids):
                        poison[slot] = True
        return jnp.asarray(poison)

    def _to_host(self, *arrays, span: bool = True) -> list:
        """Blocking device->host read of ``arrays`` as numpy arrays, floats
        as float32 (what the host's sampling math takes): one host sync,
        counted in ``EngineStats.host_syncs``.  With ``span`` the wait for
        the device (``engine.device_wait``) and the copy with its
        conversion (``engine.fetch``) are profiler spans of their own; the
        per-row reads of the sampling loop pass ``span=False`` so that the
        loop stays one ``engine.sample`` span."""
        self.stats.host_syncs += 1

        def host(a):
            f32 = jnp.issubdtype(a.dtype, jnp.floating)
            return np.asarray(a, np.float32 if f32 else None)

        if not span:
            return [host(a) for a in arrays]
        with TraceAnnotation("engine.device_wait"):
            jax.block_until_ready(arrays)
        with TraceAnnotation("engine.fetch",
                             bytes=sum(a.nbytes for a in arrays)):
            return [host(a) for a in arrays]

    def _run_decode(self, tokens, pos):
        """Run the ONE compiled decode step with the folded numeric guard
        over host ``tokens`` (B, T) and positions (B,); returns the device
        (logits, per-slot finite flags), not yet read.  Handles the
        kernel-fault rung: an injected ``kernel_fault`` on a paged engine
        degrades the attention path to the pure-JAX reference and retries
        ONCE.  Any other exception propagates: a kernel the compiler
        refuses, or one that fails at run time, is a fault of the served
        path, not something to hide behind the reference datapath.

        The retry is safe because the injected fault raises host-side
        ahead of the call, leaving ``self.cache`` intact (the donated
        buffers are never consumed)."""
        B, T = tokens.shape
        with TraceAnnotation("engine.decode", B=B, T=T):
            tokens = jnp.asarray(tokens, jnp.int32)
            pos = jnp.asarray(pos, jnp.int32)
            poison = self._poison_mask()
            fi = self.fault_injector
            try:
                if fi is not None:
                    fi.check_kernel(self.tick,
                                    "attention_kernel" in self.degraded)
                logits, ok, self.cache = self._decode(
                    self.params, self.cache, tokens, pos, poison)
            except FaultInjected as e:
                if not self.paged or "attention_kernel" in self.degraded:
                    raise
                self._degrade_attention_kernel(str(e))
                logits, ok, self.cache = self._decode(
                    self.params, self.cache, tokens, pos, poison)
        return logits, ok

    def step(self) -> int:
        """One engine tick: deadlines → admission → one batched decode
        step (speculative draft + verify while the spec rung is healthy).
        Returns the number of tokens committed this tick.  Every executed
        tick beats the watchdog; dropped ticks (fault injection) do not —
        which is exactly what ``HeartbeatMonitor`` stall detection keys
        on.  Under ``jax.profiler`` the tick is one ``engine.step`` span
        numbered ``self.tick``, its phases spans below it
        (docs/architecture.md, "Spans and counters")."""
        self.tick += 1
        with StepTraceAnnotation("engine.step", step_num=self.tick):
            fi = self.fault_injector
            if fi is not None:
                fi.begin_tick(self.tick)
                if fi.drop_tick(self.tick):
                    return 0  # lost tick: no admission, no decode, no heartbeat
            self._enforce_deadlines(self.clock())
            with TraceAnnotation("engine.admit", queued=len(self.queue)):
                self._admit()
            if self.prefill_chunk is not None:
                self._run_prefill_chunks()
            live = self._decoding_slots()
            if live:
                if self.spec_active:
                    n = self._spec_step(live)
                else:
                    n = self._plain_step(live)
            else:
                n = 0
            if self.audit_every_step:
                self.audit_pages()
            if self.watchdog is not None:
                self.watchdog.beat(0)
            if live and self.degraded:
                self.stats.fallback_ticks += 1
            return n

    def _plain_step(self, live: List[int]) -> int:
        """One non-speculative decode tick over ``live``; returns committed
        tokens (quarantined slots commit nothing).

        The logits stay on the device.  Greedy rows take their tokens from
        one batched argmax over the whole (B, 1, V) block, read with the
        finite flags in the tick's one host sync ((B,) ids and flags, not
        the logits).  A tempered row samples its own device row in slot
        order, so the RNG splits fall as they would without greedy
        neighbours; only such rows pay a read each."""
        if self.paged:
            live = self._publish_table(live)
            if not live:
                return 0
        logits, ok = self._run_decode(self.slot_last_tok[:, None],
                                      self.slot_pos)
        ids, ok = self._to_host(self._sample(logits, 0.0), ok)
        committed = 0
        with TraceAnnotation("engine.sample", live=len(live)):
            for slot in live:
                req = self.slot_req[slot]
                if not ok[slot]:
                    self._quarantine_slot(
                        slot, "non-finite logits (quarantined)")
                    continue
                if req.temperature <= 0.0:
                    tok = int(ids[slot, 0])
                    self.stats.batched_choices += 1
                else:
                    # sampled in float32, to which bf16 logits convert
                    # exactly
                    row = logits[slot : slot + 1, 0].astype(jnp.float32)
                    (tok,) = self._to_host(
                        self._sample(row, req.temperature), span=False)
                    tok = int(tok[0])
                req.output.append(tok)
                self._emit(req, (tok,))
                self.slot_last_tok[slot] = tok
                self.slot_pos[slot] += 1
                self.slot_remaining[slot] -= 1
                committed += 1
                self._finish_if_done(slot)
        self.stats.decode_steps += 1
        self.stats.decode_tokens += committed
        return committed

    # -- speculative decode ---------------------------------------------------

    @staticmethod
    def _temp_softmax(row: np.ndarray, temperature: float) -> np.ndarray:
        """softmax(row / temperature) in float64 — the one sampling
        distribution shared by the draft chain and the accept/resample
        math (the rejection ratio must use the exact distribution the
        draft sampled from)."""
        z = row.astype(np.float64) / temperature
        z -= z.max()
        p = np.exp(z)
        return p / p.sum()

    def _host_sample(self, row: np.ndarray, temperature: float,
                     dist: Optional[np.ndarray] = None):
        """Sample one token from a logits row on the host.  Returns
        (token, its sampling distribution — None for the greedy point
        mass).  ``dist`` reuses a precomputed ``_temp_softmax``.  Host-side
        numpy sampling keeps the draft chain's per-slot temperatures
        independent of the target's jax RNG stream — greedy streams are
        identical to the non-speculative engine; stochastic streams are
        distributionally correct but use this separate RNG."""
        if temperature <= 0.0:
            return int(np.argmax(row)), None
        p = self._temp_softmax(row, temperature) if dist is None else dist
        return int(self._np_rng.choice(p.size, p=p)), p

    def _accept(self, logits_rows: np.ndarray, drafts: np.ndarray,
                draft_dists: Optional[np.ndarray], temperature: float):
        """Standard speculative rejection sampling against the verify
        logits.  logits_rows: (k+1, V) target logits (row j predicts the
        token after verify input j); drafts: (k,) proposed tokens;
        draft_dists: (k, V) draft sampling distributions (None under
        greedy).  Returns (accepted_draft_count, committed tokens) — the
        accepted draft prefix plus exactly one resampled/bonus token, so
        even an all-rejected tick commits one token (the tick never
        stalls).

        Greedy (temperature 0) degenerates to longest-prefix argmax match:
        the committed stream is bit-identical to the non-speculative
        engine's.  Stochastically, draft token d is kept with probability
        min(1, p_target(d) / p_draft(d)) and the first rejection resamples
        from the residual max(0, p_target - p_draft) — the committed
        stream is distributed exactly as target-model sampling.
        """
        k = drafts.shape[0]
        if temperature <= 0.0:
            tgt = np.argmax(logits_rows, axis=-1)  # (k+1,)
            a = 0
            while a < k and int(drafts[a]) == int(tgt[a]):
                a += 1
            return a, [int(t) for t in tgt[: a + 1]]
        out: List[int] = []
        a = 0
        for j in range(k):
            p_t = self._temp_softmax(logits_rows[j], temperature)
            p_d = draft_dists[j]
            d = int(drafts[j])
            if self._np_rng.random() < min(1.0, p_t[d] / max(p_d[d], 1e-30)):
                out.append(d)
                a += 1
                continue
            residual = np.maximum(p_t - p_d, 0.0)
            tot = residual.sum()
            if tot <= 0.0:  # distributions identical: any p_t sample works
                residual, tot = p_t, 1.0
            out.append(int(self._np_rng.choice(residual.size, p=residual / tot)))
            return a, out
        # all k drafts accepted: bonus token from the last verify position
        tok, _ = self._host_sample(logits_rows[k], temperature)
        out.append(tok)
        return a, out

    def _spec_step(self, live: List[int]) -> int:
        """One speculative tick: k draft-model steps propose tokens, ONE
        multi-token target step verifies all k+1 positions, the accepted
        prefix commits.

        Rollback is free by construction: every tick writes the k+1
        positions starting at the committed frontier, the frontier advances
        by >= 1, so the stale (rejected) tail of one tick — at most k
        entries — always lies inside the next tick's write range and is
        overwritten before the position masks would ever expose it.  The
        same argument covers the draft cache (its accepted prefix is
        exactly what it wrote), paged pools (position-identity addressing),
        and widened local rings (window + spec_k slots; see
        ``transformer.init_layer_cache``).

        Failure model: a raising or numerically-poisoned draft chain
        degrades speculation (rung 1) and serves this very tick plain —
        the target never depends on the draft's health.  Per-slot
        non-finite *verify* logits quarantine that slot only; its draft
        proposals are excluded from acceptance accounting so
        ``accept_rate`` stays meaningful."""
        k = self.spec_k
        B = self.max_batch
        pos0 = jnp.asarray(self.slot_pos, jnp.int32)
        try:
            drafts, draft_dists = self._draft_chain(live, pos0, k, B)
        except Exception as e:
            # rung 1: dead/poisoned draft — the target serves on, plain
            self._degrade_speculation(f"draft phase failed: {e}")
            return self._plain_step(live)
        # -- verify phase: ONE (B, k+1) multi-token target step ---------------
        if self.paged:
            live = self._publish_table(live, span=k)
            if not live:
                return 0
        tokens = np.concatenate(
            [np.asarray(self.slot_last_tok, np.int64)[:, None], drafts], axis=1)
        arr, ok = self._to_host(*self._run_decode(tokens, pos0))
        # -- commit the accepted prefix (+ the guaranteed bonus token) --------
        committed_total = 0
        tick_accepted = 0
        proposed = 0
        n_verified = len(live)
        with TraceAnnotation("engine.sample", live=len(live)):
            for slot in live:
                req = self.slot_req[slot]
                if not ok[slot]:
                    self._quarantine_slot(
                        slot, "non-finite verify logits (quarantined)")
                    continue
                remaining = int(self.slot_remaining[slot])
                a, toks = self._accept(
                    arr[slot], drafts[slot], draft_dists[slot], req.temperature)
                c = min(len(toks), remaining)
                toks = toks[:c]
                self.stats.draft_proposed += k
                proposed += k
                # committed drafts: toks is [d_1..d_a, bonus]; truncation by
                # remaining can clip the bonus, in which case ALL c committed
                # tokens are accepted drafts (min handles both cases)
                self.stats.draft_accepted += min(a, c)
                tick_accepted += min(a, c)
                req.output.extend(toks)
                self._emit(req, toks)
                self.slot_last_tok[slot] = toks[-1]
                self.slot_pos[slot] += c
                self.slot_remaining[slot] -= c
                committed_total += c
                self._finish_if_done(slot)
        self.stats.decode_steps += 1
        self.stats.decode_tokens += committed_total
        self.stats.verified_positions += n_verified * (k + 1)
        self._spec_ticks += 1
        # feed measured acceptance back into the sizer (EMA): its
        # committed_per_tick / throughput picks track observed traffic
        # instead of the configured spec_accept prior
        if (self.sizer is not None and getattr(self.sizer, "spec_k", 0) > 0
                and proposed > 0):
            tick_rate = min(1.0, tick_accepted / proposed)
            self.sizer = self.sizer.observe_accept(tick_rate)
            # rung 1, soft trigger: once warmed up, speculation switches
            # itself off when the observed-acceptance payoff model says a
            # plain tick would commit more tokens per second
            if (self.spec_fallback_accept is not None
                    and self._spec_ticks >= self.spec_fallback_min_ticks
                    and not self.sizer.spec_worthwhile(
                        max(1, n_verified),
                        min_accept=self.spec_fallback_accept)):
                self._degrade_speculation(
                    f"acceptance collapsed (EMA {self.sizer.spec_accept:.3f}"
                    f" < floor {self.spec_fallback_accept:g} or modeled "
                    f"payoff < 1)")
        return committed_total

    def _draft_chain(self, live: List[int], pos0, k: int, B: int):
        """The k+1 sequential draft steps proposing k tokens (see
        ``_spec_step`` for why k+1).  Raises on a dead draft (injected or
        real) or non-finite draft logits — per-slot masking cannot save a
        chain whose proposals feed later steps, so the caller degrades
        speculation instead."""
        with TraceAnnotation("engine.draft", k=k):
            fi = self.fault_injector
            if fi is not None:
                fi.check_draft(self.tick)
            drafts = np.zeros((B, k), np.int64)
            draft_dists: List[Optional[np.ndarray]] = [None] * B
            needs_dists = any(
                self.slot_req[s].temperature > 0.0 for s in live)
            if needs_dists:
                draft_dists = [
                    np.zeros((k, self.cfg.vocab)) if self.slot_req[s] is not None
                    else None for s in range(B)]
            cur = jnp.asarray(self.slot_last_tok, jnp.int32)[:, None]
            # k+1 draft steps for k proposals: the last step writes the final
            # draft's KV (its logits are discarded), so after a fully-accepted
            # tick the draft cache has no hole at the new frontier - 1 — the
            # accepted prefix is always exactly what the draft itself wrote.
            for j in range(k + 1):
                dlogits, self.draft_cache = self._draft_decode(
                    self.draft_params, self.draft_cache, cur, pos0 + j)
                if j == k:
                    break
                (rows,) = self._to_host(dlogits[:, 0])
                if not np.isfinite(rows[live]).all():
                    raise FloatingPointError("non-finite draft logits")
                nxt = np.asarray(self.slot_last_tok).copy()
                for slot in live:
                    temp = self.slot_req[slot].temperature
                    tok, dist = self._host_sample(rows[slot], temp)
                    drafts[slot, j] = tok
                    nxt[slot] = tok
                    if dist is not None:
                        draft_dists[slot][j] = dist
                cur = jnp.asarray(nxt, jnp.int32)[:, None]
            return drafts, draft_dists

    def run_until_done(self, max_ticks: int = 10000) -> EngineStats:
        for _ in range(max_ticks):
            if not self.queue and not self._live_slots():
                break
            self.step()
        return self.stats
