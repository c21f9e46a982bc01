"""Serving driver: continuous-batching engine demo / load generator.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --smoke \
        --requests 32 --max-new 16 --compress quant_sparse --q-prune 0.5 \
        --kv-dtype int8 --page-size 16 --share-prefix --plan-cache /tmp/plan

Reports throughput, mean batch occupancy (the realized paper-style weight
reuse factor), and the n_opt the BatchSizer would pick on the target
hardware.  ``--compress`` serves through a compressed-weight execution plan
(core/weight_plan): the weight stream shrinks by quantization and/or block
pruning and the reported n_opt moves accordingly (Section 5.6).
``--kv-dtype int8`` serves with the quantized KV cache (halved kv_read
stream); ``--page-size N`` serves with the paged KV cache (pool of N-token
pages + page table instead of a max_len reservation per slot; ``--pool-pages``
caps the pool, ``--share-prefix`` maps common prompt prefixes copy-on-write);
``--plan-cache DIR`` persists the packed pytree so later engine boots skip
the pack step entirely.  ``--draft-config ARCH --spec-k K`` serves with
speculative decode: the draft model proposes K tokens per tick and the
target verifies all K+1 positions in one multi-token step through the
same compressed datapath (draft positions amortize the weight stream like
extra batch samples).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import time
import warnings

import jax
import numpy as np

import repro.configs as C
from repro.core import autotune as AT
from repro.core.batching import UNBOUNDED_NOPT, BatchSizer, mean_decode_context
from repro.core.perf_model import paged_pool_pages
from repro.core.weight_plan import PlanConfig, load_plan, save_plan
from repro.distributed import shardlib as sl
from repro.launch import mesh as M
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import (
    get_api,
    kv_bytes_per_token,
    supports_int8_kv,
    supports_paged_kv,
)
from repro.serving.config import config_from_args
from repro.serving.engine import Request, ServingEngine
from repro.serving.faultinject import TickClock
from repro.serving.mixed import MixedServingEngine, WorkloadSpec
from repro.serving.loadgen import (
    LengthMixture,
    load_trace,
    make_requests,
    poisson_trace,
    run_open_loop,
)


def _fmt_nopt(n: int) -> str:
    return "inf (memory-bound at any batch)" if n >= UNBOUNDED_NOPT else str(n)


def _open_loop_mixture(p: int, n: int, cap: int) -> LengthMixture:
    """Chat-style mixture anchored at the CLI lengths: 70% of arrivals at
    the --prompt-len scale, 25% up to 2x, 5% at ~4x (the long-prefill
    tail continuous batching exists for), every component clamped so
    prompt + max_new fits the engine's admission bound ``cap``."""
    n_rng = (max(1, n // 2), max(1, n))

    def pr(a, b):
        hi = max(1, cap - n_rng[1])
        a = max(1, min(a, hi))
        return (a, max(a, min(b, hi)))

    return LengthMixture((
        (0.70, pr(max(1, p // 2), p), n_rng),
        (0.25, pr(p, 2 * p), n_rng),
        (0.05, pr(4 * p, 4 * p), n_rng),
    ))


def _build_plan(api, cfg, params, pc: PlanConfig, cache_dir: str | None):
    """Compress (or restore) the serving plan; the cache stores the packed
    pytree + metadata via checkpoint/store so boots skip re-packing."""
    if cache_dir:
        try:
            plan = load_plan(cache_dir, params)
            if plan.cfg == pc:
                print(f"[serve] plan cache hit: {cache_dir}")
                return plan
            print("[serve] plan cache stale (config changed); re-packing")
        except FileNotFoundError:
            pass
        except ValueError as e:
            # saved for a different arch/shape: re-pack rather than abort
            print(f"[serve] plan cache incompatible ({e}); re-packing")
    t0 = time.time()
    plan = api.compress(cfg, params, pc)
    print(f"[serve] packed weights in {time.time() - t0:.2f}s")
    if cache_dir:
        save_plan(cache_dir, plan)
        print(f"[serve] plan cached to {cache_dir}")
    return plan


def _init_params(api, cfg, seed: int, mesh, rules):
    """Random weights from ``seed``, created under their shardings when a
    mesh is given (no device ever holds the whole dense model)."""
    init = functools.partial(api.init_params, cfg)
    key = jax.random.key(seed)
    if mesh is None:
        return jax.jit(init)(key)
    shardings = sl.tree_shardings(
        jax.eval_shape(init, key), api.param_axes(cfg), mesh=mesh, rules=rules)
    return jax.jit(init, out_shardings=shardings)(key)


def _parse_mix(spec: str, ap) -> list:
    """'arch:weight,arch:weight' -> [(arch, weight)] (weight defaults 1)."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        arch, _, w = part.partition(":")
        if arch not in C.ARCH_IDS:
            ap.error(f"--workload-mix: unknown arch {arch!r} "
                     f"(choose from {', '.join(C.ARCH_IDS)})")
        try:
            weight = float(w) if w else 1.0
        except ValueError:
            ap.error(f"--workload-mix: bad weight {w!r} for {arch}")
        out.append((arch, weight))
    if not out:
        ap.error("--workload-mix: empty spec")
    return out


def _main_mixed(args, ap):
    """Heterogeneous closed-loop serving: one MixedServingEngine admits
    every family in the mix — per-family compiled steps and sizers, one
    shared page pool, one submit/step/stats surface."""
    mix = _parse_mix(args.workload_mix, ap)
    mesh = M.make_serving_mesh(args.mesh)
    rng = np.random.default_rng(args.seed)
    specs, apis = [], {}
    for arch, weight in mix:
        cfg = C.get_config(arch, smoke=args.smoke)
        api = get_api(cfg)
        params = api.init_params(cfg, jax.random.key(args.seed))
        paged = args.page_size > 0 and supports_paged_kv(cfg)
        ctx = (mean_decode_context(args.prompt_len + api.prefix_len(cfg),
                                   args.max_new) if paged else args.max_len)
        rules = M.rules_for(cfg, None, mesh=mesh) if mesh is not None else None
        ec = config_from_args(args, mesh=mesh, rules=rules,
                              expected_context=ctx if paged else None)
        if ec.cache.kv_dtype and not supports_int8_kv(cfg):
            # per-family downgrade, not per-run: whisper keeps an fp cache
            # while the text member of the same mix serves int8
            ec = dataclasses.replace(
                ec, cache=dataclasses.replace(ec.cache, kv_dtype=None))
        specs.append(WorkloadSpec(name=arch, cfg=cfg, params=params,
                                  config=ec, weight=weight))
        apis[arch] = (cfg, api)
    engine = MixedServingEngine(specs, num_pages=args.pool_pages or None)
    print(f"[serve] workload mix: "
          + ", ".join(f"{a}:{w:g}" for a, w in mix)
          + f" (one engine, {len(mix)} compiled step sets)")
    if engine.allocator is not None:
        print(f"[serve] shared page pool: {engine.num_pages} pages x "
              f"{args.page_size} tok across "
              f"{sum(e.paged for e in engine.engines.values())} paged "
              f"families")
    n_total = args.requests
    uid = 0
    for arch, weight in mix:
        cfg, api = apis[arch]
        n = max(1, round(n_total * engine.sizer.share(arch)))
        for _ in range(n):
            extras = {}
            if "patches" in api.extra_keys:
                extras["patches"] = rng.normal(
                    size=(cfg.n_patches, cfg.d_model)).astype(np.float32)
            if "frames" in api.extra_keys:
                extras["frames"] = rng.normal(
                    size=(cfg.n_frames, cfg.d_model)).astype(np.float32)
            engine.submit(arch, Request(
                uid=uid,
                prompt=rng.integers(0, cfg.vocab,
                                    size=args.prompt_len).astype(np.int32),
                max_new_tokens=args.max_new,
                extras=extras or None,
            ))
            uid += 1
    t0 = time.time()
    stats = engine.run_until_done()
    dt = time.time() - t0
    engine.audit_pages()  # raises on any cross-family page leak
    for arch, s in stats.items():
        print(f"[serve]   {arch}: {s.completed} completed, "
              f"{s.decode_tokens} tokens, mean batch {s.mean_batch:.2f} "
              f"(n_opt {_fmt_nopt(engine.sizer.n_opt[arch])})")
    agg = engine.aggregate_stats()
    print(f"[serve] mixed: {agg.completed}/{uid} requests in {dt:.2f}s; "
          f"{agg.decode_tokens} tokens "
          f"({agg.decode_tokens / max(dt, 1e-9):.1f} tok/s on this host), "
          f"{engine.tick} ticks, page audit clean")
    return engine, stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=C.ARCH_IDS)
    ap.add_argument("--workload-mix", default=None, metavar="SPEC",
                    help="heterogeneous serving: comma-separated "
                         "'arch:weight' list (e.g. 'tinyllama-1.1b:2,"
                         "whisper-tiny:1') served by ONE MixedServingEngine "
                         "— one engine tick runs each family's own compiled "
                         "step and all paged families draw from one shared "
                         "page pool; --requests splits by weight "
                         "(closed-loop only, replaces --arch)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compress", default="none",
                    choices=("none", "quant", "block_sparse", "quant_sparse"),
                    help="weight representation for the serving plan")
    ap.add_argument("--q-prune", type=float, default=0.0,
                    help="block-pruned fraction for the sparse representations")
    ap.add_argument("--block", type=int, default=128, help="sparse block edge (bk=bn)")
    ap.add_argument("--kv-dtype", default="fp", choices=("fp", "int8"),
                    help="KV cache dtype (int8 = quantized cache, halved kv stream)")
    ap.add_argument("--page-size", type=int, default=0, metavar="N",
                    help="serve with the paged KV cache: pool of N-token "
                         "pages + per-sequence page table (0 = contiguous "
                         "max_len reservation per slot)")
    ap.add_argument("--pool-pages", type=int, default=0, metavar="P",
                    help="paged pool capacity in pages (0 = size for the "
                         "workload via perf_model.paged_pool_pages: max_batch "
                         "sequences at the actual prompt+max_new context)")
    ap.add_argument("--share-prefix", action="store_true",
                    help="paged mode: map common prompt prefixes to shared "
                         "physical pages (copy-on-write)")
    ap.add_argument("--plan-cache", default=None, metavar="DIR",
                    help="persist/restore the packed plan so engines boot "
                         "from packed weights instead of re-packing")
    ap.add_argument("--autotune-plan", default=None, metavar="PATH",
                    help="serve a TunedPlan artifact (tools/autotune.py): "
                         "its per-leaf plan rules and serving knobs (kv "
                         "dtype, page geometry, max batch/len) override the "
                         "corresponding flags; incompatible with --compress")
    ap.add_argument("--mesh", default="none", metavar="SPEC",
                    help="shard the serving plan over a device mesh via the "
                         "axis-rules registry: 'none' (default), 'host' "
                         "(1 x n_devices as data x model), or 'DxM' (e.g. "
                         "4x2)")
    ap.add_argument("--draft-config", default=None, choices=C.ARCH_IDS,
                    metavar="ARCH",
                    help="speculative decode: draft-model architecture "
                         "proposing --spec-k tokens per tick (same vocab as "
                         "--arch; verified in one multi-token target step)")
    ap.add_argument("--spec-k", type=int, default=0, metavar="K",
                    help="draft tokens proposed+verified per tick (0 = "
                         "plain decode; needs --draft-config)")
    ap.add_argument("--request-timeout", type=float, default=0.0, metavar="S",
                    help="total-latency deadline per request in seconds; a "
                         "request exceeding it is TIMED_OUT and its slot/"
                         "pages free immediately (0 = no deadline)")
    ap.add_argument("--ttft-deadline", type=float, default=0.0, metavar="S",
                    help="queue-to-first-token deadline in seconds (0 = no "
                         "deadline)")
    ap.add_argument("--max-retries", type=int, default=1, metavar="N",
                    help="bounded retries per request on transient faults "
                         "(non-finite logits, page-pool pressure); resumes "
                         "from the committed prefix with backoff")
    ap.add_argument("--evict-policy", default="fifo",
                    choices=("fifo", "priority"),
                    help="admission under pressure: 'fifo' queues (back-"
                         "pressure), 'priority' preempts the lowest-priority "
                         "slot (snapshot + requeue, prefill-from-prefix "
                         "readmission)")
    ap.add_argument("--prefill-chunk", type=int, default=0, metavar="C",
                    help="continuous batching: prefill long prompts in "
                         "C-token chunks interleaved with decode ticks "
                         "instead of synchronously at admission (0 = "
                         "synchronous inline prefill)")
    ap.add_argument("--prefill-budget", type=int, default=0, metavar="T",
                    help="max prompt tokens advanced per tick across all "
                         "in-flight chunked prefills (0 = one chunk per "
                         "tick; needs --prefill-chunk)")
    ap.add_argument("--arrival-rate", type=float, default=0.0, metavar="R",
                    help="open-loop load: seeded Poisson arrivals at R "
                         "requests per engine tick instead of submitting "
                         "all --requests upfront (0 = closed-loop)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="open-loop load: replay a JSONL arrival trace "
                         "(serving/loadgen format; takes precedence over "
                         "--arrival-rate)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.workload_mix:
        for flag, ok in (("--arch", not args.arch),
                         ("--autotune-plan", not args.autotune_plan),
                         ("--compress", args.compress == "none"),
                         ("--draft-config", not args.draft_config),
                         ("--trace", not args.trace),
                         ("--arrival-rate", args.arrival_rate == 0)):
            if not ok:
                ap.error(f"--workload-mix is closed-loop heterogeneous "
                         f"serving; drop {flag}")
        return _main_mixed(args, ap)
    if not args.arch:
        ap.error("one of --arch / --workload-mix is required")

    cfg = C.get_config(args.arch, smoke=args.smoke)
    tuned = None
    if args.autotune_plan:
        if args.compress != "none":
            ap.error("--autotune-plan carries its own plan; drop --compress")
        tuned = AT.load_tuned(args.autotune_plan)
        if tuned["arch"] != cfg.name:
            ap.error(f"--autotune-plan was searched for {tuned['arch']!r}, "
                     f"this run serves {cfg.name!r}")
        # the artifact owns the knobs the search optimized over; flags it
        # does not cover (spec decode needs --draft-config) stay CLI-set
        s = tuned["serving"]
        args.kv_dtype = s.get("kv_dtype", args.kv_dtype)
        args.page_size = int(s.get("page_size") or 0)
        args.pool_pages = int(s.get("num_pages") or 0)
        args.max_batch = int(s.get("max_batch") or args.max_batch)
        args.max_len = int(s.get("max_len") or args.max_len)
        pr = tuned.get("predicted", {})
        print(f"[serve] autotune plan {args.autotune_plan}: "
              f"strategy={tuned['strategy']} trials={tuned['trials']} "
              f"seed={tuned['seed']}; predicted "
              f"{pr.get('tokens_per_s') or 0:.0f} tok/s "
              f"({pr.get('speedup') or 1:.2f}x uniform), accuracy budget "
              f"{tuned['accuracy']['budget']:.1%} at max "
              f"q={tuned['accuracy']['max_q']:.2f}")
    api = get_api(cfg)
    mesh = M.make_serving_mesh(args.mesh)
    rules = M.rules_for(cfg, None, mesh=mesh) if mesh is not None else None
    params = _init_params(api, cfg, args.seed, mesh, rules)
    kv_dtype = "int8" if args.kv_dtype == "int8" else None
    if kv_dtype and not supports_int8_kv(cfg):
        kv_dtype = None  # engine would warn and serve fp: log the fp budget
    paged = args.page_size > 0 and supports_paged_kv(cfg)
    # contiguous mode reads the whole max_len reservation (ring length);
    # paged mode reads only what a request wrote: charge the sizer's kv
    # term with the workload's actual mean context.
    ctx = (mean_decode_context(args.prompt_len + api.prefix_len(cfg), args.max_new)
           if paged else args.max_len)
    kv_tok = kv_bytes_per_token(cfg, jax.numpy.int8 if kv_dtype else None,
                                context_len=ctx)
    data_parallel, model_parallel, kv_parallel = sl.parallelism_degrees(
        mesh, rules if rules is not None else sl.DEFAULT_RULES,
        int(getattr(cfg, "n_kv_heads", 0) or 0))
    if mesh is not None:
        print(f"[serve] mesh {dict(mesh.shape)}: data-parallel "
              f"{data_parallel}, model-parallel {model_parallel}, "
              f"kv shard degree {kv_parallel}")
    spec_k = args.spec_k
    draft_cfg = draft_params = None
    if args.draft_config and spec_k <= 0:
        ap.error("--draft-config needs --spec-k > 0 (it would otherwise be "
                 "silently ignored)")
    if spec_k > 0:
        if not args.draft_config:
            ap.error("--spec-k needs --draft-config")
        draft_cfg = C.get_config(args.draft_config, smoke=args.smoke)
        if draft_cfg.vocab != cfg.vocab:
            ap.error(f"--draft-config vocab {draft_cfg.vocab} != target "
                     f"vocab {cfg.vocab}")
        draft_params = get_api(draft_cfg).init_params(
            draft_cfg, jax.random.key(args.seed + 1))
        print(f"[serve] speculative decode: {draft_cfg.name} drafts "
              f"{spec_k} tokens/tick, verified in one (B, {spec_k + 1}) "
              f"target step")
    sizer = BatchSizer(n_params=api.n_params_exact(cfg),
                       kv_bytes_per_token=kv_tok, context_len=ctx,
                       model_parallel=model_parallel, kv_parallel=kv_parallel,
                       spec_k=spec_k)
    print(f"[serve] {cfg.name}: n_params={api.n_params_exact(cfg):,} "
          f"machine-balance n_opt={_fmt_nopt(sizer.n_opt)} per model group"
          + (f" (x{data_parallel} data replicas for the global batch)"
             if data_parallel > 1 else "")
          + f" (modeled with TPU v5e peaks, kv={kv_tok:.0f} B/tok @ ctx "
            f"{ctx}; serving on {jax.devices()[0].device_kind})")

    plan = None
    if tuned is not None:
        plan = _build_plan(api, cfg, params, AT.plan_config(tuned),
                           args.plan_cache)
    elif args.compress != "none":
        plan = _build_plan(api, cfg, params, PlanConfig(
            default=args.compress, q_prune=args.q_prune,
            bk=args.block, bn=args.block,
        ), args.plan_cache)
    if plan is not None:
        if mesh is not None:
            plan = plan.placed(mesh, rules)
        params = plan.params

    pool_pages = args.pool_pages
    if paged and not pool_pages:
        # size the pool for the workload, not for max_len: max_batch
        # concurrent sequences at their *allocated* context (admission
        # charges the full S + max_new, unlike the sizer's per-step mean).
        # Pages are a *logical token capacity* and therefore shard-
        # invariant: under a mesh every chip holds all num_pages pages but
        # only its kv_heads slice of each, so the per-shard BYTES divide by
        # the kv shard degree while the page count does not.
        pool_pages = 1 + paged_pool_pages(
            args.max_batch, args.prompt_len + api.prefix_len(cfg) + args.max_new,
            args.page_size)
    if paged and mesh is not None and model_parallel > 1 \
            and kv_parallel != model_parallel:
        # divisibility fallback: the pools' kv_heads dim cannot split this
        # model axis, so every chip stores (and streams) the FULL pool —
        # the per-shard divisor silently becomes 1 and a byte budget sized
        # for pool_bytes/model_parallel per chip would be exceeded.
        warnings.warn(
            f"{cfg.name}: paged pools do not shard across the "
            f"{model_parallel}-way model axis (n_kv_heads={cfg.n_kv_heads} "
            f"-> kv shard degree {kv_parallel}); per-shard pool bytes equal "
            f"the global pool — budget --pool-pages accordingly",
            stacklevel=1)
    open_loop = bool(args.trace) or args.arrival_rate > 0
    # the engine would warn-and-serve-fp itself; pre-clearing keeps the
    # sizer's logged budget consistent with the cache actually allocated
    args.kv_dtype = "int8" if kv_dtype else "fp"
    args.pool_pages = pool_pages
    engine = ServingEngine(cfg, params, plan=plan, config=config_from_args(
        args, mesh=mesh, rules=rules,
        # open-loop timing is simulated: one tick = one time unit of the
        # arrival schedule, so deadlines/TTFT/latency are seed-reproducible
        clock=TickClock() if open_loop else None,
        expected_context=ctx if paged else None,
        draft_cfg=draft_cfg, draft_params=draft_params))
    if engine.prefill_chunk is not None:
        print(f"[serve] continuous batching: {engine.prefill_chunk}-token "
              f"prefill chunks, {engine.prefill_budget} tok/tick budget")
    if engine.paged:
        print(f"[serve] paged KV cache: {engine.num_pages} pages x "
              f"{engine.page_size} tok (pool "
              f"{engine.num_pages * engine.page_size} tok vs contiguous "
              f"reservation {engine.max_batch * args.max_len} tok"
              + (f"; {engine.kv_parallel}-way kv shard -> 1/"
                 f"{engine.kv_parallel} of each page's bytes per chip"
                 if engine.kv_parallel > 1 else "")
              + f"), prefix sharing {'on' if args.share_prefix else 'off'}")
    if plan is not None:
        # one coherent traffic budget, in the bytes/token units the sizer
        # charges at this engine's actual batch
        # a tuned plan gets the per-leaf provenance block: the kind +
        # q_prune assignment the search picked, inspectable without
        # re-running it
        print(f"[serve] {plan.summary(kv_bytes_per_token=kv_tok, context_len=args.max_len, batch=engine.max_batch, per_leaf=tuned is not None)}")
        n_corr = plan.sizer(n_params=api.n_params_exact(cfg),
                            kv_bytes_per_token=kv_tok,
                            context_len=args.max_len,
                            model_parallel=model_parallel,
                            kv_parallel=kv_parallel).n_opt
        print(f"[serve] plan-corrected n_opt={_fmt_nopt(n_corr)}")
    rng = np.random.default_rng(args.seed)

    def _extras():
        extras = {}
        if "patches" in api.extra_keys:
            extras["patches"] = rng.normal(size=(cfg.n_patches, cfg.d_model)).astype(np.float32)
        if "frames" in api.extra_keys:
            extras["frames"] = rng.normal(size=(cfg.n_frames, cfg.d_model)).astype(np.float32)
        return extras or None

    if open_loop:
        if args.trace:
            arrivals = load_trace(args.trace)
            print(f"[serve] replaying {len(arrivals)} arrivals from "
                  f"{args.trace}")
        else:
            cap = args.max_len - api.prefix_len(cfg) - spec_k
            mix = _open_loop_mixture(args.prompt_len, args.max_new, cap)
            arrivals = poisson_trace(args.arrival_rate, args.requests, mix,
                                     seed=args.seed)
            print(f"[serve] poisson arrivals: {args.arrival_rate}/tick, "
                  f"n={len(arrivals)}, seed={args.seed}")
        reqs = make_requests(arrivals, cfg.vocab, seed=args.seed)
        for r in reqs:
            r.extras = _extras()
        t0 = time.time()
        report = run_open_loop(engine, arrivals, reqs, seed=args.seed)
        dt = time.time() - t0
        stats = engine.stats
        s = report.summary()
        print(f"[serve] open-loop: {s['completed']}/{s['n_requests']} "
              f"completed in {s['ticks']} ticks ({dt:.2f}s wall); "
              f"p50/p99 TTFT {s['p50_ttft_s']:.1f}/{s['p99_ttft_s']:.1f} "
              f"ticks, p50/p99 latency {s['p50_latency_s']:.1f}/"
              f"{s['p99_latency_s']:.1f} ticks, "
              f"{s['tokens_per_s']:.2f} committed tok/tick "
              f"(sizer n_opt {_fmt_nopt(sizer.n_opt)}), "
              f"mean batch {s['mean_batch']:.2f}, "
              f"leaked pages {s['leaked_pages']}")
    else:
        for uid in range(args.requests):
            engine.submit(Request(
                uid=uid,
                prompt=rng.integers(0, cfg.vocab, size=args.prompt_len).astype(np.int32),
                max_new_tokens=args.max_new,
                extras=_extras(),
            ))
        t0 = time.time()
        stats = engine.run_until_done()
        dt = time.time() - t0
    if not open_loop:
        print(f"[serve] completed {stats.completed} requests in {dt:.2f}s; "
              f"decode steps {stats.decode_steps}, tokens {stats.decode_tokens}, "
              f"mean batch {stats.mean_batch:.2f} "
              f"({stats.decode_tokens/max(dt,1e-9):.1f} tok/s on this host)")
    if engine.paged:
        print(f"[serve] paged: mean admitted context {stats.mean_context:.1f} "
              f"tok (sizer charged ctx {ctx}), "
              f"{stats.pages_shared} prefix pages shared, "
              f"{stats.cow_copies} copy-on-write copies")
    if engine.spec_k:
        print(f"[serve] speculative: {stats.verified_positions} verified "
              f"positions -> {stats.decode_tokens} committed tokens "
              f"({stats.decode_tokens / max(1, stats.verified_positions):.2f} "
              f"committed/verified), draft accept rate "
              f"{stats.accept_rate:.2f}, "
              f"{stats.mean_batch:.2f} committed tokens/tick")
    # failure-model outcomes: anything nonzero means the engine served
    # through faults or pressure rather than at steady state
    if (stats.failed or stats.evicted or stats.timed_out or stats.retried
            or stats.fallback_ticks or engine.degraded):
        print(f"[serve] failure model: {stats.failed} failed, "
              f"{stats.timed_out} timed out, {stats.evicted} evictions, "
              f"{stats.retried} retries, {stats.fallback_ticks} degraded "
              f"ticks" + (f"; degraded: {engine.degraded}"
                          if engine.degraded else ""))
    return engine, stats


if __name__ == "__main__":
    main()
