"""Bring-up smoke test of the served path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded path on a 1x4 mesh

One chip: serves tinyllama-1.1b at its published widths (22 layers, random
weights from a seed) through ``repro.launch.serve.main`` with the paper's
two optimizations composed (int8 block-sparse weights at q_prune 0.5, int8
paged KV cache with prefix sharing), 16 closed-loop requests.  It then
checks that

* every request completed, none failed or timed out, and the engine did
  not degrade to a fallback path;
* the compiled decode step holds the Pallas kernels (``tpu_custom_call``
  for paged attention, the block-sparse matmul and the fused gate+up);
* prefill plus 8 greedy decode steps give the same tokens on the kernel
  path as on the reference datapath (gather attention, gather+einsum
  sparse matmuls at full f32 matmul precision), on the same chip, with
  the logits inside LOGIT_REL_BOUND.

Four chips: only the sharded path (``serve.py --mesh 1x4``) and what it is
compared with: the same model and plan unsharded on one of those chips.
Greedy streams must be identical, and the packed weights and page pools
must be split across the four devices rather than whole on one.

Exits non-zero, printing no result, without a TPU or without the
repository next to this script.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Wall times printed here are smoke timings, not benchmarks.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

ARCH = "tinyllama-1.1b"
PAGE_SIZE = 16
PROMPT_LEN, MAX_NEW, MAX_BATCH = 128, 32, 8
SERVE_ARGV = [
    "--arch", ARCH,
    "--compress", "quant_sparse", "--q-prune", "0.5", "--block", "128",
    "--kv-dtype", "int8", "--page-size", str(PAGE_SIZE), "--share-prefix",
    "--requests", "16", "--prompt-len", str(PROMPT_LEN),
    "--max-new", str(MAX_NEW), "--max-batch", str(MAX_BATCH),
    "--max-len", str(PROMPT_LEN + MAX_NEW), "--seed", "0",
]
CHECK_PROMPTS, CHECK_STEPS = 4, 8
MESH = "1x4"

# Both paths read the same int8 weight codes, scales and int8 KV pages, so
# quantization error is common to both and cancels.  What differs is where
# f32 partial sums are rounded to bf16 (unit roundoff 2**-8) and in what
# order they are summed.  Allowing one bf16 rounding per layer of the
# residual stream, accumulated linearly over the 22 layers, bounds the
# relative logit difference by 22 * 2**-8 = 0.086.
N_LAYERS = 22
LOGIT_REL_BOUND = N_LAYERS * 2.0 ** -8

# the kernels the served decode step must contain (Pallas kernel names)
STEP_KERNELS = {
    "paged attention": ("_paged_decode_kernel",),
    "block-sparse matmul": ("_bsmm_mc_kernel", "_bsmm_kernel"),
    "fused gate+up": ("_fused_kernel",),
}


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Seconds spent in XLA backend compilation (a persistent-cache hit
    counts only its lookup), and the number of cache hits."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def seeded_prompts(vocab: int, n: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=PROMPT_LEN).astype(np.int32)
            for _ in range(n)]


def greedy(engine, prompts, max_new: int):
    """Serve ``prompts`` greedily on ``engine``; returns the token streams
    and, per decode step, the logits of the rows that were decoding."""
    import numpy as np
    from repro.serving.engine import Request

    steps = []
    run = engine._run_decode

    def recorded(tokens, pos):
        rows = engine._decoding_slots()
        logits, ok = run(tokens, pos)
        steps.append((rows, logits[rows]))
        return logits, ok

    engine._run_decode = recorded
    try:
        reqs = [Request(uid=i, prompt=p, max_new_tokens=max_new)
                for i, p in enumerate(prompts)]
        for r in reqs:
            engine.submit(r)
        stats = engine.run_until_done()
    finally:
        del engine._run_decode
    if stats.failed or stats.timed_out or any(not r.done for r in reqs):
        fail(f"greedy run did not complete: {stats}")
    return [list(r.output) for r in reqs], steps


def step_kernels(engine, jnp) -> set:
    """Names of the Pallas kernels in the engine's decode step, lowered for
    its real arguments."""
    import re

    B = engine.max_batch
    lowered = engine._decode.lower(
        engine.params, engine.cache, jnp.zeros((B, 1), jnp.int32),
        jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool))
    text = lowered.as_text()
    if "tpu_custom_call" not in text:
        fail("the decode step holds no tpu_custom_call: no Pallas kernel")
    return set(re.findall(r"kernel_name\W+(\w+)", text))


def kernels_off(tree):
    """``tree`` with every packed leaf on the gather reference datapath."""
    import jax
    from repro.core.weight_plan import PackedLinear

    return jax.tree.map(
        lambda n: dataclasses.replace(n, use_kernel=False)
        if isinstance(n, PackedLinear) else n,
        tree, is_leaf=lambda n: isinstance(n, PackedLinear))


def engine_like(engine, params, **over):
    """A fresh engine with ``engine``'s model, plan and cache geometry."""
    from repro.serving.config import EngineConfig
    from repro.serving.engine import ServingEngine

    config = EngineConfig.of(
        max_len=engine.max_len, max_batch=engine.max_batch,
        kv_dtype="int8", page_size=engine.page_size,
        num_pages=engine.num_pages, share_prefix=True, **over)
    return ServingEngine(engine.cfg, params, plan=engine.plan, config=config)


def serve(serve_main, argv):
    t0 = time.time()
    engine, stats = serve_main(argv)
    wall = time.time() - t0
    n = 16
    if stats.completed != n or stats.failed or stats.timed_out:
        fail(f"served {stats.completed}/{n} requests ({stats.failed} failed, "
             f"{stats.timed_out} timed out)")
    if engine.degraded:
        fail(f"engine degraded: {engine.degraded}")
    say(f"served {stats.completed}/{n} requests, 0 failed, 0 timed out, "
        f"engine.degraded == {{}}; {stats.decode_tokens} decode tokens")
    say(f"wall time of serve.main (packing, compilation and serving; a "
        f"smoke timing, not a benchmark): {wall:.2f} s")
    return engine


def one_chip(jax, jnp, serve_main, clock) -> None:
    import numpy as np
    from repro.models import layers

    engine = serve(serve_main, SERVE_ARGV)
    say(f"compile seconds so far: {clock.seconds:.2f} "
        f"({clock.cache_hits} persistent-cache hits)")

    names = step_kernels(engine, jnp)
    for what, options in STEP_KERNELS.items():
        if not names & set(options):
            fail(f"no {what} kernel in the decode step (kernels: "
                 f"{sorted(names)})")
    say(f"decode step kernels (tpu_custom_call): {sorted(names)}")

    prompts = seeded_prompts(engine.cfg.vocab, CHECK_PROMPTS, seed=1)
    toks_k, steps_k = greedy(engine, prompts, CHECK_STEPS + 1)
    prev = layers.force_attention_kernel(False)
    try:
        with jax.default_matmul_precision("highest"):
            ref = engine_like(engine, kernels_off(engine.params))
            toks_r, steps_r = greedy(ref, prompts, CHECK_STEPS + 1)
    finally:
        layers.force_attention_kernel(prev)
    if len(steps_k) != CHECK_STEPS or len(steps_r) != CHECK_STEPS:
        fail(f"expected {CHECK_STEPS} decode steps, ran {len(steps_k)} "
             f"(kernel) and {len(steps_r)} (reference)")
    err = 0.0
    for (rows_k, lk), (rows_r, lr) in zip(steps_k, steps_r):
        if rows_k != rows_r:
            fail(f"decoding rows differ: {rows_k} vs {rows_r}")
        scale = np.abs(lr).max(axis=-1, keepdims=True)
        err = max(err, float((np.abs(lk - lr) / scale).max()))
    if toks_k != toks_r:
        fail(f"greedy tokens differ from the reference path: {toks_k} vs "
             f"{toks_r} (max relative logit error {err:.3e})")
    say(f"reference check: {CHECK_PROMPTS} prompts x (prefill + "
        f"{CHECK_STEPS} greedy decode steps): tokens equal to the reference "
        f"datapath; max relative logit error {err:.3e} (bound "
        f"{LOGIT_REL_BOUND:.3e})")
    if not err <= LOGIT_REL_BOUND:
        fail(f"logit error {err:.3e} over the bound {LOGIT_REL_BOUND:.3e}")
    stats = jax.devices()[0].memory_stats() or {}
    say(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")


def bytes_by_device(tree) -> collections.Counter:
    import jax

    out = collections.Counter()
    for a in jax.tree.leaves(tree):
        for s in a.addressable_shards:
            out[s.device.id] += s.data.nbytes
    return out


def four_chips(jax, jnp, serve_main, clock) -> None:
    engine4 = serve(serve_main, SERVE_ARGV + ["--mesh", MESH])
    n_dev = engine4.mesh.size
    say(f"compile seconds so far: {clock.seconds:.2f}")
    whole = {k: sum(a.nbytes for a in jax.tree.leaves(t))
             for k, t in (("params", engine4.params), ("cache", engine4.cache))}
    for k, t in (("params", engine4.params), ("cache", engine4.cache)):
        per = bytes_by_device(t)
        say(f"{k}: {whole[k]} bytes whole; per device "
            + ", ".join(f"{d}: {b}" for d, b in sorted(per.items())))
        if len(per) != n_dev or max(per.values()) >= 0.75 * whole[k]:
            fail(f"{k} are not split across the {n_dev} devices")
    for d in jax.devices():
        say(f"device {d.id} bytes_in_use "
            f"{(d.memory_stats() or {}).get('bytes_in_use', 'not reported')}")

    dev0 = jax.devices()[0]
    engine1 = engine_like(engine4, jax.device_put(engine4.params, dev0))
    prompts = seeded_prompts(engine4.cfg.vocab, MAX_BATCH, seed=1)
    toks4, _ = greedy(engine4, prompts, MAX_NEW)
    toks1, _ = greedy(engine1, prompts, MAX_NEW)
    if toks4 != toks1:
        diff = [i for i, (a, b) in enumerate(zip(toks4, toks1)) if a != b]
        fail(f"greedy streams differ between the {MESH} mesh and one chip "
             f"(requests {diff})")
    say(f"parity: {len(prompts)} prompts x {MAX_NEW} greedy tokens identical "
        f"on the {MESH} mesh and on one chip")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served path on one chip (default); 4: the "
                         "sharded path on a 1x4 mesh against one chip")
    args = ap.parse_args(argv)

    if not (SRC / "repro" / "launch" / "serve.py").is_file():
        fail(f"the repro package is not next to this script ({SRC})")
    sys.path.insert(0, str(SRC))
    import jax
    import jax.numpy as jnp

    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX finds no TPU: {e}")
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"JAX finds no TPU (its first device is {dev.platform}); this "
             f"smoke runs only on the chip")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} TPU devices, JAX "
             f"finds {len(devices)}")

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import main as serve_main

    say(f"device_kind {dev.device_kind}, {len(devices)} device(s); compile "
        f"cache {enable_compile_cache()}")
    clock = CompileClock(jax)
    (one_chip if args.chips == 1 else four_chips)(jax, jnp, serve_main, clock)
    say(f"compile seconds: {clock.seconds:.2f} ({clock.cache_hits} "
        f"persistent-cache hits)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
