"""The system under test, built the way ``launch/serve.py`` builds it.

``get_api`` -> weights -> ``api.compress`` -> ``config_from_args`` ->
``ServingEngine``.  The weights are the benchmark's (``bench/weights.py``);
everything after them is the program's own path.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time

from bench import spec, weights


def program():
    """Import the program from the checkout's ``src``."""
    src = str(spec.ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.configs as C
    from repro.core.batching import mean_decode_context
    from repro.core.weight_plan import PlanConfig
    from repro.models.api import get_api
    from repro.serving.config import config_from_args
    from repro.serving.engine import Request, RequestState, ServingEngine

    return argparse.Namespace(
        C=C, PlanConfig=PlanConfig, get_api=get_api,
        config_from_args=config_from_args, ServingEngine=ServingEngine,
        Request=Request, RequestState=RequestState,
        mean_decode_context=mean_decode_context)


def sizes(conf: dict, smoke: bool) -> dict:
    """The configuration as run: the program's ``arch`` and the
    ``overrides`` of its ModelConfig, the weight ``plan``, the ``model`` the
    reference computes and the engine's ``serving`` knobs; the ``smoke``
    section's values with ``smoke`` (the CPU rehearsal)."""
    out = {"arch": conf["program"]["arch"],
           "overrides": dict(conf["program"].get("overrides", {})),
           "plan": dict(conf["plan"]), "model": dict(conf["model"]),
           "serving": dict(conf["serving"])}
    if smoke:
        sm = conf["smoke"]
        out["overrides"] = dict(sm.get("overrides", {}))
        out["plan"].update(sm.get("plan", {}))
        out["serving"].update(sm.get("serving", {}))
        out["model"] = dict(sm["model"])
    return out


def agree(cfg, model: dict) -> list:
    """The program's model must be the one the configuration file states.
    Each key of the ``model`` section is held to the program's config: the
    keys below by the field they name, any other by the ``ModelConfig``
    field of the same name, else by the ``MoEConfig`` one.  A key the
    section leaves out is not checked.  Returns the stated keys the program
    has no field for; the reference still reads them, so ``correct``
    catches a mismatch there."""
    moe = cfg.moe
    got = {"layers": cfg.n_layers, "d_model": cfg.d_model, "heads": cfg.n_heads,
           "kv_heads": cfg.n_kv_heads, "head_dim": cfg.hd, "d_ff": cfg.d_ff,
           "vocab": cfg.vocab, "tied": bool(cfg.tie_embeddings),
           "rope_theta": float(cfg.rope_base),
           "experts": moe.n_experts if moe else 0,
           "top_k": moe.top_k if moe else 0,
           "expert_d_ff": moe.expert_d_ff if moe else 0}
    fields = [(c, {f.name for f in dataclasses.fields(c)})
              for c in (cfg, moe) if c is not None]
    bad, unchecked = {}, []
    for key, want in model.items():
        if key in got:
            value = got[key]
        else:
            owner = next((c for c, names in fields if key in names), None)
            if owner is None:
                unchecked.append(key)
                continue
            value = getattr(owner, key)
            value = list(value) if isinstance(value, tuple) else value
        if value != want:
            bad[key] = (value, want)
    if bad:
        raise ValueError(f"program model differs from the configuration: {bad}")
    return unchecked


def model_config(P, conf: dict, smoke: bool):
    s = sizes(conf, smoke)
    cfg = P.C.get_config(s["arch"], smoke=smoke)
    return dataclasses.replace(cfg, **s["overrides"]) if s["overrides"] else cfg


def plan_config(P, plan: dict):
    return P.PlanConfig(
        default=plan["default"], q_prune=plan["q_prune"],
        bk=plan["block"], bn=plan["block"], score=plan["score"],
        min_size=plan["min_size"], min_contract=plan["min_contract"])


@dataclasses.dataclass
class Built:
    P: argparse.Namespace
    cfg: object
    api: object
    shapes: object  # the dense parameter structure (ShapeDtypeStructs)
    plan: object  # the program's WeightPlan
    serving: dict
    seed: int
    times: dict
    unchecked: list  # model keys the program has no field for (``agree``)

    def new_engine(self, mix: dict):
        """A ``ServingEngine`` over the packed plan, with the traffic's
        knobs, through the program's own argparse adapter."""
        P, serving = self.P, self.serving
        ns = argparse.Namespace(
            max_len=mix["max_len"], max_batch=mix["max_batch"],
            seed=int(self.seed) % (1 << 31), kv_dtype=serving["kv_dtype"],
            page_size=serving["page_size"], pool_pages=0, share_prefix=False,
            prefill_chunk=serving["prefill_chunk"],
            prefill_budget=mix["prefill_budget"], evict_policy="fifo",
            request_timeout=0, ttft_deadline=0, max_retries=1)
        ctx = P.mean_decode_context(mix["prompt"]["median"],
                                    mix["output"]["median"])
        t = time.monotonic()
        engine = P.ServingEngine(
            self.cfg, self.plan.params, plan=self.plan,
            config=P.config_from_args(ns, expected_context=ctx))
        self.times["engine_s"] = time.monotonic() - t
        return engine

    def release(self):
        self.plan = None


def build(conf: dict, seed: int, smoke: bool) -> Built:
    """Weights from ``seed``, packed by the program's ``api.compress``."""
    import jax

    P = program()
    sz = sizes(conf, smoke)
    cfg = model_config(P, conf, smoke)
    unchecked = agree(cfg, sz["model"])
    api = P.get_api(cfg)
    times = {}
    t = time.monotonic()
    shapes = weights.param_shapes(api, cfg)
    dense = weights.make_params(shapes, seed, sz["plan"]["block"])
    jax.block_until_ready(dense)
    times["weights_s"] = time.monotonic() - t
    t = time.monotonic()
    plan = api.compress(cfg, dense, plan_config(P, sz["plan"]))
    del dense
    jax.block_until_ready(plan.params)
    times["pack_s"] = time.monotonic() - t
    return Built(P, cfg, api, shapes, plan, sz["serving"], seed, times,
                 unchecked)


def lowered_steps(engine) -> str:
    """StableHLO text of the engine's step program at the two shapes the
    window runs: the batched decode step and a batch-1 prefill chunk."""
    import jax
    import jax.numpy as jnp

    B, C = engine.max_batch, engine.prefill_chunk
    cache1 = jax.eval_shape(lambda: engine.api.init_cache(
        engine.cfg, 1, engine.max_len, engine.dtype, kv_dtype=engine.kv_dtype,
        **engine._spec_cache_kw()))
    i32 = jnp.int32
    decode = engine._decode.lower(
        engine.params, engine.cache, jax.ShapeDtypeStruct((B, 1), i32),
        jax.ShapeDtypeStruct((B,), i32), jax.ShapeDtypeStruct((B,), bool))
    chunk = engine._decode.lower(
        engine.params, cache1, jax.ShapeDtypeStruct((1, C), i32),
        jax.ShapeDtypeStruct((1,), i32), jax.ShapeDtypeStruct((1,), bool))
    return decode.as_text() + "\n" + chunk.as_text()

