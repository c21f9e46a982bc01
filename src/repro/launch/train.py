"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Production posture: the same driver runs on a pod slice by passing
--mesh pod (the mesh/sharding path is identical to the dry-run); on CPU it
uses the host mesh.  Fault tolerance: every --ckpt-every steps an async
checkpoint is written; on start the latest complete checkpoint is restored;
the RestartSupervisor retries the step loop after transient failures.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

import repro.configs as C
from repro import checkpoint as ckpt
from repro.data import LMDataConfig, synthetic_lm_batch
from repro.distributed import shardlib as sl
from repro.distributed.fault import RestartSupervisor, StragglerDetector
from repro.launch import mesh as M
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import get_api
from repro.training import optimizer as O
from repro.training.trainer import make_train_step


def _shardings(mesh, rules, shapes_tree, axes_tree):
    def one(sds, ax):
        return NamedSharding(mesh, sl._resolve(mesh, rules, ax, sds.shape))

    return jax.tree.map(one, shapes_tree, axes_tree)


def run(args, cfg=None) -> dict:
    if cfg is None:
        cfg = C.get_config(args.arch, smoke=args.smoke)
    if args.remat:
        cfg = dataclasses.replace(cfg, remat=True)
    api = get_api(cfg)
    mesh = (
        M.make_production_mesh(multi_pod=args.mesh == "multipod")
        if args.mesh in ("pod", "multipod") else M.make_host_mesh()
    )
    rules = M.rules_for(cfg, None)
    # warmup scales with the run: a hardcoded 20-step warmup left short
    # smoke runs entirely inside the ramp (lr ~ 0, loss never moved)
    opt_cfg = O.OptimizerConfig(
        lr=args.lr,
        warmup_steps=min(20, max(1, args.steps // 4)),
        decay_steps=max(100, args.steps),
    )

    key = jax.random.key(args.seed)
    with sl.use_mesh(mesh, rules):
        params = api.init_params(cfg, key)
        opt_state = O.init_opt_state(opt_cfg, params, error_feedback=args.compression is not None)

    # placement
    p_axes = api.param_axes(cfg)
    o_axes = O.opt_state_axes(opt_cfg, p_axes, error_feedback=args.compression is not None)
    p_sh = _shardings(mesh, rules, jax.eval_shape(lambda: params), p_axes)
    o_sh = _shardings(mesh, M.opt_rules(rules), jax.eval_shape(lambda: opt_state), o_axes)
    params = jax.tree.map(jax.device_put, params, p_sh)
    opt_state = jax.tree.map(jax.device_put, opt_state, o_sh)

    data_cfg = LMDataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch, seed=args.seed,
        host_index=jax.process_index(), host_count=jax.process_count(),
    )

    step_fn = make_train_step(
        cfg, api.loss_fn, opt_cfg, accum_steps=args.accum, compression=args.compression
    )

    def wrapped(params, opt_state, batch):
        with sl.use_mesh(mesh, rules):
            return step_fn(params, opt_state, batch)

    jstep = jax.jit(wrapped, donate_argnums=(0, 1))

    saver = ckpt.AsyncCheckpointer(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    start_step = 0
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        (params, opt_state), meta = ckpt.restore(
            args.ckpt_dir, (params, opt_state), shardings=(p_sh, o_sh)
        )
        start_step = int(meta.get("step", 0))
        print(f"[train] restored step {start_step} from {args.ckpt_dir}")

    straggler = StragglerDetector(n_hosts=jax.process_count())
    losses = []

    def extras(step):
        out = {}
        rng = np.random.default_rng(step)
        hb = data_cfg.host_batch
        if "patches" in api.extra_keys:
            out["patches"] = rng.normal(size=(hb, cfg.n_patches, cfg.d_model)).astype(np.float32)
        if "frames" in api.extra_keys:
            out["frames"] = rng.normal(size=(hb, cfg.n_frames, cfg.d_model)).astype(np.float32)
        return out

    def loop(start: int) -> int:
        nonlocal params, opt_state
        for step in range(start, args.steps):
            t0 = time.time()
            batch = synthetic_lm_batch(data_cfg, step)
            batch.update(extras(step))
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt_state, metrics = jstep(params, opt_state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            straggler.record(jax.process_index(), time.time() - t0)
            if step % args.log_every == 0:
                print(
                    f"[train] step {step} loss {loss:.4f} "
                    f"lr {float(metrics['lr']):.2e} gnorm {float(metrics['grad_norm']):.2f} "
                    f"({time.time()-t0:.2f}s)",
                    flush=True,
                )
            if saver and step > 0 and step % args.ckpt_every == 0:
                saver.save(step, (params, opt_state), {"step": step, "arch": args.arch})
        return args.steps

    def restore_fn() -> int:
        nonlocal params, opt_state, start_step
        if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
            (params, opt_state), meta = ckpt.restore(
                args.ckpt_dir, (params, opt_state), shardings=(p_sh, o_sh)
            )
            return int(meta.get("step", 0))
        return start_step

    RestartSupervisor(max_restarts=2).run(loop, restore_fn)
    if saver:
        saver.save(args.steps, (params, opt_state), {"step": args.steps, "arch": args.arch})
        saver.wait()
    return {"final_loss": losses[-1] if losses else float("nan"), "losses": losses,
            "params": params}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=C.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--compression", default=None, choices=[None, "int8", "topk"])
    ap.add_argument("--mesh", default="host", choices=["host", "pod", "multipod"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()
    out = run(args)
    print(f"[train] done; final loss {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
