"""Production meshes and sharding-rule selection.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): (16, 16) -> (data, model) single pod of 256 v5e chips;
(2, 16, 16) -> (pod, data, model) for the 512-chip two-pod dry-run.  DP runs
over pod+data, TP/EP over model.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.sharding import AxisType

from repro.distributed import shardlib as sl


def _mesh(shape, axes):
    """A mesh whose axes the partitioner may shard over freely: the model
    code annotates logical axes (shardlib) and leaves propagation to GSPMD,
    which needs ``Auto`` axes (``jax.make_mesh`` defaults to ``Explicit``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """Whatever this process has — used by tests/examples on CPU."""
    n = len(jax.devices())
    return _mesh((1, n), ("data", "model"))


def make_serving_mesh(spec: str):
    """Mesh for the serving driver's ``--mesh`` flag.

    ``"host"`` -> (1, n_devices) as (data, model) — every visible device in
    one tensor-parallel group; ``"DxM"`` (e.g. ``"4x2"``) -> an explicit
    (data, model) shape over the first D*M devices; ``"none"`` -> None
    (unsharded single-device serving, the default).
    """
    if spec in (None, "", "none"):
        return None
    if spec == "host":
        return make_host_mesh()
    try:
        d, m = (int(x) for x in spec.lower().split("x"))
    except ValueError:
        raise ValueError(
            f"--mesh must be 'none', 'host', or 'DxM' (got {spec!r})") from None
    if d * m > len(jax.devices()):
        raise ValueError(
            f"--mesh {spec} needs {d * m} devices, have {len(jax.devices())} "
            f"(hint: XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return _mesh((d, m), ("data", "model"))


def rules_for(cfg, shape=None, *, zero_opt: bool = True,
              sequence_parallel: bool = False, mesh=None) -> dict:
    """Logical->physical rules for one (arch, shape) cell.

    Baseline rules come from shardlib.DEFAULT_RULES; per-cell adjustments:
      * long-context decode (global_batch below the data-axis size): shard
        the KV cache / sequence over `data` instead of the (unshardable)
        batch — sequence parallelism for the 500k cells;
      * MoE archs whose expert count is not divisible by the model axis:
        shard the expert FFN hidden dim instead (expert_ff -> model).

    ``mesh`` (optional) supplies the actual model-axis size for the MoE
    divisibility check; without it the production 16-way axis is assumed
    (the historical behavior for the dry-run meshes).
    """
    rules = dict(sl.DEFAULT_RULES)
    model_size = int(mesh.shape.get("model", 1)) if mesh is not None else 16
    if sequence_parallel and (shape is None or shape.kind in ("train", "prefill")):
        # Megatron-SP: the residual stream between TP blocks is sharded on
        # seq over `model`; GSPMD turns the per-block f32 all-reduces into
        # bf16 all-gather + reduce-scatter pairs.
        rules["seq_sp"] = "model"
    if shape is not None and shape.kind == "decode":
        # flash-decoding style: the KV cache shards along *sequence* over the
        # model axis (attention reduces over seq -> small stat collectives),
        # batch over data.  For batch < data-axis size (long_500k) the data
        # axis joins the sequence shard too.
        if shape.global_batch >= 16:
            rules["cache_seq"] = "model"
        else:
            rules["cache_seq"] = ("data", "model")
    if shape is not None and shape.kind == "prefill" and shape.global_batch < 16:
        rules["seq"] = "data"
        rules["cache_seq"] = "data"
    if (cfg is not None and cfg.moe is not None
            and cfg.moe.n_experts_padded % model_size != 0):
        # expert count doesn't divide the model axis and no padding was
        # configured: fall back to intra-expert TP
        rules["experts"] = None
        rules["expert_ff"] = "model"
    return rules


def opt_rules(rules: dict) -> dict:
    """ZeRO-1: optimizer state additionally sharded over the data axes by
    mapping the (otherwise replicated) d_model dimension onto them."""
    r = dict(rules)
    r["d"] = ("pod", "data")
    return r
