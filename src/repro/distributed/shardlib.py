"""Logical-axis sharding: models annotate tensors with *logical* axis names;
a rule table (set by the launcher per mesh) maps them to physical mesh axes.

This is the GSPMD discipline that lets one model definition run on a laptop
(no mesh: every annotation is a no-op), a single pod (data, model), and a
multi-pod mesh (pod, data, model) without edits — the core requirement for
1000+-node runnability.

Divisibility-aware: a rule is applied to a dimension only if the dimension is
divisible by the product of the mapped mesh axis sizes; otherwise that
dimension is left unsharded (e.g. whisper-tiny's 6 heads on a 16-way model
axis).  This keeps every (arch x mesh) cell lowerable with zero per-arch
special cases, at a documented efficiency cost reported by the roofline.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

# logical axis name -> mesh axis name (or tuple of mesh axes)
DEFAULT_RULES = {
    "batch": ("pod", "data"),  # data parallel
    "seq": None,  # sequence: unsharded by default (overridden for long ctx)
    "seq_sp": None,  # residual-stream seq dim: mapped to `model` under
    #                  Megatron-style sequence parallelism (launcher opt-in)
    "cache_seq": None,  # decode KV cache length (sharded for long_500k)
    "d": None,  # d_model: replicated on activations
    "heads": "model",  # attention heads — tensor parallel
    "kv_heads": "model",
    "qkv": "model",  # fused qkv feature dim
    "ff": "model",  # FFN hidden
    "vocab": "model",  # embedding/LM-head vocab shard
    "experts": "model",  # MoE expert parallelism
    "expert_ff": None,  # intra-expert TP (used when E % model != 0)
    "zero": ("pod", "data"),  # optimizer-state sharding axis (ZeRO)
}


def _get():
    if not hasattr(_state, "stack"):
        _state.stack = []
    return _state.stack


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Optional[dict] = None):
    """Activate a mesh + logical rules for model-internal annotations."""
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    _get().append((mesh, merged))
    try:
        with mesh:
            yield
    finally:
        _get().pop()


def current_mesh() -> Optional[Mesh]:
    s = _get()
    return s[-1][0] if s else None


def current_rules() -> dict:
    s = _get()
    return s[-1][1] if s else dict(DEFAULT_RULES)


def _axes_size(mesh: Mesh, axes: Union[str, Sequence[str], None]) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape.get(a, 1)
    return size


def _resolve(
    mesh: Mesh,
    rules: dict,
    logical: Sequence[Optional[str]],
    shape,
    unconstrained_ok: bool = False,
) -> P:
    """Logical axes -> PartitionSpec, dropping non-divisible mappings.

    A dropped mapping becomes ``P.UNCONSTRAINED`` for activation constraints
    (let GSPMD propagate something sensible — pinning to replicated would
    force gathers, e.g. gemma3's 8 heads on a 16-way model axis) and ``None``
    (replicated) for jit in/out_shardings, which must be concrete.
    """
    spec = []
    used: set = set()
    dropped = P.UNCONSTRAINED if unconstrained_ok else None
    for dim, name in zip(shape, logical):
        mapped = rules.get(name) if name else None
        if mapped is None:
            spec.append(None)
            continue
        axes = (mapped,) if isinstance(mapped, str) else tuple(mapped)
        axes = tuple(a for a in axes if a in mesh.shape and a not in used)
        size = _axes_size(mesh, axes)
        if size <= 1 or dim % size != 0:
            spec.append(dropped)
            continue
        used.update(axes)
        spec.append(axes[0] if len(axes) == 1 else axes)
    return P(*spec)


# ---------------------------------------------------------------------------
# axis-rules registry: ONE table from leaf kind to logical axes
# ---------------------------------------------------------------------------
#
# Sharding used to be scattered per call site: model `*_axes` helpers for
# dense params, an ad-hoc `_quantized_axes` rewrite in the dry-run for
# {"q","s"} dicts, nothing at all for `PackedLinear` leaves or the serving
# caches.  The registry replaces that with a serving-wide contract:
#
#   * `register_axes(kind, axes)` — *named* leaf kinds (KV caches, scale
#     leaves, page pools, the page table) register their canonical logical
#     axes once, where the leaf layout is defined; every consumer (engine
#     cache placement, launcher in_shardings, docs) reads the same entry.
#   * `register_node_axes(name, predicate, expander)` — *structured* leaf
#     kinds (PackedLinear, int8 {"q","s"} dicts) register an expander that
#     maps the dense weight's logical axes to a matching pytree of axes for
#     the node's children (blocks shard on the output-feature axis, the
#     walk stays replicated, scales drop the contraction axis, ...).
#   * `tree_shardings(tree, axes_tree, ...)` — NamedShardings for any
#     params/cache pytree, dense or compressed, via the expanders; this is
#     what lets a compressed, paged, int8-KV serving plan lower under
#     `use_mesh` with zero special cases.
#
# Divisibility stays the registry's problem, not the caller's: `_resolve`
# drops any mapping the dimension cannot honor (whisper-tiny's 6 heads on a
# 16-way model axis fall back to replicated), so every (leaf kind x mesh)
# cell is lowerable.

AXIS_REGISTRY: dict = {}

_NODE_RULES: list = []  # (name, predicate, expander) — first match wins


def register_axes(kind: str, axes: Sequence[Optional[str]]) -> tuple:
    """Register canonical logical axes for a *named* leaf kind (e.g.
    ``attn.kv_pages``).  Returns the stored tuple so definition sites can
    register and consume in one expression."""
    AXIS_REGISTRY[kind] = tuple(axes)
    return AXIS_REGISTRY[kind]


def axes_for(kind: str) -> tuple:
    """Logical axes registered for a named leaf kind."""
    return AXIS_REGISTRY[kind]


def register_node_axes(name: str, predicate, expander):
    """Register a *structured* leaf kind.

    ``predicate(node) -> bool`` recognizes the node (also used as the
    ``is_leaf`` cut when walking pytrees); ``expander(node, dense_axes) ->
    pytree`` returns logical-axis tuples matching the node's own pytree
    structure.  ``dense_axes`` is the logical axes of the dense leaf the
    node replaced (may be None: expanders must fall back to replicated).
    """
    _NODE_RULES.append((name, predicate, expander))


def is_registered_node(x) -> bool:
    return any(pred(x) for _, pred, _ in _NODE_RULES)


def expand_axes(node, axes):
    """Logical axes for one (possibly structured) leaf: dense leaves keep
    ``axes`` as-is; registered node kinds expand to per-child axes."""
    for _, pred, exp in _NODE_RULES:
        if pred(node):
            return exp(node, axes)
    return axes


def registry_table() -> dict:
    """The full registry, for docs/tests: named kinds -> axes plus the
    structured-kind names."""
    return {**{k: AXIS_REGISTRY[k] for k in sorted(AXIS_REGISTRY)},
            "node_kinds": tuple(name for name, _, _ in _NODE_RULES)}


# ---------------------------------------------------------------------------
# cache-kind registry: ONE table of serving-state leaf kinds
# ---------------------------------------------------------------------------
#
# The axis registry above answers "how does this leaf shard"; serving also
# needs "what IS this leaf" — is it positionally addressed (a KV cache with
# a sequence axis the engine can page, window, or speculative-write), or
# recurrent state (a fixed-size summary the step rewrites in place)?  That
# classification used to live implicitly in per-family code paths
# (`supports_paged_kv`, transformer's kind dispatch, the engine's spec
# gates).  `register_cache_kind` layers it on `register_axes`: every model
# family registers its serving-state leaves here — attention KV and paged
# pools, enc-dec/VLM cross-attention frames, rgLRU/xLSTM recurrent state —
# so the engine, the sharding dry-run, and the docs all read one table.

CACHE_KIND_REGISTRY: dict = {}


@dataclasses.dataclass(frozen=True)
class CacheKind:
    """One serving-state leaf kind.

    ``axes`` is either a logical-axes tuple (single-leaf kinds) or a dict
    of sub-leaf name -> tuple (multi-leaf kinds like recurrent state);
    every tuple is also entered in the axis registry (as ``name`` or
    ``name.sub``) so sharding keeps working through ``axes_for``.
    ``positional`` marks sequence-addressed storage — the property that
    gates paging, speculative decode, and chunked prefill.  ``paged`` marks
    the pool-resident layout variants.  ``family`` groups kinds by the
    module that owns the layout.
    """

    name: str
    axes: Any
    positional: bool
    paged: bool = False
    family: str = "attn"


def register_cache_kind(name: str, axes, *, positional: bool,
                        paged: bool = False, family: str = "attn"):
    """Register a serving-state leaf kind; returns the stored axes (tuple
    kinds) so definition sites can register and consume in one expression,
    matching ``register_axes``."""
    if isinstance(axes, dict):
        stored = {k: register_axes(f"{name}.{k}", v) for k, v in axes.items()}
    else:
        stored = register_axes(name, axes)
    CACHE_KIND_REGISTRY[name] = CacheKind(
        name=name, axes=stored, positional=positional, paged=paged,
        family=family)
    return stored


def cache_kind(name: str) -> CacheKind:
    return CACHE_KIND_REGISTRY[name]


def cache_kind_table() -> dict:
    """name -> CacheKind for every registered serving-state kind, in sorted
    order (docs/architecture.md renders this table)."""
    return {k: CACHE_KIND_REGISTRY[k] for k in sorted(CACHE_KIND_REGISTRY)}


def _leaf_spec(mesh, rules, leaf, ax) -> P:
    shape = getattr(leaf, "shape", None)
    if shape is None:
        return P()
    if ax is None:
        ax = (None,) * len(shape)
    ax = tuple(ax)
    if len(ax) != len(shape):
        raise ValueError(
            f"{len(ax)} logical axes {ax} for shape {tuple(shape)}")
    return _resolve(mesh, rules, ax, shape)


def tree_shardings(tree, axes_tree, *, mesh: Optional[Mesh] = None,
                   rules: Optional[dict] = None):
    """NamedShardings for a params/cache pytree (arrays or
    ShapeDtypeStructs), dense or compressed.

    ``axes_tree`` carries the *dense* logical axes (a tuple per dense leaf
    position, e.g. from ``api.param_axes``); registered structured nodes
    (PackedLinear, {"q","s"}) are expanded through the registry.  ``None``
    axes (or missing structure under a node) mean replicated.
    """
    mesh = mesh or current_mesh()
    rules = rules if rules is not None else current_rules()
    if mesh is None:
        raise ValueError("tree_shardings needs a mesh (argument or use_mesh)")

    def one(node, ax):
        expanded = expand_axes(node, ax)
        if expanded is None:  # replicated subtree (no axes recorded)
            return jax.tree.map(
                lambda leaf: NamedSharding(mesh, _leaf_spec(mesh, rules, leaf, None)),
                node,
            )
        return jax.tree.map(
            lambda leaf, a: NamedSharding(mesh, _leaf_spec(mesh, rules, leaf, a)),
            node, expanded,
        )

    return jax.tree.map(one, tree, axes_tree, is_leaf=is_registered_node)


def shard_degree(mesh: Mesh, rules: dict, logical: Sequence[Optional[str]],
                 shape, *, dim: Optional[int] = None) -> int:
    """Achieved shard degree of a leaf under (mesh, rules): the product of
    mesh-axis sizes ``_resolve`` actually applied (non-divisible mappings
    have already been dropped).  ``dim`` restricts to one dimension — e.g.
    the kv_heads axis of a cache leaf, which is what the multi-chip perf
    model divides the kv stream by."""
    spec = _resolve(mesh, rules, logical, shape)
    dims = range(len(spec)) if dim is None else (dim,)
    deg = 1
    for d in dims:
        entry = spec[d] if d < len(spec) else None
        if entry is None or entry is P.UNCONSTRAINED:
            continue
        deg *= _axes_size(mesh, entry)
    return deg


def parallelism_degrees(mesh: Optional[Mesh], rules: dict,
                        n_kv_heads: int = 0) -> tuple:
    """(data, model, kv) shard degrees for serving accounting — THE one
    derivation the engine and the serve driver share.

    ``data``: nominal degree of the batch axis (the rules' ``batch``
    mapping over this mesh) — a per-model-group n_opt must be multiplied by
    it to get the global batch.  ``model``: the model-axis size (the
    weight-stream divisor).  ``kv``: the degree the kv_heads dimension
    *actually* achieves under divisibility (1 when it cannot split — the
    cache replicates and every chip pays the full kv stream).
    """
    if mesh is None:
        return 1, 1, 1
    data = _axes_size(mesh, rules.get("batch"))
    model = int(mesh.shape.get("model", 1))
    kv = shard_degree(mesh, rules, ("kv_heads",), (n_kv_heads,)) \
        if n_kv_heads else 1
    return data, model, kv


def shard(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Constrain activation sharding by logical axis names (no-op without
    an active mesh)."""
    s = _get()
    if not s:
        return x
    mesh, rules = s[-1]
    if len(logical) != x.ndim:
        raise ValueError(f"{len(logical)} axes for rank-{x.ndim} tensor")
    spec = _resolve(mesh, rules, logical, x.shape, unconstrained_ok=True)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_pinned(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Like ``shard`` but dropped mappings pin to replicated instead of
    UNCONSTRAINED — used at cache boundaries where the layout must match the
    declared in/out_shardings exactly (a mismatch makes GSPMD reshard the
    whole buffer at the jit boundary)."""
    s = _get()
    if not s:
        return x
    mesh, rules = s[-1]
    if len(logical) != x.ndim:
        raise ValueError(f"{len(logical)} axes for rank-{x.ndim} tensor")
    spec = _resolve(mesh, rules, logical, x.shape, unconstrained_ok=False)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def mesh_axes(logical: Optional[str], size: int):
    """The PartitionSpec entry the active (mesh, rules) give a dimension of
    ``size`` named ``logical``: mesh axis name(s), or None where there is
    no mesh, no mapping, or the mapping does not divide ``size``.  Kernel
    wrappers resolve each logical axis once this way, so every operand
    that carries it shards identically."""
    s = _get()
    if not s or logical is None:
        return None
    mesh, rules = s[-1]
    return _resolve(mesh, rules, (logical,), (size,))[0]


def kernel_call(fn, args: Sequence[Any], in_specs: Sequence[Any], out_specs):
    """``fn(*args)``, run per shard under the active mesh.

    A Pallas kernel lowers to one opaque TPU custom call that the SPMD
    partitioner cannot split, so under a mesh each kernel call runs inside
    ``shard_map`` on its operands' local shards (``in_specs`` and
    ``out_specs`` are PartitionSpecs over the active mesh; operands held
    otherwise are resharded to match).  ``None`` args pass through
    unchanged.  Without a mesh this is a plain call."""
    s = _get()
    if not s:
        return fn(*args)
    present = [i for i, a in enumerate(args) if a is not None]

    def local(*xs):
        full = list(args)
        for i, x in zip(present, xs):
            full[i] = x
        return fn(*full)

    return jax.shard_map(
        local, mesh=s[-1][0], in_specs=tuple(in_specs[i] for i in present),
        out_specs=out_specs, check_vma=False,
    )(*(args[i] for i in present))


def spec_for(shape: Sequence[int], *logical: Optional[str], mesh: Optional[Mesh] = None,
             rules: Optional[dict] = None) -> P:
    """PartitionSpec for a parameter of `shape` with logical axes (used to
    build in_shardings for jit)."""
    mesh = mesh or current_mesh()
    rules = rules or current_rules()
    if mesh is None:
        return P()
    return _resolve(mesh, rules, logical, shape)


def named_sharding(mesh: Mesh, shape, *logical, rules=None) -> NamedSharding:
    return NamedSharding(mesh, spec_for(shape, *logical, mesh=mesh, rules=rules))
