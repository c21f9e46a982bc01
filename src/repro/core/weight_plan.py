"""Compressed-weight execution plan: one representation decision per matmul.

This is where the paper's two optimizations finally compose end-to-end.
Batch processing (serving/engine.py) amortizes each streamed weight across
the live decode batch; pruning + weight encoding (Sections 4.1 / 4.3 / 5.6)
shrink the stream itself.  The plan walks a model's params pytree, assigns
every large matmul weight one of four representations, materializes the
compressed pytree, and provides the single dispatch (``apply_linear``)
every layer routes its matmuls through:

    ``dense``        — fp weights, streamed as-is (b_weight = 2, bf16).
    ``quant``        — int8 payload + per-output-channel fp32 scales
                       (Section 4.1 at int8; the legacy ``{"q","s"}`` dict
                       consumed by ``qdense`` since the quant-serving PR).
    ``block_sparse`` — surviving (bk, bn) blocks packed per block-column
                       with int32 row indices (the z_w analogue,
                       Section 5.6) — fp payload.
    ``quant_sparse`` — both: int8 block payload + scales.  t_mem shrinks by
                       (1 - q_prune) * b_weight/2 * q_overhead; at batch
                       n_opt, t_calc shrinks with (1 - q_prune) too — the
                       paper's combined-optimization claim.

The compressed pytree has the same treedef shape as the dense one (leaves
become ``PackedLinear`` pytree nodes or ``{"q","s"}`` dicts), so it scans,
vmaps, jits and donates exactly like dense params: the serving engine keeps
its single compiled decode step.

Stats from the plan (surviving weights, payload/metadata bytes) feed
``core.batching.BatchSizer`` so n_opt moves the way Section 5.6 predicts:
with a kernel that skips pruned blocks both t_calc and t_mem scale with
(1 - q_prune) and n_opt depends only on q_overhead; with masked-dense
compute (no skipping) n_opt scales with (1 - q_prune).

Invariants (counted on by the engine, the kernels, and the plan cache):

* **Dense-treedef preservation** — ``compress(params, ...)`` returns a
  pytree with exactly the dense treedef shape: leaves become
  ``PackedLinear`` nodes or ``{"q", "s"}`` dicts in place, nothing is
  added, removed, or reordered.  This is what lets the packed pytree scan
  / vmap / jit / donate through the unchanged model code, keeps the
  serving engine at ONE compiled decode step, and makes
  ``save_plan``/``load_plan`` a flat-leaf round trip.
* **Walk ordering** — ``PackedLinear.walk`` enumerates surviving blocks in
  ascending (block_column j, list_position s) order with payload index
  ``j * max_blocks + s`` into the rectangular ``BlockSparse`` block array
  (see core/sparse_format.py): ``cols`` is non-decreasing and every
  column's entries are contiguous.  The multi-column kernel's
  double-buffered DMA and the WALK_FIRST/WALK_LAST accumulator flags
  assume this order; ``pad_walk`` may append no-op entries but never
  reorders.
* **Stacked leaves** — scan-unit / MoE-expert stacking adds leading batch
  dims to a packed leaf; ``apply_linear`` vmaps them down to the 2-D case,
  so pack-time geometry (bk, bn, max_blocks, walk length) is uniform
  across the stack.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.pruning import BlockPruneConfig, block_mask, expand_block_mask
from repro.core.sparse_format import BlockSparse, build_walk, pad_walk
from repro.distributed import shardlib as sl

REPRS = ("dense", "quant", "block_sparse", "quant_sparse")

# Leaves consumed by qdense / embed / unembed call sites, by name.
QUANT_KEYS = ("w", "tok", "head")


# ---------------------------------------------------------------------------
# packed representation (a pytree node: scans/vmaps/jits like a plain array)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PackedLinear:
    """One block-sparse (optionally int8) matmul weight.

    Dense shape (K, N) in (bk, bn) blocks; per block-column j the surviving
    blocks are stored contiguously (zero-padded to ``mb`` = max blocks per
    column so the layout is static):

      blocks:     (n_cols * mb, bk, bn)  payload — fp32 or int8
      block_rows: (n_cols, mb) int32     row-block index per stored block
      counts:     (n_cols,) int32        true survivor count per column
      scales:     (N,) fp32 or None      per-output-channel dequant scales
                                         (present iff kind == quant_sparse)
      walk:       dict of (n_walk,) int32 arrays or None — the multi-column
                  kernel's pack-time block list (sparse_format.build_walk):
                  one entry per surviving block across all columns, so the
                  kernel grid no longer pays max_blocks steps for short
                  columns.  Stacked variants pad each slice's walk to a
                  shared length with no-op steps.

    Stacked variants (scan units and/or MoE experts) carry the matching
    leading dims on every child; ``apply_linear`` detects that and vmaps
    (recursively — a scan-stacked MoE leaf has two leading dims).
    ``lax.scan`` slices the children the same way it slices plain stacked
    arrays, so the unit-scan compile discipline is untouched.
    """

    blocks: Any
    block_rows: Any
    counts: Any
    scales: Optional[Any]
    walk: Optional[Any] = None
    # static metadata (pytree aux): per-matrix dense shape + block geometry
    kind: str = "block_sparse"
    shape: tuple = ()
    bk: int = 128
    bn: int = 128
    # None = the Pallas kernel on the TPU backend, the gather reference
    # elsewhere (resolved at trace time, like layers.paged_decode_attention)
    use_kernel: Optional[bool] = None
    # logical name of the dense weight's output-feature axis (e.g. "ff"):
    # under a mesh the kernel call shards every block-column child on it
    out_axis: Optional[str] = None

    @property
    def stacked(self) -> bool:
        return self.blocks.ndim > 3


jax.tree_util.register_dataclass(
    PackedLinear,
    data_fields=["blocks", "block_rows", "counts", "scales", "walk"],
    meta_fields=["kind", "shape", "bk", "bn", "use_kernel", "out_axis"],
)


# ---------------------------------------------------------------------------
# axis-rules registry entries (distributed/shardlib): how the compressed
# representations shard, registered where the layouts are defined
# ---------------------------------------------------------------------------


def _packed_leaf_axes(node: PackedLinear, axes):
    """Expand a dense weight's logical axes (..., in_ax, out_ax) to the
    PackedLinear children.

    The payload and its metadata are grouped *per block-column* (the output-
    feature tiling), so every child that carries a block-column dimension
    shards on the dense weight's output-feature axis — each chip streams
    only its slice of the compressed stream, EIE's distribution of a
    compressed network across PEs.  The ``walk`` is the kernel's global
    pack-time schedule (column boundaries, accumulator flags): it must stay
    replicated, like the contraction-axis geometry it encodes.  The
    contraction axis itself is never sharded: block rows index it, and a
    split there would break the offset-calculated gather.
    """
    lead_n = node.blocks.ndim - 3
    ax = tuple(axes) if axes is not None else ()
    out_ax = ax[-1] if len(ax) >= 2 else None
    lead = ax[:-2] if len(ax) == lead_n + 2 else (None,) * lead_n
    return dataclasses.replace(
        node,
        blocks=lead + (out_ax, None, None),
        block_rows=lead + (out_ax, None),
        counts=lead + (out_ax,),
        scales=None if node.scales is None else lead + (out_ax,),
        walk=None if node.walk is None else {k: lead + (None,) for k in node.walk},
    )


def _quant_leaf_axes(node: dict, axes):
    """{"q", "s"}: the int8 payload keeps the dense weight's axes; the
    per-output-channel scales drop the contraction axis."""
    if axes is None:
        return {"q": None, "s": None}
    ax = tuple(axes)
    return {"q": ax, "s": ax[:-2] + ax[-1:]}


sl.register_node_axes(
    "packed", lambda n: isinstance(n, PackedLinear), _packed_leaf_axes)
sl.register_node_axes(
    "quant", lambda n: isinstance(n, dict) and "q" in n, _quant_leaf_axes)


# ---------------------------------------------------------------------------
# plan configuration + assignment
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlanConfig:
    """How to compress a model for serving.

    default:   representation for every eligible matmul leaf.
    rules:     ((path_substring, repr[, q_prune]), ...) — first match
               overrides the default (e.g. (("embed", "quant"),
               ("w_down", "dense"))).  A 3-tuple additionally overrides the
               plan-wide ``q_prune`` for the matched leaf, which is how the
               offline autotuner (core/autotune) emits per-leaf sparsity:
               (("w_up", "quant_sparse", 0.75), ("wo", "quant_sparse", 0.25)).
    q_prune:   block-pruned fraction for the sparse representations (the
               default when a matching rule carries no override).
    bk/bn:     block geometry (MXU-aligned 128x128 in production; smaller in
               tests so tiny configs have enough blocks to prune).
    min_size / min_contract: eligibility floor (same as quant serving: tiny
               mats stay dense — streaming them is free anyway).
    """

    default: str = "quant_sparse"
    rules: tuple = ()
    q_prune: float = 0.0
    bk: int = 128
    bn: int = 128
    score: str = "l1"
    min_size: int = 16384
    min_contract: int = 64

    def __post_init__(self):
        if self.default not in REPRS:
            raise ValueError(f"default must be one of {REPRS}, got {self.default!r}")
        if not 0.0 <= self.q_prune < 1.0:
            raise ValueError(f"q_prune must be in [0, 1), got {self.q_prune}")
        for r in self.rules:
            if len(r) not in (2, 3):
                raise ValueError(f"rule must be (sub, repr[, q_prune]), got {r!r}")
            if r[1] not in REPRS:
                raise ValueError(f"unknown representation {r[1]!r} in rule {r!r}")
            if len(r) == 3 and r[2] is not None and not 0.0 <= r[2] < 1.0:
                raise ValueError(f"rule q_prune must be in [0, 1), got {r!r}")

    @property
    def block(self) -> BlockPruneConfig:
        return BlockPruneConfig(bk=self.bk, bn=self.bn, score=self.score)


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def path_str(path) -> str:
    return "/".join(_key_str(k) for k in path)


def leaf_name(path) -> str:
    return _key_str(path[-1]) if path else ""


def _quant_eligible(name: str, leaf, cfg: PlanConfig) -> bool:
    return (
        hasattr(leaf, "ndim")
        and leaf.ndim >= 2
        and leaf.size >= cfg.min_size
        and leaf.shape[-2] >= cfg.min_contract  # a real contraction dim
        and (name.startswith("w") or name in QUANT_KEYS)
    )


def _sparse_eligible(name: str, leaf, cfg: PlanConfig) -> bool:
    """Sparse packing needs a projection-style matmul weight (w*): embedding
    tables are consumed by gather (tok) or a transposed tied unembed (head),
    neither of which the block layout serves; shapes must tile exactly."""
    if not (_quant_eligible(name, leaf, cfg) and name.startswith("w")):
        return False
    K, N = leaf.shape[-2], leaf.shape[-1]
    return K % cfg.bk == 0 and N % cfg.bn == 0 and K // cfg.bk >= 1 and N // cfg.bn >= 1


def assign_leaf(path, leaf, cfg: PlanConfig) -> tuple:
    """(representation, q_prune) for one leaf: rules override the default
    (and, for 3-tuple rules, the plan-wide q_prune); ineligible leaves
    degrade gracefully (quant_sparse -> quant -> dense).  q_prune is 0 for
    the non-sparse representations — they stream every weight."""
    name = leaf_name(path)
    ps = path_str(path)
    kind, q = cfg.default, cfg.q_prune
    for rule in cfg.rules:
        if rule[0] in ps:
            kind = rule[1]
            if len(rule) == 3 and rule[2] is not None:
                q = float(rule[2])
            break
    if kind not in REPRS:
        raise ValueError(f"unknown representation {kind!r} for {ps}")
    if kind in ("block_sparse", "quant_sparse") and not _sparse_eligible(name, leaf, cfg):
        kind = "quant" if kind == "quant_sparse" else "dense"
    if kind == "quant" and not _quant_eligible(name, leaf, cfg):
        kind = "dense"
    if kind not in ("block_sparse", "quant_sparse"):
        q = 0.0
    return kind, q


def assign_repr(path, leaf, cfg: PlanConfig) -> str:
    """Representation for one leaf (``assign_leaf`` without the q_prune)."""
    return assign_leaf(path, leaf, cfg)[0]


# ---------------------------------------------------------------------------
# packing (host-side, build time)
# ---------------------------------------------------------------------------


def quantize_leaf(leaf):
    """int8-quantize one matmul weight into the {"q", "s"} dict ``qdense``
    consumes.  Scales reduce over the contraction axis (-2) only, so stacked
    per-layer / per-expert weights keep independent per-(layer, channel)
    scales and scan slicing stays aligned: q (L, d, f) pairs with s (L, f)."""
    lf = jnp.asarray(leaf, jnp.float32)
    amax = jnp.max(jnp.abs(lf), axis=-2, keepdims=True)
    scales = jnp.maximum(amax, 1e-8) / 127.0
    qv = jnp.clip(jnp.round(lf / scales), -127, 127).astype(jnp.int8)
    return {"q": qv, "s": jnp.squeeze(scales, axis=-2)}


def quantize_for_serving(params, min_size: int = 16384):
    """int8-quantize all eligible matmul weights (the pre-plan API; kept as
    the ``quant``-everywhere special case of ``compress``)."""
    cfg = PlanConfig(default="quant", min_size=min_size)

    def q(path, leaf):
        if hasattr(leaf, "ndim") and _quant_eligible(leaf_name(path), leaf, cfg):
            return quantize_leaf(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(q, params)


def pack_block_sparse(leaf, cfg: PlanConfig, quant: bool,
                      out_axis: Optional[str] = None) -> PackedLinear:
    """Prune ``leaf`` to block sparsity cfg.q_prune and pack it.

    Handles any leading stacking dims (scan units, MoE experts, or both):
    each (K, N) slice is pruned independently; ``mb`` (stored blocks per
    column) is the max over all slices and columns so the packed layout is
    rectangular and scan/vmap slicing stays trivial.  Padded entries are
    zero blocks with row index 0 — they multiply by zero, and the kernel
    additionally skips them via ``counts``.
    """
    w = np.asarray(jnp.asarray(leaf, jnp.float32))
    lead = w.shape[:-2]
    ws = w.reshape((-1,) + w.shape[-2:])
    L, K, N = ws.shape
    bk, bn = cfg.bk, cfg.bn
    nrb, ncb = K // bk, N // bn

    masks = np.stack(
        [np.asarray(block_mask(jnp.asarray(ws[l]), cfg.q_prune, cfg.block)) for l in range(L)]
    )  # (L, nrb, ncb)
    counts = masks.sum(axis=1).astype(np.int32)  # (L, ncb)
    mb = max(1, int(counts.max()))

    # (L, nrb, ncb, bk, bn) block view for panel gathering
    wb = ws.reshape(L, nrb, bk, ncb, bn).transpose(0, 1, 3, 2, 4)

    scales = None
    if quant:
        # per-(slice, output-channel) scales over the *masked* matrix, so a
        # column whose largest weights were pruned keeps full int8 range
        wm = ws * np.stack(
            [np.asarray(expand_block_mask(jnp.asarray(masks[l]), cfg.block)) for l in range(L)]
        )
        amax = np.abs(wm).max(axis=1)  # (L, N)
        scales = np.maximum(amax, 1e-8).astype(np.float32) / 127.0

    pdtype = np.int8 if quant else np.float32
    blocks = np.zeros((L, ncb * mb, bk, bn), pdtype)
    rows = np.zeros((L, ncb, mb), np.int32)
    for l in range(L):
        for j in range(ncb):
            for s, i in enumerate(np.nonzero(masks[l, :, j])[0]):
                payload = wb[l, i, j]
                if quant:
                    sc = scales[l, j * bn : (j + 1) * bn][None, :]
                    payload = np.clip(np.round(payload / sc), -127, 127)
                blocks[l, j * mb + s] = payload
                rows[l, j, s] = i

    # Multi-column kernel walk (static, built once at pack time): one entry
    # per surviving block; stacked slices padded to a shared length so scan
    # and vmap slice the walk exactly like the payload.
    walks = [build_walk(rows[l], counts[l], mb) for l in range(L)]
    n_walk = max(w["idx"].shape[0] for w in walks)
    walks = [pad_walk(w, n_walk) for w in walks]
    walk = {
        k: jnp.asarray(np.stack([w[k] for w in walks]).reshape(lead + (n_walk,)))
        for k in ("idx", "rows", "cols", "flags")
    }

    blocks = blocks.reshape(lead + blocks.shape[1:])
    rows = rows.reshape(lead + rows.shape[1:])
    counts = counts.reshape(lead + counts.shape[1:])
    if scales is not None:
        scales = scales.reshape(lead + scales.shape[1:])
    return PackedLinear(
        blocks=jnp.asarray(blocks),
        block_rows=jnp.asarray(rows),
        counts=jnp.asarray(counts),
        scales=None if scales is None else jnp.asarray(scales),
        walk=walk,
        kind="quant_sparse" if quant else "block_sparse",
        shape=(K, N),
        bk=bk,
        bn=bn,
        out_axis=out_axis,
    )


# ---------------------------------------------------------------------------
# plan object + stats
# ---------------------------------------------------------------------------

_DENSE_STREAM_BYTES = 2.0  # dense weights are streamed bf16 at serving time


@dataclasses.dataclass(frozen=True)
class LeafPlan:
    path: str
    kind: str
    shape: tuple
    n_weights: int
    surviving: int  # weights actually streamed (== n_weights unless pruned)
    payload_bytes: float
    metadata_bytes: float
    # logical sharding axes of the *dense* leaf (e.g. ("d", "ff")); the
    # axis-rules registry expands them to the packed children (see
    # _packed_leaf_axes / _quant_leaf_axes).  () when the plan was built
    # without axes (compress(axes=None), pre-registry plan caches).
    axes: tuple = ()

    @property
    def bytes(self) -> float:
        return self.payload_bytes + self.metadata_bytes

    @property
    def q_prune(self) -> float:
        return 1.0 - self.surviving / max(1, self.n_weights)


@dataclasses.dataclass
class WeightPlan:
    """The materialized plan: per-leaf assignments + the compressed pytree.

    ``params`` is treedef-compatible with the dense pytree it came from —
    pass it anywhere dense params go (prefill, decode_step, ServingEngine).
    """

    cfg: PlanConfig
    leaves: dict  # path -> LeafPlan
    params: Any = None
    _by_path: dict = dataclasses.field(default_factory=dict)

    # -- the one dispatch ---------------------------------------------------

    def apply_linear(self, path: str, x: jax.Array) -> jax.Array:
        """y = x @ W for the planned weight at ``path`` (e.g.
        "unit/0/mlp/w_up"), whatever representation it was assigned."""
        if path not in self._by_path:
            raise KeyError(f"no planned weight at {path!r}; known: {sorted(self._by_path)[:8]}...")
        return apply_linear(x, self._by_path[path])

    # -- aggregate stats (feed the perf model / BatchSizer) -----------------

    @property
    def n_weights(self) -> int:
        return sum(l.n_weights for l in self.leaves.values())

    @property
    def surviving_weights(self) -> int:
        return sum(l.surviving for l in self.leaves.values())

    @property
    def weight_bytes(self) -> float:
        """HBM bytes streamed per decode step (payload + metadata)."""
        return sum(l.bytes for l in self.leaves.values())

    @property
    def q_prune_effective(self) -> float:
        return 1.0 - self.surviving_weights / max(1, self.n_weights)

    @property
    def b_weight_effective(self) -> float:
        """Payload bytes per *surviving* weight (the perf model's b_weight)."""
        payload = sum(l.payload_bytes for l in self.leaves.values())
        return payload / max(1, self.surviving_weights)

    @property
    def q_overhead_effective(self) -> float:
        """Metadata inflation per payload byte (the paper's q_overhead)."""
        payload = sum(l.payload_bytes for l in self.leaves.values())
        return self.weight_bytes / max(1.0, payload)

    def sizer(self, *, sparse_compute: bool = True, **kw):
        """A BatchSizer with this plan's memory-traffic corrections applied:
        n_opt then moves the way the paper's Section 5.6 predicts."""
        from repro.core.batching import BatchSizer

        kw.setdefault("n_params", self.n_weights)
        return BatchSizer(
            b_weight=self.b_weight_effective,
            q_prune=self.q_prune_effective,
            q_overhead=self.q_overhead_effective,
            sparse_compute=sparse_compute,
            **kw,
        )

    # -- sharding (axis-rules registry) -------------------------------------

    def axes_tree(self):
        """Dense logical-axis pytree matching ``params`` (tuples at planned-
        node positions, from ``LeafPlan.axes``; None = replicated where the
        plan has no record).  Feed to ``shardlib.tree_shardings`` — the
        registry expands packed/quant nodes to per-child axes."""

        def ax(path, node):
            lp = self.leaves.get(path_str(path))
            return tuple(lp.axes) if lp is not None and lp.axes else None

        return jax.tree_util.tree_map_with_path(
            ax, self.params, is_leaf=_is_plan_node)

    def param_shardings(self, mesh=None, rules=None):
        """NamedShardings for the compressed ``params`` pytree under
        (mesh, rules) — what the serving engine / launcher place packed
        weights with."""
        return sl.tree_shardings(
            self.params, self.axes_tree(), mesh=mesh, rules=rules)

    def placed(self, mesh, rules=None) -> "WeightPlan":
        """This plan with its packed params moved onto their shardings.
        Packing runs on one device; placing the plan (rather than only
        the engine's copy) leaves no whole-model copy behind there."""
        params = jax.device_put(self.params, self.param_shardings(mesh, rules))
        return dataclasses.replace(
            self, params=params, _by_path=_index_nodes(params))

    @property
    def fused_pairs(self) -> int:
        """Gated-FFN (w_gate, w_up) pairs the fused gate+up node serves as
        one launch: both sparse-packed, same kind and dense shape."""
        n = 0
        for p, l in self.leaves.items():
            if not p.endswith("w_gate") or l.kind not in ("block_sparse", "quant_sparse"):
                continue
            lu = self.leaves.get(p[: -len("w_gate")] + "w_up")
            if lu is not None and lu.kind == l.kind and lu.shape == l.shape:
                n += 1
        return n

    def summary(
        self,
        *,
        kv_bytes_per_token: float = 0.0,
        context_len: int = 0,
        batch: Optional[int] = None,
        per_leaf: bool = False,
    ) -> str:
        """One coherent traffic budget, in the bytes/token units the sizer
        consumes: the weight stream is charged once per decode step and
        amortized over the batch; the KV stream is charged per live token.
        ``batch`` defaults to the plan-corrected n_opt so the logged budget
        matches what ``sizer().step_time`` would charge at the balance
        point.

        Each kind's aggregate carries its q_prune range so a non-uniform
        (autotuned) plan is inspectable at a glance; ``per_leaf=True``
        appends one provenance line per leaf — the full kind + q_prune
        assignment a loaded plan cache would otherwise hide."""
        by_kind: dict = {}
        for l in self.leaves.values():
            agg = by_kind.setdefault(l.kind, [0, 0.0, 1.0, 0.0])
            agg[0] += 1
            agg[1] += l.bytes
            agg[2] = min(agg[2], l.q_prune)
            agg[3] = max(agg[3], l.q_prune)

        def _q_label(lo: float, hi: float) -> str:
            if hi <= 0.0:
                return ""
            if hi - lo < 5e-3:
                return f" q={hi:.2f}"
            return f" q={lo:.2f}..{hi:.2f}"

        parts = [
            f"{k}:{n} ({b/1e6:.2f} MB{_q_label(lo, hi)})"
            for k, (n, b, lo, hi) in sorted(by_kind.items())
        ]
        from repro.core.batching import UNBOUNDED_NOPT

        n = batch or self.sizer(
            kv_bytes_per_token=kv_bytes_per_token, context_len=context_len
        ).n_opt
        # the UNBOUNDED_NOPT sentinel means memory-bound at any batch —
        # render it as inf, not a batch size the reader might believe
        n_label = "inf" if (batch is None and n >= UNBOUNDED_NOPT) else str(n)
        w_tok = self.weight_bytes / max(1, n)
        kv_tok = kv_bytes_per_token * context_len
        s = (
            f"plan[{', '.join(parts)}] "
            f"q_prune={self.q_prune_effective:.3f} "
            f"b_weight={self.b_weight_effective:.2f} "
            f"q_overhead={self.q_overhead_effective:.4f} "
            f"fused_pairs={self.fused_pairs} "
            f"bytes/step={self.weight_bytes/1e6:.2f} MB | "
            f"bytes/tok@n={n_label}: weights={w_tok:.0f} kv={kv_tok:.0f} "
            f"total={w_tok + kv_tok:.0f}"
        )
        if per_leaf:
            s += "\n" + "\n".join(
                f"  {p}: {l.kind} q={l.q_prune:.2f} "
                f"{l.bytes/1e3:.1f} kB ({l.shape})"
                for p, l in sorted(self.leaves.items())
            )
        return s


def _leaf_stats(path: str, kind: str, leaf, packed, axes: tuple = ()) -> LeafPlan:
    n = int(np.prod(leaf.shape)) if hasattr(leaf, "shape") else 1
    shape = tuple(getattr(leaf, "shape", ()))
    if kind == "dense":
        return LeafPlan(path, kind, shape, n, n, n * _DENSE_STREAM_BYTES, 0.0, axes)
    if kind == "quant":
        scales = packed["s"]
        return LeafPlan(path, kind, shape, n, n, float(n), 4.0 * scales.size, axes)
    # sparse kinds
    p: PackedLinear = packed
    counts = np.asarray(p.counts)
    surv_blocks = int(counts.sum())
    surviving = surv_blocks * p.bk * p.bn
    b = 1.0 if kind == "quant_sparse" else _DENSE_STREAM_BYTES
    payload = surviving * b
    meta = 4.0 * surv_blocks + 4.0 * counts.size  # row idx per block + counts
    if p.scales is not None:
        meta += 4.0 * np.asarray(p.scales).size
    return LeafPlan(path, kind, shape, n, surviving, payload, meta, axes)


# ---------------------------------------------------------------------------
# serve-time plan cache: persist/restore compressed pytrees (checkpoint/store)
# ---------------------------------------------------------------------------


def _node_meta(node) -> dict:
    """Static reconstruction metadata for one planned node."""
    if isinstance(node, PackedLinear):
        return {
            "repr": "packed",
            "kind": node.kind,
            "shape": list(node.shape),
            "bk": node.bk,
            "bn": node.bn,
            "out_axis": node.out_axis,
            "has_scales": node.scales is not None,
            "has_walk": node.walk is not None,
        }
    if isinstance(node, dict) and "q" in node:
        return {"repr": "quant"}
    return {"repr": "dense"}


def _is_plan_node(n) -> bool:
    return isinstance(n, PackedLinear) or (isinstance(n, dict) and "q" in n)


def _index_nodes(params) -> dict:
    """path -> planned node (PackedLinear / quant dict / plain leaf)."""
    out = {}

    def visit(path, node):
        out[path_str(path)] = node
        return node

    jax.tree_util.tree_map_with_path(visit, params, is_leaf=_is_plan_node)
    return out


def save_plan(base: str, plan: WeightPlan) -> str:
    """Persist a compressed plan (packed pytree + reconstruction metadata)
    via ``checkpoint.store`` so a serving engine can boot from packed
    weights instead of re-packing at startup.  Returns the directory."""
    from repro.checkpoint import store

    metadata = {
        "plan_cfg": {
            **{
                f.name: getattr(plan.cfg, f.name)
                for f in dataclasses.fields(plan.cfg)
                if f.name != "rules"
            },
            "rules": [list(r) for r in plan.cfg.rules],
        },
        "leaves": {
            p: {**dataclasses.asdict(l), "shape": list(l.shape),
                "axes": list(l.axes)}
            for p, l in plan.leaves.items()
        },
        "packed": {p: _node_meta(n) for p, n in plan._by_path.items()},
    }
    return store.save(base, 0, plan.params, metadata=metadata, keep=1)


def load_plan(base: str, dense_params) -> WeightPlan:
    """Rebuild a WeightPlan saved by :func:`save_plan`.

    ``dense_params`` supplies the pytree *structure* only (e.g. from
    ``api.init_params``): its array leaves are replaced node-for-node with
    the stored packed representations — no pruning/quantization runs.
    """
    from repro.checkpoint import store

    leaves_np, manifest = store.restore_flat(base)
    meta = manifest["metadata"]
    cfg_d = dict(meta["plan_cfg"])
    cfg_d["rules"] = tuple(tuple(r) for r in cfg_d["rules"])
    unknown = set(cfg_d) - {f.name for f in dataclasses.fields(PlanConfig)}
    if unknown or any("out_axis" not in pm for pm in meta["packed"].values()
                      if pm["repr"] == "packed"):
        raise ValueError(f"plan cache written by another version "
                         f"(plan config fields {sorted(unknown)})")
    cfg = PlanConfig(**cfg_d)

    def skeleton(path, leaf):
        if not hasattr(leaf, "ndim"):
            return leaf
        pm = meta["packed"].get(path_str(path), {"repr": "dense"})
        if pm["repr"] == "quant":
            return {"q": 0, "s": 0}
        if pm["repr"] == "packed":
            if tuple(leaf.shape[-2:]) != tuple(pm["shape"]):
                raise ValueError(
                    f"plan cache leaf {path_str(path)} packs dense shape "
                    f"{tuple(pm['shape'])}, model has {tuple(leaf.shape[-2:])}"
                )
            return PackedLinear(
                blocks=0,
                block_rows=0,
                counts=0,
                scales=0 if pm["has_scales"] else None,
                walk={"idx": 0, "rows": 0, "cols": 0, "flags": 0}
                if pm["has_walk"]
                else None,
                kind=pm["kind"],
                shape=tuple(pm["shape"]),
                bk=pm["bk"],
                bn=pm["bn"],
                out_axis=pm["out_axis"],
            )
        return leaf

    skel = jax.tree_util.tree_map_with_path(skeleton, dense_params)
    flat, treedef = jax.tree_util.tree_flatten(skel)
    if len(flat) != manifest["n_leaves"]:
        raise ValueError(
            f"plan cache has {manifest['n_leaves']} leaves, model structure "
            f"expects {len(flat)} — was it saved for a different config?"
        )
    if str(treedef) != manifest["treedef"]:
        raise ValueError("plan cache treedef does not match this model's structure")
    # dense placeholders are the model's own arrays: their stored shapes
    # must match (catches e.g. a layer-count change that keeps the treedef)
    for i, (ph, entry) in enumerate(zip(flat, manifest["leaves"])):
        if hasattr(ph, "shape") and tuple(ph.shape) != tuple(entry["shape"]):
            raise ValueError(
                f"plan cache leaf {i} has shape {tuple(entry['shape'])}, "
                f"model structure expects {tuple(ph.shape)}"
            )
    params = jax.tree_util.tree_unflatten(treedef, [jnp.asarray(a) for a in leaves_np])
    leaves = {
        # `or ()` / .get: plan caches written before the axis-rules registry
        # have no axes entry — they restore as unsharded (replicated) plans
        p: LeafPlan(**{**d, "shape": tuple(d["shape"]),
                       "axes": tuple(d.get("axes") or ())})
        for p, d in meta["leaves"].items()
    }
    return WeightPlan(cfg=cfg, leaves=leaves, params=params, _by_path=_index_nodes(params))


def compress(params, cfg: PlanConfig = PlanConfig(), *, axes=None) -> WeightPlan:
    """Walk ``params``, assign each leaf a representation, pack, and return
    the WeightPlan (with ``plan.params`` the compressed pytree).

    ``axes`` (optional) is the matching pytree of dense logical sharding
    axes (``api.param_axes(cfg)``): each leaf's axes are recorded in its
    ``LeafPlan`` so the plan can emit NamedShardings for its own packed
    pytree (``plan.param_shardings``) through the axis-rules registry.
    """

    leaves: dict = {}
    by_path: dict = {}

    def _one(path, leaf, ax=None):
        if not hasattr(leaf, "ndim"):
            return leaf
        ps = path_str(path)
        kind, q = assign_leaf(path, leaf, cfg)
        if kind == "dense":
            packed = leaf
        elif kind == "quant":
            packed = quantize_leaf(leaf)
        else:
            pc = cfg if q == cfg.q_prune else dataclasses.replace(cfg, q_prune=q)
            packed = pack_block_sparse(leaf, pc, quant=(kind == "quant_sparse"),
                                       out_axis=ax[-1] if ax else None)
        leaves[ps] = _leaf_stats(
            ps, kind, leaf, packed, axes=tuple(ax) if ax else ())
        by_path[ps] = packed
        return packed

    if axes is not None:
        compressed = jax.tree_util.tree_map_with_path(_one, params, axes)
    else:
        compressed = jax.tree_util.tree_map_with_path(_one, params)
    return WeightPlan(cfg=cfg, leaves=leaves, params=compressed, _by_path=by_path)


# ---------------------------------------------------------------------------
# the runtime dispatch — every layer's matmuls route through here
# ---------------------------------------------------------------------------


# THE activation table: gated variants alias their underlying activation.
# Single source of truth — kernels/fused_gate_up and models/layers._ACT
# both consume this map, so a new activation lands everywhere at once.
GATE_ACTS = {
    "linear": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "sigmoid": jax.nn.sigmoid,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "swiglu": jax.nn.silu,
    "geglu": jax.nn.gelu,
    "gelu_glu": jax.nn.gelu,
    "tanh": jnp.tanh,
}
_GATE_ACTS = GATE_ACTS  # internal alias (kept for call sites/tests)


def _fusable_pair(g, u) -> bool:
    return (
        isinstance(g, PackedLinear)
        and isinstance(u, PackedLinear)
        and g.kind == u.kind
        and g.shape == u.shape
        and (g.bk, g.bn) == (u.bk, u.bn)
        and (g.scales is None) == (u.scales is None)
        and g.blocks.ndim == u.blocks.ndim
    )


def apply_gate_up(x: jax.Array, w_gate, w_up, activation: str = "silu") -> jax.Array:
    """act(x @ Wg) * (x @ Wu) — the fused-pair plan node every gated FFN
    routes through.

    When both weights are block-sparse packed with matching geometry (the
    quant_sparse pair), the whole gated projection runs as ONE kernel launch
    (kernels/fused_gate_up): activations are streamed once, the gate never
    round-trips HBM, and both int8 epilogues run on-chip.  Stacked pairs
    (MoE experts, unsliced unit stacks) vmap down to the 2-D case; any other
    representation mix falls back to two ``apply_linear`` dispatches plus
    the elementwise gate (which XLA fuses, but as two weight streams).
    """
    if activation not in _GATE_ACTS:
        raise ValueError(f"unknown gate activation {activation!r}")
    if _fusable_pair(w_gate, w_up):
        if w_gate.stacked:
            return jax.vmap(
                functools.partial(apply_gate_up, activation=activation)
            )(x, w_gate, w_up)
        return _apply_fused_pair(x, w_gate, w_up, activation)
    return _GATE_ACTS[activation](apply_linear(x, w_gate)) * apply_linear(x, w_up)


def _apply_fused_pair(x, g: PackedLinear, u: PackedLinear, activation: str):
    K, N = g.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, K)
    if _kernel_on(g) or _kernel_on(u):
        from repro.kernels import ops

        out = sl.mesh_axes(g.out_axis, N // g.bn)
        rows = sl.mesh_axes("batch", x2.shape[0])

        def local(x2, gb, gr, gc, gs, ub, ur, uc, us):
            return ops.fused_gate_up(
                x2, _local_sparse(g, gb, gr, gc), _local_sparse(u, ub, ur, uc),
                gate_scales=gs, up_scales=us, activation=activation)

        col = (P(out, None, None), P(out, None), P(out), P(out))
        y = sl.kernel_call(
            local,
            (x2, g.blocks, g.block_rows, g.counts, g.scales,
             u.blocks, u.block_rows, u.counts, u.scales),
            in_specs=(P(rows, None),) + col + col, out_specs=P(rows, out))
    else:
        y = _GATE_ACTS[activation](_packed_ref_matmul(x2, g)) * _packed_ref_matmul(x2, u)
    return y.astype(x.dtype).reshape(*lead, N)


def _kernel_on(w: PackedLinear) -> bool:
    if w.use_kernel is not None:
        return w.use_kernel
    return jax.default_backend() == "tpu"


def apply_linear(x: jax.Array, w) -> jax.Array:
    """y = x @ W for any planned representation of W.

    W is a plain array (dense), a {"q", "s"} dict (int8 quant), or a
    PackedLinear (block-sparse, optionally int8).  Stacked weights (one
    leading dim: MoE experts, unsliced unit stacks) pair with an equally
    stacked leading dim on x and vmap down to the 2-D case.  x may carry any
    extra leading dims (batch, sequence).
    """
    if isinstance(w, PackedLinear):
        if w.stacked:
            return jax.vmap(apply_linear)(x, w)
        return _apply_packed(x, w)
    if isinstance(w, dict) and "q" in w:
        if w["q"].ndim > 2:
            return jax.vmap(apply_linear)(x, w)
        return _apply_quant(x, w)
    if getattr(w, "ndim", 2) > 2:
        return jax.vmap(apply_linear)(x, w)
    return x @ w.astype(x.dtype)


def _apply_quant(x, w):
    """int8 path: 1 byte/weight from HBM (Section 4.1 at int8), dequantized
    in the epilogue — (x @ q) * s with f32 accumulation; scales factor out
    of the contraction."""
    dt = x.dtype
    y = jax.lax.dot_general(
        x, w["q"].astype(dt),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return (y * w["s"].astype(jnp.float32)).astype(dt)


def _apply_packed(x, w: PackedLinear):
    K, N = w.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, K)
    if _kernel_on(w):
        y = _packed_kernel_matmul(x2, w)
    else:
        y = _packed_ref_matmul(x2, w)
    return y.astype(x.dtype).reshape(*lead, N)


def _packed_ref_matmul(x2: jax.Array, w: PackedLinear) -> jax.Array:
    """Gather-based reference datapath (pure jnp — runs anywhere, vmappable).

    The activation gather by ``block_rows`` is the offset-calculation IP of
    Section 5.6 expressed as indexing; padded blocks are zero so ``counts``
    is not consulted (the kernel path uses it to skip MACs).
    """
    K, N = w.shape
    M = x2.shape[0]
    n_cols, mb = w.block_rows.shape
    xb = x2.reshape(M, K // w.bk, w.bk)
    xsel = jnp.take(xb, w.block_rows.reshape(-1), axis=1)  # (M, n_cols*mb, bk)
    xsel = xsel.reshape(M, n_cols, mb, w.bk)
    bl = w.blocks.reshape(n_cols, mb, w.bk, w.bn)
    y = jnp.einsum(
        "mcsk,cskn->mcn",
        xsel.astype(jnp.float32),
        bl.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).reshape(M, N)
    if w.scales is not None:
        y = y * w.scales.astype(jnp.float32)
    return y


def _local_sparse(w: PackedLinear, blocks, block_rows, counts) -> BlockSparse:
    """The BlockSparse view of one shard's block columns of ``w``."""
    return BlockSparse(
        blocks=blocks, block_rows=block_rows, counts=counts,
        shape=(w.shape[0], block_rows.shape[0] * w.bn),
        cfg=BlockPruneConfig(bk=w.bk, bn=w.bn),
    )


def _packed_kernel_matmul(x2: jax.Array, w: PackedLinear) -> jax.Array:
    """Pallas block-sparse kernel path: pruned blocks are never read from HBM
    and never enter the MXU (ops wrapper pads the row dim / picks interpret
    mode off-TPU).  With a pack-time walk the multi-column double-buffered
    kernel runs; legacy PackedLinears without one fall back to the
    per-column sweep.

    Under a mesh the call runs per shard (``shardlib.kernel_call``): each
    chip multiplies its own block columns.  The walk indexes the global
    column list, so a column-sharded call takes the per-column kernel."""
    from repro.kernels import ops

    out = sl.mesh_axes(w.out_axis, w.shape[1] // w.bn)
    rows = sl.mesh_axes("batch", x2.shape[0])
    walk = w.walk if out is None else None

    def local(x2, blocks, block_rows, counts, scales, walk):
        return ops.block_sparse_matmul(
            x2, _local_sparse(w, blocks, block_rows, counts),
            scales=scales, walk=walk)

    return sl.kernel_call(
        local, (x2, w.blocks, w.block_rows, w.counts, w.scales, walk),
        in_specs=(P(rows, None), P(out, None, None), P(out, None), P(out),
                  P(out), P()),
        out_specs=P(rows, out))
