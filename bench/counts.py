"""Operations and bytes the served model needs, from shapes and the plan.

Everything here follows from the configuration file (``model`` and
``plan``): which matmul weights the plan packs, how many of their blocks
survive (``q_prune`` of every matrix's blocks, rounded as the plan rounds
it), and the int8 payloads and scales that stream.  Work is counted as
needed, not as run: a decode step's matmuls over its live rows, a chunk's
over its tokens, each routed token through its ``top_k`` experts only, the
LM head only where a logit is used.  Padding rows and the MoE layer's dense
all-expert compute are not counted.
"""

from __future__ import annotations

import dataclasses

from bench.reference.decoder import serve_kind


@dataclasses.dataclass(frozen=True)
class Matrix:
    """One matmul weight of a layer: ``count`` matrices of (K, N)."""

    name: str
    K: int
    N: int
    count: int  # experts per layer (1 for a plain weight)
    kind: str  # sparse | quant | dense
    blocks: int  # surviving (block, block) tiles per matrix (sparse only)
    block: int

    @property
    def weights(self) -> int:
        """Weights streamed per matrix."""
        return self.blocks * self.block ** 2 if self.kind == "sparse" else self.K * self.N


def _surviving(K: int, N: int, block: int, q: float) -> int:
    nb = (K // block) * (N // block)
    return nb - int(round(q * nb))


@dataclasses.dataclass(frozen=True)
class Work:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    tied: bool
    experts: int
    top_k: int
    block: int
    matrices: tuple  # Matrix per layer weight
    embed: tuple  # Matrix for tok (and head)

    @classmethod
    def of(cls, sizes: dict) -> "Work":
        m, plan = sizes["model"], sizes["plan"]
        L, d, H, KVH, hd = (m["layers"], m["d_model"], m["heads"],
                            m["kv_heads"], m["head_dim"])
        E, f, ef = m["experts"], m["d_ff"], m["expert_d_ff"]
        b, q = plan["block"], plan["q_prune"]
        shapes = [("wq", d, H * hd, 1), ("wk", d, KVH * hd, 1),
                  ("wv", d, KVH * hd, 1), ("wo", H * hd, d, 1)]
        if E:
            shapes += [("w_gate", d, ef, E), ("w_up", d, ef, E),
                       ("w_down", ef, d, E), ("router", d, E, 1)]
        else:
            shapes += [("w_gate", d, f, 1), ("w_up", d, f, 1), ("w_down", f, d, 1)]
        mats = []
        for name, K, N, n in shapes:
            stacked = (L, n, K, N) if n > 1 else (L, K, N)
            kind = serve_kind(name, stacked, plan)
            blocks = _surviving(K, N, b, q) if kind == "sparse" else 0
            mats.append(Matrix(name, K, N, n, kind, blocks, b))
        emb = [Matrix("tok", m["vocab"], d, 1,
                      serve_kind("tok", (m["vocab"], d), plan), 0, b)]
        if not m["tied"]:
            emb.append(Matrix("head", d, m["vocab"], 1,
                              serve_kind("head", (d, m["vocab"]), plan), 0, b))
        return cls(L, d, H, KVH, hd, m["vocab"], bool(m["tied"]), E,
                   m["top_k"], b, tuple(mats), tuple(emb))

    # -- bytes a plan streams (the plan's own accounting) -------------------

    def plan_bytes(self) -> float:
        """Payload plus metadata bytes of every planned weight, as
        ``WeightPlan.weight_bytes`` accounts them."""
        total = 0.0
        for mat in self.matrices:
            total += self.layers * mat.count * _matrix_bytes(mat)
        for mat in self.embed:
            total += _matrix_bytes(mat)
        return total

    # -- needed work --------------------------------------------------------

    def layer_weights_per_token(self) -> float:
        """Weights a token multiplies through, over all layers (the routed
        experts only)."""
        per = 0.0
        for mat in self.matrices:
            share = self.top_k if mat.count > 1 else 1
            per += share * mat.weights
        return self.layers * per

    def head_flops(self) -> float:
        return 2.0 * self.d * self.vocab

    def attn_flops(self, context: float) -> float:
        """One query token over ``context`` keys, all layers."""
        return 4.0 * context * self.heads * self.head_dim * self.layers

    def decode_flops(self, context: float) -> float:
        return (2.0 * self.layer_weights_per_token() + self.head_flops()
                + self.attn_flops(context))

    def prefill_flops(self, prompt: int) -> float:
        """A whole prompt: every token through the layers, causal attention,
        the LM head for the last position only."""
        attn = 2.0 * self.heads * self.head_dim * prompt * (prompt + 1) * self.layers
        return 2.0 * self.layer_weights_per_token() * prompt + attn + self.head_flops()

    def stream_calls(self, rows: int) -> list:
        """(flops, bytes) of each weight-stream kernel call of one step over
        ``rows`` token rows, per layer: the gate+up pair as one fused call,
        each other sparse matrix as one walk call; the experts of a layer as
        one group (each routed row through ``top_k`` of them)."""
        mats = {m.name: m for m in self.matrices if m.kind == "sparse"}
        out = []
        fused = [mats.pop(n) for n in ("w_gate", "w_up") if n in mats]
        groups = ([fused] if fused else []) + [[m] for m in mats.values()]
        for grp in groups:
            n = grp[0].count
            r = rows * self.top_k if n > 1 else rows
            K, N = grp[0].K, grp[0].N
            w = sum(m.blocks for m in grp) * self.block ** 2
            flops = 2.0 * r * w
            byts = n * (w + 4.0 * N * len(grp)) + 2.0 * r * K + 2.0 * r * N
            out.append((flops, byts))
        return [c for c in out for _ in range(self.layers)]

    def attn_call(self, contexts) -> tuple:
        """(flops, bytes) of one paged-attention call per layer over live
        sequences with the given contexts (keys attended, new one included)."""
        c = float(sum(contexts))
        n = len(contexts)
        flops = 4.0 * c * self.heads * self.head_dim
        byts = (c * self.kv_heads * (2.0 * self.head_dim + 8.0)
                + 4.0 * n * self.heads * self.head_dim)
        return flops, byts


def _matrix_bytes(mat: Matrix) -> float:
    if mat.kind == "sparse":
        ncb = mat.N // mat.block
        return mat.weights + 4.0 * mat.blocks + 4.0 * ncb + 4.0 * mat.N
    if mat.kind == "quant":
        return float(mat.K * mat.N) + 4.0 * mat.N
    return 2.0 * mat.K * mat.N


def least_time(calls, peaks: dict) -> tuple:
    """(seconds, bound): the sum over calls of each call's roofline time,
    and which bound ("compute" or "memory") took the larger share."""
    t_c = t_m = 0.0
    for flops, byts in calls:
        fc = flops / peaks["bf16_flops"]
        fm = byts / peaks["hbm_bytes_per_s"]
        if fc >= fm:
            t_c += fc
        else:
            t_m += fm
    return t_c + t_m, ("compute" if t_c >= t_m else "memory")
