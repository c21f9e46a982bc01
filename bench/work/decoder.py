"""Operations and bytes of the GQA decoder block (``reference/decoder.py``):
attention through ``wq``/``wk``/``wv``/``wo`` and a dense or sparse-expert
FFN in every layer, an embedding table and an LM head.

A configuration's work module is found by the name of its reference
(``spec.work``); ``of(sizes)`` gives the object the metric readers use:

* ``plan_bytes()``: bytes the program's plan packs;
* ``sparse_weights()``: weights of the pruned matrices that survive;
* ``decode_flops(context)``, ``prefill_flops(prompt)``: needed operations
  of one decoded token and of one whole prompt;
* ``stream_calls(rows)``: (flops, bytes) of each weight-stream kernel call
  of one step over ``rows`` rows, every layer's;
* ``attn_call(contexts)``: (flops, bytes) of one paged-attention call over
  live sequences, and ``attn_layers``, the layers that make such a call.
"""

from __future__ import annotations

import dataclasses

from bench.counts import Matrix, _matrix_bytes, _surviving
from bench.reference.decoder import serve_kind


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

    @property
    def attn_layers(self) -> int:
        """Every layer attends."""
        return self.layers

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

    def sparse_weights(self) -> int:
        """Surviving weights of every pruned matrix, all layers and experts."""
        return sum(m.weights * m.count * self.layers for m in self.matrices
                   if m.kind == "sparse")

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


of = Work.of
