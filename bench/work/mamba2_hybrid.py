"""Operations and bytes of the hybrid Mamba-2 + attention block
(``reference/mamba2_hybrid.py``): Mamba-2 layers (``w_z``, ``w_xbc``,
``w_dt``, a width-``mamba_d_conv`` conv, the SSM, ``w_out``) and GQA
attention layers (``wq``/``wk``/``wv``/``wo``), each with a dense SwiGLU
MLP, an embedding table and a tied or untied LM head.

``of(sizes)`` gives the object the metric readers use, with the decoder
block's interface (``work/decoder.py``): ``plan_bytes``, ``sparse_weights``,
``decode_flops``, ``prefill_flops``, ``stream_calls``, ``attn_call`` and
``attn_layers`` (the attention layers only); and, new here, ``ssm_call``
with ``ssm_layers``: one ``_ssm_decode_kernel`` call over the live rows.

The SSM's needed operations are the recurrence's, 5 per state element and
token (``exp(dt A) * state``, ``(dt x) * B``, their sum, ``* C`` and the
sum over the state): the chunked scan that prefill runs does more, and that
surplus is not needed work.
"""

from __future__ import annotations

import dataclasses

from bench.counts import Matrix, _matrix_bytes, _surviving
from bench.reference.decoder import serve_kind

SSM_OPS = 5.0  # per state element and token


@dataclasses.dataclass(frozen=True)
class Work:
    mamba_layers: int
    attn_layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    d_conv: int
    conv_dim: int
    block: int
    mamba: tuple  # Matrix per Mamba layer weight (the conv too, dense)
    attn: tuple  # Matrix per attention layer weight
    vectors: int  # 1-D weights per Mamba layer the plan streams (not norms)
    embed: tuple  # Matrix for tok (and head)

    @classmethod
    def of(cls, sizes: dict) -> "Work":
        m, plan = sizes["model"], sizes["plan"]
        kinds = list(m["pattern"])
        n_m, n_a = kinds.count("mamba"), kinds.count("global")
        assert n_m + n_a == m["layers"], kinds
        d, H, KVH, hd, f = (m["d_model"], m["heads"], m["kv_heads"],
                            m["head_dim"], m["d_ff"])
        sh, sp, N, K = (m["mamba_n_heads"], m["mamba_d_head"],
                        m["mamba_d_state"], m["mamba_d_conv"])
        di = sh * sp
        cd = di + 2 * N  # x, then B and C of one group
        b, q = plan["block"], plan["q_prune"]
        mlp = [("w_gate", d, f), ("w_up", d, f), ("w_down", f, d)]

        def matrices(shapes, layers):
            out = []
            for name, rows, cols in shapes:
                kind = serve_kind(name, (layers, rows, cols), plan)
                blocks = _surviving(rows, cols, b, q) if kind == "sparse" else 0
                out.append(Matrix(name, rows, cols, 1, kind, blocks, b))
            return tuple(out)

        mamba = matrices([("w_z", d, di), ("w_xbc", d, cd), ("w_dt", d, sh),
                          ("conv_w", K, cd), ("w_out", di, d)] + mlp, n_m)
        attn = matrices([("wq", d, H * hd), ("wk", d, KVH * hd),
                         ("wv", d, KVH * hd), ("wo", H * hd, d)] + mlp, n_a)
        vectors = 3 * sh + di + cd  # dt_bias, A_log, D, norm, conv bias
        emb = [Matrix("tok", m["vocab"], d, 1,
                      serve_kind("tok", (m["vocab"], d), plan), 0, b)]
        if not m["tied"]:
            emb.append(Matrix("head", d, m["vocab"], 1,
                              serve_kind("head", (d, m["vocab"]), plan), 0, b))
        return cls(n_m, n_a, d, H, KVH, hd, m["vocab"], sh, sp, N, K, cd, b,
                   mamba, attn, vectors, tuple(emb))

    @property
    def ssm_layers(self) -> int:
        return self.mamba_layers

    def _groups(self):
        return ((self.mamba, self.mamba_layers), (self.attn, self.attn_layers))

    # -- bytes a plan streams (the plan's own accounting) -------------------

    def plan_bytes(self) -> float:
        """Payload plus metadata bytes of every planned weight, as
        ``WeightPlan.weight_bytes`` accounts them (the 1-D Mamba weights
        stream dense, 2 bytes each; norm scales are left out)."""
        total = sum(layers * _matrix_bytes(mat)
                    for mats, layers in self._groups() for mat in mats)
        total += self.mamba_layers * 2.0 * self.vectors
        return total + sum(_matrix_bytes(mat) for mat in self.embed)

    def sparse_weights(self) -> int:
        """Surviving weights of every pruned matrix, all layers."""
        return sum(mat.weights * layers for mats, layers in self._groups()
                   for mat in mats if mat.kind == "sparse")

    # -- needed work --------------------------------------------------------

    def layer_weights_per_token(self) -> float:
        """Matmul weights a token multiplies through, over all layers (the
        conv is counted apart)."""
        return sum(layers * mat.weights for mats, layers in self._groups()
                   for mat in mats if mat.name != "conv_w")

    def head_flops(self) -> float:
        return 2.0 * self.d * self.vocab

    def attn_flops(self, context: float) -> float:
        """One query token over ``context`` keys, all attention layers."""
        return 4.0 * context * self.heads * self.head_dim * self.attn_layers

    def ssm_flops(self) -> float:
        """One token through every Mamba layer's conv and SSM."""
        per = (SSM_OPS * self.ssm_heads * self.ssm_head_dim * self.ssm_state
               + 2.0 * self.d_conv * self.conv_dim)
        return self.mamba_layers * per

    def decode_flops(self, context: float) -> float:
        return (2.0 * self.layer_weights_per_token() + self.head_flops()
                + self.attn_flops(context) + self.ssm_flops())

    def prefill_flops(self, prompt: int) -> float:
        """A whole prompt: every token through the layers, causal attention,
        the recurrence, the LM head for the last position only."""
        attn = (2.0 * self.heads * self.head_dim * prompt * (prompt + 1)
                * self.attn_layers)
        return ((2.0 * self.layer_weights_per_token() + self.ssm_flops())
                * prompt + attn + self.head_flops())

    def stream_calls(self, rows: int) -> list:
        """(flops, bytes) of each weight-stream kernel call of one step over
        ``rows`` token rows, every layer's: the gate+up pair as one fused
        call, each other sparse matrix as one walk call."""
        out = []
        for mats, layers in self._groups():
            sparse = {m.name: m for m in mats if m.kind == "sparse"}
            fused = [sparse.pop(n) for n in ("w_gate", "w_up") if n in sparse]
            groups = ([fused] if fused else []) + [[m] for m in sparse.values()]
            calls = []
            for grp in groups:
                K, N = grp[0].K, grp[0].N
                w = sum(m.blocks for m in grp) * self.block ** 2
                byts = w + 4.0 * N * len(grp) + 2.0 * rows * K + 2.0 * rows * N
                calls.append((2.0 * rows * w, byts))
            out += [c for c in calls for _ in range(layers)]
        return out

    def attn_call(self, contexts) -> tuple:
        """(flops, bytes) of one paged-attention call per attention layer
        over live sequences with the given contexts (keys attended, the new
        one included)."""
        c = float(sum(contexts))
        n = len(contexts)
        flops = 4.0 * c * self.heads * self.head_dim
        byts = (c * self.kv_heads * (2.0 * self.head_dim + 8.0)
                + 4.0 * n * self.heads * self.head_dim)
        return flops, byts

    def ssm_call(self, rows: int) -> tuple:
        """(flops, bytes) of one ``_ssm_decode_kernel`` call (one Mamba
        layer) over ``rows`` live rows: the float32 state read and written,
        x, dt, B, C in and y out per row (float32), A and D once."""
        hp = self.ssm_heads * self.ssm_head_dim
        flops = SSM_OPS * hp * self.ssm_state * rows
        byts = (rows * (2.0 * 4 * self.ssm_state * hp  # state in and out
                        + 4.0 * 3 * hp  # x, dt in; y out
                        + 4.0 * 2 * self.ssm_state)  # B, C
                + 4.0 * 2 * hp)  # A, D
        return flops, byts


of = Work.of
