"""Operations and bytes the served model needs, from shapes and the plan:
what every block's work module (``bench/work/<name>.py``) shares.

Everything follows from the configuration file (``model`` and ``plan``):
which matmul weights the plan packs, how many of their blocks survive
(``q_prune`` of every matrix's blocks, rounded as the plan rounds it), and
the int8 payloads and scales that stream.  Work is counted as needed, not
as run: a decode step's matmuls over its live rows, a chunk's over its
tokens, each routed token through its ``top_k`` experts only, the LM head
only where a logit is used.  Padding rows and the MoE layer's dense
all-expert compute are not counted.
"""

from __future__ import annotations

import dataclasses


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
