"""Analytical throughput model from the paper, Section 4.4.

The paper models a fully-connected layer transition j -> j+1 as two overlapped
streams -- computation on m*r MACs and weight transfer over a memory interface
of throughput T_mem -- and takes the max:

    t_calc = s_{j+1} * s_j * N * (1 - q_prune) / (m * r * f_pu)
    t_mem  = s_{j+1} * s_j * b_weight * q_overhead * (1 - q_prune) * N
             / (T_mem * n)
    t_proc = max(t_calc, t_mem)

and derives the optimal batch size (machine-balance point, t_calc == t_mem):

    n_opt = m * r * f_pu * b_weight * q_overhead / T_mem

This module implements the model exactly (so the paper's numbers can be
reproduced) and re-instantiates it with TPU v5e constants, where the same
two-term structure is the weight-streaming roofline of decode/serving.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

# ---------------------------------------------------------------------------
# Hardware descriptions
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """A machine, in the paper's parameterization.

    m:        parallel processing units (neurons per section)
    r:        parallel MACs per processing unit
    f_pu:     clock of the processing units [Hz]
    T_mem:    achievable memory throughput [bytes/s]
    b_weight: bytes per stored weight
    name:     human-readable tag
    """

    name: str
    m: int
    r: int
    f_pu: float
    T_mem: float
    b_weight: float = 2.0  # Q7.8 -> 16 bit

    @property
    def macs_per_s(self) -> float:
        return self.m * self.r * self.f_pu

    @property
    def flops_per_s(self) -> float:
        # one MAC = 2 FLOPs (mul + add)
        return 2.0 * self.macs_per_s


# The paper's batch-processing design on the ZedBoard (Zynq XC7020):
# m = 114 MAC units (batch sizes 1..4), f_pu = 100 MHz, r = 1.
# The four Zynq HP ports at 133 MHz x 64 bit give a practical ~1.6 GB/s
# aggregated weight throughput (the paper states DDR3 controller peak of the
# PS side is shared; we calibrate T_mem from the paper's own n_opt = 12.66
# with m=114, r=1, f=100e6, b=2, q_ov=1:   T_mem = m*r*f*b/n_opt).
ZYNQ_BATCH = HardwareSpec(
    name="zedboard-batch-m114",
    m=114,
    r=1,
    f_pu=100e6,
    T_mem=114 * 1 * 100e6 * 2.0 / 12.66,  # ~1.80 GB/s, calibrated to n_opt=12.66
    b_weight=2.0,
)

# The paper's pruning design: m = 4 coprocessors x r = 3 MACs = 12 MACs.
ZYNQ_PRUNE = HardwareSpec(
    name="zedboard-prune-m4r3",
    m=4,
    r=3,
    f_pu=100e6,
    T_mem=ZYNQ_BATCH.T_mem,
    b_weight=2.0,
)

# TPU v5e, one chip (published peaks, Google Cloud documentation "TPU
# v5e"). The MXU plays the role of the m x r MAC array:
# peak 197 TFLOP/s bf16 => m*r = 197e12 / 2 / f. We fold it into f_pu=1,
# m*r = MACs/s so the formulas carry over unchanged.  These are the v5e's
# numbers only: the sizer models a v5e whatever device it runs on, and
# serve.py prints the device kind beside them.
TPU_V5E = HardwareSpec(
    name="tpu-v5e-chip",
    m=1,
    r=1,
    f_pu=197e12 / 2.0,  # MACs/s
    T_mem=819e9,  # HBM bytes/s
    b_weight=2.0,  # bf16
)

TPU_V5E_PEAK_FLOPS = 197e12
TPU_V5E_HBM_BW = 819e9
TPU_V5E_ICI_BW = 50e9  # per link, per direction


# ---------------------------------------------------------------------------
# Layer / network descriptions
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerShape:
    """One FC transition: s_in inputs (s_j), s_out neurons (s_{j+1})."""

    s_in: int
    s_out: int

    @property
    def weights(self) -> int:
        return self.s_in * self.s_out


def fc_network(sizes: Sequence[int]) -> tuple[LayerShape, ...]:
    """A network '784x800x800x10' -> tuple of LayerShape transitions."""
    return tuple(LayerShape(a, b) for a, b in zip(sizes[:-1], sizes[1:]))


# The paper's four evaluation networks (Table 2 footnotes).
MNIST_4LAYER = fc_network([784, 800, 800, 10])
MNIST_8LAYER = fc_network([784, 800, 800, 800, 800, 800, 800, 10])
HAR_4LAYER = fc_network([561, 1200, 300, 6])
HAR_6LAYER = fc_network([561, 2000, 1500, 750, 300, 6])

PAPER_NETWORKS = {
    "mnist-4layer": MNIST_4LAYER,
    "mnist-8layer": MNIST_8LAYER,
    "har-4layer": HAR_4LAYER,
    "har-6layer": HAR_6LAYER,
}


def network_parameters(net: Sequence[LayerShape]) -> int:
    return sum(l.weights for l in net)


# ---------------------------------------------------------------------------
# The two-term model (Section 4.4)
# ---------------------------------------------------------------------------


def t_calc(
    layer: LayerShape,
    hw: HardwareSpec,
    n_samples: int,
    q_prune: float = 0.0,
) -> float:
    """Compute time for a layer across n_samples inputs [seconds]."""
    if not 0.0 <= q_prune <= 1.0:
        raise ValueError(f"q_prune must be in [0,1], got {q_prune}")
    ops = layer.s_out * layer.s_in * n_samples * (1.0 - q_prune)
    return ops / (hw.m * hw.r * hw.f_pu)


def t_mem(
    layer: LayerShape,
    hw: HardwareSpec,
    n_samples: int,
    batch: int = 1,
    q_prune: float = 0.0,
    q_overhead: float = 1.0,
) -> float:
    """Weight-transfer time for a layer across n_samples inputs [seconds].

    With batch processing, each weight is fetched once per `batch` samples.
    With pruning, only (1 - q_prune) of the weights are streamed, inflated by
    the sparse-format overhead q_overhead (paper: 64/(3*16) = 1.33).
    """
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    if q_overhead < 1.0:
        raise ValueError(f"q_overhead must be >= 1, got {q_overhead}")
    nbytes = (
        layer.s_out
        * layer.s_in
        * hw.b_weight
        * q_overhead
        * (1.0 - q_prune)
        * n_samples
    )
    return nbytes / (hw.T_mem * batch)


def t_proc(
    layer: LayerShape,
    hw: HardwareSpec,
    n_samples: int,
    batch: int = 1,
    q_prune: float = 0.0,
    q_overhead: float = 1.0,
) -> float:
    """Overall processing time: compute and transfer are overlapped (max)."""
    return max(
        t_calc(layer, hw, n_samples, q_prune),
        t_mem(layer, hw, n_samples, batch, q_prune, q_overhead),
    )


def network_t_proc(
    net: Sequence[LayerShape],
    hw: HardwareSpec,
    n_samples: int,
    batch: int = 1,
    q_prune: float | Sequence[float] = 0.0,
    q_overhead: float = 1.0,
) -> float:
    """Sum of per-layer t_proc over a whole network [seconds]."""
    if isinstance(q_prune, (int, float)):
        q_prune = [float(q_prune)] * len(net)
    if len(q_prune) != len(net):
        raise ValueError("q_prune must have one entry per layer")
    return sum(
        t_proc(l, hw, n_samples, batch, q, q_overhead)
        for l, q in zip(net, q_prune)
    )


def n_opt(hw: HardwareSpec, q_overhead: float = 1.0) -> float:
    """Optimal batch size: machine-balance point t_calc == t_mem.

    n_opt = m * r * f_pu * b_weight * q_overhead / T_mem
    """
    return hw.m * hw.r * hw.f_pu * hw.b_weight * q_overhead / hw.T_mem


def arithmetic_intensity(batch: int, b_weight: float = 2.0) -> float:
    """MACs per weight byte streamed, as a function of batch size."""
    return batch / b_weight


def machine_balance(hw: HardwareSpec) -> float:
    """MACs per byte the machine can sustain (the roofline ridge point)."""
    return hw.macs_per_s / hw.T_mem


# ---------------------------------------------------------------------------
# Cycle-accurate variant (paper Section 5.5):
#   ceil(s_out/m) * s_in * n + m * c_a   clock cycles for the batch datapath
# ---------------------------------------------------------------------------


def batch_datapath_cycles(
    layer: LayerShape, m: int, n: int, c_a: int = 1
) -> int:
    """Exact cycle count of the paper's batch-processing datapath."""
    return math.ceil(layer.s_out / m) * layer.s_in * n + m * c_a


def pruning_datapath_cycles(
    layer: LayerShape, m: int, r: int, n: int, q_prune: float
) -> int:
    """Cycle count of the paper's pruning datapath (Section 4.4 general form)."""
    per_row = math.ceil(layer.s_in * (1.0 - q_prune) / r)
    return math.ceil(layer.s_out / m) * per_row * n


# ---------------------------------------------------------------------------
# TPU decode roofline: the same model applied to LM serving
# ---------------------------------------------------------------------------


def decode_n_opt(
    peak_flops: float = TPU_V5E_PEAK_FLOPS,
    hbm_bw: float = TPU_V5E_HBM_BW,
    b_weight: float = 2.0,
    q_prune: float = 0.0,
    q_overhead: float = 1.0,
    sparse_compute: bool = True,
    n_params: int | None = None,
    kv_bytes_per_token: float = 0.0,
    context_len: int = 0,
    model_parallel: int = 1,
    kv_parallel: int | None = None,
) -> float:
    """Batch size at which decode flips from HBM-bound to compute-bound.

    Each decoded token touches every weight byte once per batch: the GEMV
    becomes a GEMM with n columns. Balance: 2*n FLOPs per b_weight bytes ==
    peak_flops / hbm_bw  =>  n_opt = peak_flops * b_weight / (2 * hbm_bw).

    This is the paper's n_opt with (m*r*f_pu) -> peak_flops/2 [MACs/s] and
    T_mem -> hbm_bw.

    Pruning (Section 5.6): with a kernel that skips pruned blocks
    (``sparse_compute=True``) both t_calc and t_mem scale with (1 - q_prune),
    so the balance point moves only by the format overhead q_overhead —
    exactly the paper's claim that the optimizations compose.  With
    masked-dense execution (``sparse_compute=False``) only t_mem shrinks and
    n_opt scales with (1 - q_prune): a smaller batch already saturates the
    MXU because the weight stream got cheaper but the MACs did not.

    KV-cache reads are *per-sample* traffic: they scale with the batch and
    never amortize, so they tilt the balance point upward.  Solving
    t_calc(n) == t_mem(n) for ``decode_step_time``'s two terms:

        n_opt = (W_stream / hbm_bw) / (2*P_compute/peak - ctx*kv/hbm_bw)

    with W_stream = P_eff * b_weight * q_overhead.  Needs ``n_params`` and
    ``context_len`` only when ``kv_bytes_per_token`` > 0; an int8 cache
    halves the kv term, moving n_opt back toward the weight-only point.
    A non-positive denominator means the per-token kv stream alone exceeds
    the compute budget — decode stays memory-bound at any batch (inf).

    Multi-chip (EIE-style distribution of the compressed stream across
    chips): ``model_parallel`` = m chips in one tensor-parallel group, each
    streaming W/m weight bytes and executing 1/m of the MACs;
    ``kv_parallel`` = the degree the KV cache leaves *actually* shard by
    (defaults to m; smaller when divisibility drops the kv_heads mapping —
    whisper-tiny's 6 heads on a 16-way model axis leave the cache
    replicated, kv_parallel = 1).  ``n`` is the batch per model group (data
    parallelism replicates the whole analysis).  Per chip:

        t_calc = 2 * comp * n / (m * peak)
        t_mem  = (W/m + n * ctx * kv / kv_m) / hbm

    Solving t_calc == t_mem:

        n_opt = (W_stream / hbm_bw) / (2*comp/peak - (m/kv_m) * ctx*kv/hbm_bw)

    With kv_m == m every term divides by m and n_opt is *unchanged* — a
    perfectly sharded group keeps the single-chip balance point per chip.
    With kv_m < m the replicated cache is relatively heavier per chip: the
    balance point rises, and can hit memory-bound-at-any-batch even where
    one chip had a finite n_opt — the multi-chip accounting the sharded
    serving bench checks (balance == 1.00 at the returned n_opt).
    """
    m = max(1, int(model_parallel))
    kv_m = max(1, int(kv_parallel if kv_parallel is not None else m))
    if kv_bytes_per_token > 0.0 and context_len > 0:
        if n_params is None:
            raise ValueError("n_params required for kv-aware n_opt")
        eff = n_params * (1.0 - q_prune)
        comp = eff if sparse_compute else n_params
        denom = (2.0 * comp / peak_flops
                 - (m / kv_m) * context_len * kv_bytes_per_token / hbm_bw)
        if denom <= 0.0:
            return float("inf")
        return (eff * b_weight * q_overhead / hbm_bw) / denom
    # weight-only balance: compute and weight stream both divide by m,
    # so model parallelism cancels out entirely.
    n = peak_flops * b_weight * q_overhead / (2.0 * hbm_bw)
    if not sparse_compute:
        n *= 1.0 - q_prune
    return n


def expected_committed(accept_rate: float, spec_k: int) -> float:
    """Expected tokens committed per speculative verify tick, per sequence.

    With k draft tokens and i.i.d. per-draft acceptance probability
    ``accept_rate`` = alpha, draft j commits only if drafts 1..j all
    matched, and the tick always commits one extra (resampled / bonus)
    token, so

        E[committed] = 1 + alpha + alpha^2 + ... + alpha^k
                     = (1 - alpha^(k+1)) / (1 - alpha)

    bounded in [1, k+1]: alpha=0 degenerates to plain decode (every tick
    still commits exactly one token), alpha=1 commits all k drafts plus
    the bonus.
    """
    if not 0.0 <= accept_rate <= 1.0:
        raise ValueError(f"accept_rate must be in [0,1], got {accept_rate}")
    if spec_k < 0:
        raise ValueError(f"spec_k must be >= 0, got {spec_k}")
    return float(sum(accept_rate**j for j in range(spec_k + 1)))


def spec_decode_n_opt(
    spec_k: int,
    peak_flops: float = TPU_V5E_PEAK_FLOPS,
    hbm_bw: float = TPU_V5E_HBM_BW,
    b_weight: float = 2.0,
    q_prune: float = 0.0,
    q_overhead: float = 1.0,
    sparse_compute: bool = True,
    n_params: int | None = None,
    kv_bytes_per_token: float = 0.0,
    context_len: int = 0,
    model_parallel: int = 1,
    kv_parallel: int | None = None,
    single_pass_kv: bool = True,
) -> float:
    """Machine-balance *sequence* batch for the speculative verify step.

    Draft tokens are extra samples of the paper's batch processing: one
    verify step pushes B * (k+1) rows (k drafts + the committed token per
    sequence) through one weight stream.  The compute term scales with the
    verified-position batch B * (k+1); with the single-pass multi-query
    kernel (``single_pass_kv=True``, the shipped datapath) the KV page
    stream does NOT — each page crosses HBM once per tick and all k+1
    positions score against it on-chip, so the kv term stays the plain-
    decode per-sequence read.  Solving t_calc == t_mem:

        t_calc = 2*comp*n*(k+1) / (m*peak)
        t_mem  = (W/m + n*ctx*kv/kv_m) / hbm
        B_opt  = (W/hbm) / ((k+1)*2*comp/peak - (m/kv_m)*ctx*kv/hbm)

    which equals ``decode_n_opt(kv_bytes_per_token / (k+1)) / (k+1)`` —
    the kv tilt on the balance point no longer grows with k.
    ``single_pass_kv=False`` models the per-position re-fetch datapath
    (kv charged k+1 times per tick; both terms scale together and B_opt =
    decode_n_opt / (k+1) exactly), kept for before/after comparisons in
    the benches.  The acceptance rate does not move the balance point
    (rejected positions still streamed and verified); it enters through
    ``expected_committed``, which converts verified positions into
    committed tokens/s.  The memory-bound-at-any-batch sentinel (inf)
    passes through unchanged — note single-pass makes it strictly harder
    to hit (the kv stream must now exceed (k+1)x the compute budget).
    """
    if spec_k < 0:
        raise ValueError(f"spec_k must be >= 0, got {spec_k}")
    kv = kv_bytes_per_token
    if single_pass_kv:
        kv = kv / (spec_k + 1)
    n = decode_n_opt(
        peak_flops, hbm_bw, b_weight, q_prune, q_overhead, sparse_compute,
        n_params, kv, context_len, model_parallel, kv_parallel,
    )
    return n / (spec_k + 1)


def spec_step_time(
    n_params: int,
    batch: int,
    spec_k: int,
    accept_rate: float,
    draft_n_params: int = 0,
    kv_bytes_per_token: float = 0.0,
    context_len: int = 0,
    peak_flops: float = TPU_V5E_PEAK_FLOPS,
    hbm_bw: float = TPU_V5E_HBM_BW,
    b_weight: float = 2.0,
    single_pass_kv: bool = True,
    **kw,
) -> dict:
    """Two-term model of one speculative tick: k draft steps + one verify.

    The verify step is ``decode_step_time`` at the verified-position batch
    ``batch * (k+1)`` — B*(k+1) rows through one target weight stream.
    With the single-pass multi-query kernel (``single_pass_kv=True``, the
    shipped datapath) the kv stream is charged ONCE per tick — kv_read =
    batch * ctx * kv_tok, the plain-decode read, because all k+1 positions
    score each page while it sits on-chip; modeled by handing the verify
    step ``kv_bytes_per_token / (k+1)`` per position (the kv term is
    linear, so kv_parallel accounting is untouched).  ``False`` restores
    the per-position re-fetch accounting ((k+1)x kv per tick) for
    before/after comparisons.  The draft model (``draft_n_params``,
    streamed at the same ``b_weight``) runs k sequential single-token
    steps at batch B; its kv stream is folded into its weight stream ratio
    and omitted (drafts are small by construction — the term that matters
    is the k weight streams).  Returns the verify dict plus:

    ``t_draft``               draft-side time per tick
    ``t_tick``                t_draft + verify t_proc
    ``committed_per_tick``    batch * expected_committed(accept_rate, k)
    ``tokens_per_s``          committed tokens per second
    ``tokens_per_weight_stream``  committed tokens amortizing ONE pass of
                              the target weight stream — the paper's reuse
                              factor, now acceptance-scaled.
    """
    kv = kv_bytes_per_token
    if single_pass_kv:
        kv = kv / (spec_k + 1)
    verify = decode_step_time(
        n_params, batch * (spec_k + 1), kv, context_len,
        peak_flops, hbm_bw, b_weight, **kw)
    t_draft = 0.0
    if spec_k > 0 and draft_n_params > 0:
        d = decode_step_time(
            draft_n_params, batch, 0.0, 0, peak_flops, hbm_bw, b_weight, **kw)
        t_draft = spec_k * d["t_proc"]
    committed = batch * expected_committed(accept_rate, spec_k)
    t_tick = verify["t_proc"] + t_draft
    out = dict(verify)
    out.update(
        t_draft=t_draft,
        t_tick=t_tick,
        committed_per_tick=committed,
        tokens_per_s=committed / t_tick,
        tokens_per_weight_stream=committed / 1.0,  # one stream per tick
    )
    return out


def pages_for_context(context_len: int, page_size: int) -> int:
    """Pages a sequence of ``context_len`` tokens occupies in the paged KV
    cache — the allocation unit of serving/engine.py's paged mode."""
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    return -(-context_len // page_size)


def paged_pool_pages(
    n_sequences: int,
    mean_context_len: float,
    page_size: int,
    headroom: float = 1.1,
) -> int:
    """Pool capacity (pages, excluding the null page) to hold ``n_sequences``
    concurrent sequences of ``mean_context_len`` expected tokens.

    The contiguous cache reserves ``n * max_len`` tokens; the paged cache
    charges ``n * ceil(mean_ctx / page_size)`` pages, so for the same pool
    bytes the sustainable concurrent batch grows by ~``max_len / mean_ctx``
    — which is why the kv term of ``decode_n_opt`` should be charged at the
    *actual* mean context rather than max_len (docs/memory_model.md walks
    the numbers).  ``headroom`` covers fragmentation at page granularity
    (up to one page per sequence) and admission/completion skew.
    """
    per_seq = pages_for_context(int(math.ceil(mean_context_len)), page_size)
    return int(math.ceil(n_sequences * per_seq * headroom))


def decode_step_time(
    n_params: int,
    batch: int,
    kv_bytes_per_token: float = 0.0,
    context_len: int = 0,
    peak_flops: float = TPU_V5E_PEAK_FLOPS,
    hbm_bw: float = TPU_V5E_HBM_BW,
    b_weight: float = 2.0,
    n_chips: int = 1,
    q_prune: float = 0.0,
    q_overhead: float = 1.0,
    sparse_compute: bool = True,
    model_parallel: int = 1,
    kv_parallel: int | None = None,
) -> dict:
    """Two-term decode-step model for an LM with n_params weights.

    Returns dict with t_calc, t_mem, t_proc, bound ('compute'|'memory').
    KV-cache reads (batch * context * kv_bytes) ride on the memory term —
    they are the per-sample data the paper's model counts as negligible for
    FC nets but which matter at 32k+ contexts.  ``sparse_compute`` states
    whether the kernel skips pruned blocks (t_calc scales with 1 - q_prune)
    or executes them as masked zeros (t_calc stays dense).

    ``model_parallel`` shards the weight stream and the MACs over m chips
    of one tensor-parallel group serving ``batch`` sequences together;
    ``kv_parallel`` (default m) is the degree the KV leaves actually shard
    by — replicated caches (kv_parallel=1) pay the full kv read on every
    chip.  ``n_chips`` keeps its historical meaning of uniform scaling
    (data-parallel groups splitting a global batch) and composes with both.
    """
    m = max(1, int(model_parallel))
    kv_m = max(1, int(kv_parallel if kv_parallel is not None else m))
    eff_params = n_params * (1.0 - q_prune)
    flops = 2.0 * (eff_params if sparse_compute else n_params) * batch
    weight_bytes = eff_params * b_weight * q_overhead
    kv_read = batch * context_len * kv_bytes_per_token
    tc = flops / (peak_flops * n_chips * m)
    tm = (weight_bytes / m + kv_read / kv_m) / (hbm_bw * n_chips)
    return {
        "t_calc": tc,
        "t_mem": tm,
        "t_proc": max(tc, tm),
        "bound": "compute" if tc >= tm else "memory",
    }
