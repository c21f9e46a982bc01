"""The one traffic generator: a traffic file's parameters plus ``--seed``
give the requests of a run.

Arrivals are an open loop (requests are due on a schedule whether or not the
server keeps up), as ``repro.serving.loadgen.poisson_trace`` makes them:
exponential gaps at ``rate_rps``, prompt tokens uniform over the vocabulary.
Unlike ``poisson_trace`` the lengths and gaps are stratified (the
quantiles of the stated laws, in an order drawn once) and the schedule does
not follow the seed: every seed offers the same requests at the same times,
and draws only their tokens.  So seed-to-seed spread measures the server,
not the draw.

Traffic file keys:

* ``rate_rps``: mean arrival rate in requests per second;
* ``prompt`` / ``output``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal length law, clipped to ``[min, max]``;
* ``seat``: requests seated before the window (0 = none).  A seated request
  stands for one caught in mid-generation at steady state: its total output
  length is drawn length-biased, it has produced a uniform share of it, and
  those tokens are part of its prompt (built by prefill at set-up);
* ``length_levels``: how many distinct prompt lengths the arrivals have,
  and how many distinct contexts the seated requests have (0 = no limit).
  Arrival prompts take the law's quantiles at the midpoints of that many
  strata, each stratum an equal share of the arrivals, whatever the rate;
  seated contexts, sorted, are cut into that many groups of near-equal size
  and each takes its group's median.  The law keeps its shape while the
  program, which compiles its write of a prefill into pages once per
  length, has a bounded set of lengths to warm up;
* ``max_batch``, ``prefill_budget``, ``max_len``: the engine's knobs for
  this traffic;
* ``check``: how the run's answers are checked (``bench/check.py``).
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Optional

import numpy as np

_STD = statistics.NormalDist()


@dataclasses.dataclass
class Req:
    uid: int
    prompt: np.ndarray  # int32 token ids
    max_new: int
    due: Optional[float] = None  # seconds after the window opens; None = seated


def _lengths(law: dict, n: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    z = np.asarray([_STD.inv_cdf(float(x)) for x in q])
    raw = law["median"] * np.exp(law["sigma"] * z)
    return np.clip(np.round(raw), law["min"], law["max"]).astype(np.int64)


def _levels(lengths: np.ndarray, levels: int) -> np.ndarray:
    """Each length replaced by the median of its group, the sorted lengths
    cut into ``levels`` groups of near-equal size (0: unchanged)."""
    if not levels or levels >= len(lengths):
        return lengths
    order = np.argsort(lengths, kind="stable")
    out = np.empty_like(lengths)
    for group in np.array_split(order, levels):
        out[group] = int(np.median(lengths[group]))
    return out


def _length_biased(law: dict, n: int, grid: int = 4096) -> np.ndarray:
    """``n`` stratified draws of the law weighted by length: the total
    output of a request found in flight at a random instant."""
    base = _lengths(law, grid).astype(np.float64)
    cdf = np.cumsum(base) / base.sum()
    q = (np.arange(n) + 0.5) / n
    return base[np.searchsorted(cdf, q)].astype(np.int64)


def n_arrivals(mix: dict, seconds: float) -> int:
    """Arrivals generated for a window: a fifth more than the rate asks,
    so the schedule never runs dry inside it."""
    return int(math.ceil(mix["rate_rps"] * seconds * 1.2)) + 8


def generate(mix: dict, vocab: int, seed: int, seconds: float) -> tuple:
    """(seated, arrivals): lists of ``Req``; arrivals ordered by ``due``.

    The schedule (each arrival's time, prompt length and output length, and
    each seated request's context and remaining output) is one fixed draw,
    the same for every seed: every seed offers the same work at the same
    times.  The seed draws the prompt tokens."""
    fixed, fixed_seat = np.random.default_rng(0), np.random.default_rng(1)
    rng = np.random.default_rng(int(seed))
    n = n_arrivals(mix, seconds)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / mix["rate_rps"]
    levels = int(mix.get("length_levels", 0))
    if levels:  # the levels' quantiles, in strata of near-equal size
        prompts = _lengths(mix["prompt"], levels)[np.arange(n) * levels // n]
    else:
        prompts = _lengths(mix["prompt"], n)
    prompts = fixed.permutation(prompts)
    outputs = fixed.permutation(_lengths(mix["output"], n))
    due = np.cumsum(fixed.permutation(gaps))
    n_seat = int(mix.get("seat", 0))
    pairs = []
    if n_seat:
        # a draw of its own, so the seated contexts do not follow the rate
        total = fixed_seat.permutation(_length_biased(mix["output"], n_seat))
        share = fixed_seat.permutation((np.arange(n_seat) + 0.5) / n_seat)
        plen = fixed_seat.permutation(_lengths(mix["prompt"], n_seat))
        done = np.floor(share * total).astype(np.int64)
        ctx = _levels(plen + done, levels)
        left = np.minimum(total - done, mix["max_len"] - ctx)
        pairs = [(int(c), int(x)) for c, x in zip(ctx, left)]
    seated = [Req(uid, rng.integers(0, vocab, ctx, dtype=np.int32), left)
              for uid, (ctx, left) in enumerate(pairs)]
    arrivals = [Req(len(seated) + i,
                    rng.integers(0, vocab, int(p), dtype=np.int32), int(m),
                    float(d))
                for i, (d, p, m) in enumerate(zip(due, prompts, outputs))]
    return seated, arrivals
