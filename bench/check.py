"""Decides ``correct``: the served tokens against the plain reference.

After the window the run takes a sample, drawn from the seed, of
``check.requests`` requests that finished in it, the one with the most
served tokens always among them.  The reference (``reference/<name>.py`` of the configuration)
runs once over each prompt followed by its served tokens.  Each served token
is greedy, so the reference's logit for it should be the reference's best
at that position up to the served path's rounding.  The gap of a token is
how far its reference logit lies below the reference's best logit there.
The numbers compared are those the traffic file's ``check`` section names,
each with its limit: ``max_logit_gap``, the widest gap over all sampled
tokens, and ``logit_gap_p99``, the 99th percentile of the gaps (for a model
whose logits hold many near ties, where one flipped near tie sets the
widest gap of the program and of the control alike; PERF.md).

The control (``bench/control.py``) puts the reference computed with
float8 activations in the program's place and reads the same gap for the
token the control would have chosen.
"""

from __future__ import annotations

import numpy as np

from bench import spec, sut, weights

NUMBERS = {"max_logit_gap": lambda g: float(g.max()),
           "logit_gap_p99": lambda g: float(np.percentile(g, 99))}


def sample(done: list, seed: int, requests: int) -> list:
    """Finished records to compare: the longest, then others in an order
    drawn from the seed, ``requests`` in all."""
    if not done:
        return []
    done = sorted(done, key=lambda r: r.req.uid)
    longest = max(done, key=lambda r: len(r.req.output))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(int(seed) + 1).permutation(len(rest))
    return [longest] + [rest[i] for i in order[:requests - 1]]


def sequences(picked: list) -> tuple:
    """(token sequences, wanted positions, served tokens) per request."""
    seqs, want, served = [], [], []
    for r in picked:
        prompt = np.asarray(r.req.prompt, np.int32)
        out = np.asarray(r.req.output, np.int32)
        seqs.append(np.concatenate([prompt, out[:-1]]))
        want.append(len(prompt) - 1 + np.arange(len(out)))
        served.append(out)
    return seqs, want, served


def gaps(ref_rows: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per row: best reference logit minus the reference logit of ``tokens``."""
    return ref_rows.max(axis=-1) - ref_rows[np.arange(len(tokens)), tokens]


def reference_logits(model: dict, plan: dict, reference: str, shapes, seed: int,
                     seqs, want, act_dtype=None):
    """The configuration's reference over ``seqs``, on weights rebuilt from
    the seed by the benchmark (nothing the program made)."""
    params = weights.make_params(shapes, seed, plan["block"])
    return spec.reference(reference).logits(model, plan, params, seqs, want,
                                            act_dtype)


def check(conf: dict, mix: dict, shapes, seed: int, done: list,
          smoke: bool = False) -> dict:
    """{name: {"value", "limit"}} for the run, or {} with nothing to compare."""
    c = mix["check"]
    picked = sample(done, seed, c["requests"])
    if not picked:
        return {}
    sz = sut.sizes(conf, smoke)
    seqs, want, served = sequences(picked)
    rows = reference_logits(sz["model"], sz["plan"], conf["reference"], shapes,
                            seed, seqs, want)
    return numbers(c, np.concatenate([gaps(r, t) for r, t in zip(rows, served)]))


def numbers(c: dict, g: np.ndarray) -> dict:
    """{name: {"value", "limit"}} of every number that the ``check``
    section ``c`` names, read from the gaps ``g`` of all sampled tokens."""
    return {name: {"value": fn(g), "limit": c[name]}
            for name, fn in NUMBERS.items() if name in c}


def judge(numbers: dict, failed: int) -> bool:
    """``correct``: something was compared, no request failed, and every
    number lies within its limit."""
    return bool(numbers) and failed == 0 and all(
        n["value"] <= n["limit"] for n in numbers.values())
