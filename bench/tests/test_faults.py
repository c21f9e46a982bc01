"""The check catches a broken timed path, and the control fails the limit
(at smoke size on the CPU; on the chip, at the cell's size: PERF.md)."""

import argparse

import numpy as np
import pytest

from bench import check, control, run, spec, sut

BENCH = spec.benchmark()
CELLS = {}
for c in BENCH["workloads"]:
    CELLS.setdefault(c["config"], c["name"])


def _args(workload, seed):
    return argparse.Namespace(workload=workload, seed=seed, seconds=2.0, trace=0,
                              keep_trace=None, rehearse=True)


def _altered_token(P, monkeypatch):
    """The decode step's sampled token altered, one in five."""
    real = P.ServingEngine._sample
    calls = [0]

    def altered(self, logits, temperature):
        tok = real(self, logits, temperature)
        calls[0] += 1
        return (tok + 1) % logits.shape[-1] if calls[0] % 5 == 0 else tok

    monkeypatch.setattr(P.ServingEngine, "_sample", altered)


def _wrap_decode(monkeypatch, wrap):
    """Replace each engine's batched decode step by ``wrap(real)``; the
    batch-1 chunk steps of prefill stay as they are."""
    real_new = sut.Built.new_engine

    def new_engine(self, mix):
        engine = real_new(self, mix)
        real = engine._decode
        broken = wrap(real)
        engine._decode = lambda params, cache, tokens, pos, poison: (
            real if tokens.shape[0] == 1 else broken)(
                params, cache, tokens, pos, poison)
        return engine

    monkeypatch.setattr(sut.Built, "new_engine", new_engine)


def _state_unchanged(P, monkeypatch):
    """The decode step returns the cache it was given: no token's K/V is
    written."""
    import jax
    import jax.numpy as jnp

    def wrap(real):
        def stale(params, cache, tokens, pos, poison):
            kept = jax.tree.map(jnp.copy, cache)
            logits, ok, _ = real(params, cache, tokens, pos, poison)
            return logits, ok, kept
        return stale

    _wrap_decode(monkeypatch, wrap)


def _half_batch(P, monkeypatch):
    """The decode step computes the first half of the batch only: the
    other rows get the logits of a row of the first half."""
    import jax.numpy as jnp

    def wrap(real):
        def half(params, cache, tokens, pos, poison):
            logits, ok, cache = real(params, cache, tokens, pos, poison)
            h = logits.shape[0] // 2
            rows = jnp.arange(logits.shape[0]) % max(h, 1)
            return logits[rows], ok, cache
        return half

    _wrap_decode(monkeypatch, wrap)


FAULTS = {"altered_token": _altered_token, "state_unchanged": _state_unchanged,
          "half_batch": _half_batch}


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("workload", list(CELLS.values()))
def test_broken_timed_path_is_caught(workload, fault, monkeypatch):
    """A run whose timed path is broken underneath reads ``correct`` false:
    a token altered where the engine produces it, a decode step that
    returns its state unchanged, half of the batch left out."""
    FAULTS[fault](sut.program(), monkeypatch)
    out = run.run_once(_args(workload, 11))
    assert out["check"], "nothing was compared"
    assert out["correct"] is False
    assert any(n["value"] > n["limit"] for n in out["check"].values())


@pytest.mark.parametrize("workload", list(CELLS.values()))
def test_control_fails_the_limit(workload):
    """The reference at float8 activations, in the program's place, reads
    above the limit; the program reads within it."""
    import jax.numpy as jnp

    s = run.Session(workload, 21, 2.0, rehearse=True)
    s.warm()
    s.seat()
    s.window()
    r = control.readings(s, jnp.float8_e4m3fn)
    assert r["sampled_tokens"] > 0 and r["limits"]
    assert r["program_correct"] is True and r["control_correct"] is False


def test_gap_is_zero_for_the_reference_best():
    rows = np.array([[0.0, 2.0, 1.0], [3.0, -1.0, 2.5]])
    assert check.gaps(rows, np.array([1, 0])).tolist() == [0.0, 0.0]
    assert check.gaps(rows, np.array([2, 2])).tolist() == [1.0, 0.5]
