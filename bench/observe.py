"""What the harness observed in a run's window, in the forms the metric
readers (``bench/metrics/*.py``) take.  Times are the harness's monotonic
clock; a request's timeline is its ``Record`` (``bench/run.py``)."""

from __future__ import annotations

import bisect


def window_tokens(run) -> int:
    return sum(1 for r in run.records.values() for t in r.tokens
               if run.t0 <= t <= run.t1)


def decode_contexts(run) -> list:
    """Per engine step of the window, the contexts (keys attended, the new
    token's included) of the sequences it decoded."""
    starts = [s for s, _, _ in run.steps]
    per = [[] for _ in run.steps]
    for r in run.records.values():
        for j, t in enumerate(r.tokens):
            if j == 0 or not run.t0 <= t <= run.t1:
                continue  # a request's first token comes from prefill
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0:
                per[i].append(r.prompt_len + j)
    return per


def prefilled_prompts(run) -> list:
    """Prompt lengths of requests whose prefill finished in the window."""
    return [r.prompt_len for r in run.records.values()
            if r.tokens and run.t0 <= r.tokens[0] <= run.t1]


def decode_program(run):
    """Executions (seconds) of the decode step's program in the trace: the
    program of the engine's step function that ran once per decode step."""
    groups = run.trace.programs("_decode_impl")
    if not groups:
        return None
    n = sum(1 for _, _, k in run.steps if k > 0)
    return min(groups.values(), key=lambda v: abs(len(v) - n))
