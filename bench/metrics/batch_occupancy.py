"""Decode tokens per decode step over the engine's slots, in the window
(engine counters ``decode_tokens`` / ``decode_steps``, as deltas), %."""


def read(run):
    steps = run.stats1["decode_steps"] - run.stats0["decode_steps"]
    toks = run.stats1["decode_tokens"] - run.stats0["decode_tokens"]
    return 100.0 * toks / (steps * run.max_batch) if steps else None
