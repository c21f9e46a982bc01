"""Blocking device-to-host reads per decode step in the window (engine
counters ``host_syncs`` / ``decode_steps``, as deltas); nothing to read for
a program without the ``host_syncs`` counter."""


def read(run):
    if "host_syncs" not in run.stats0:
        return None
    steps = run.stats1["decode_steps"] - run.stats0["decode_steps"]
    syncs = run.stats1["host_syncs"] - run.stats0["host_syncs"]
    return syncs / steps if steps else None
