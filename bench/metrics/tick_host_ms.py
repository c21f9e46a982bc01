"""Host time of a decode tick: ``engine.step`` less the ``engine.device_wait``
inside it, mean per decode tick, ms (``bench.spans.tick``).  Extras: the
tick's ms in each program span (``by_span``) and the window's device-idle
seconds by the innermost span over them (``idle_by_span``)."""

from bench import spans


def read(run):
    if run.trace is None:
        return None
    tick = spans.tick(run.trace, run.trace.program)
    if tick is None:
        return None
    return {"value": tick["tick_host_ms"], "by_span": tick["by_span"],
            "idle_by_span": tick["idle_by_span"]}
