"""Copy of the decode step's result to the host: ``engine.fetch`` directly
under ``engine.step``, mean ms per decode tick (``bench.spans.tick``)."""

from bench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.per_tick_ms(run.trace, run.trace.program, "engine.fetch",
                             parent="engine.step")
