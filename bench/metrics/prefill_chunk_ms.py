"""Mean ``engine.prefill_chunk`` of the window, dispatch to the read of its
finite flag, ms (``bench.spans.tick``)."""

from bench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.mean_ms(run.trace, run.trace.program, "engine.prefill_chunk")
