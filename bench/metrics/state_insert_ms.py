"""Mean ``engine.state_insert`` of the window: a finished prefill's
recurrent state written into its slot in place, ms (``bench.spans``).
Nothing to read for a program without the span."""

from bench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.mean_ms(run.trace, run.trace.program, "engine.state_insert")
