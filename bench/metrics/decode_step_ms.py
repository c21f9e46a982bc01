"""Mean device time of one execution of the decode step's program, ms."""

from bench import observe


def read(run):
    if run.trace is None:
        return None
    v = observe.decode_program(run)
    return 1e3 * sum(v) / len(v) if v else None
