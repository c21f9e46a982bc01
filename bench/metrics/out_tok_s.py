"""Output tokens committed in the window over the window's seconds."""

from bench import observe


def read(run):
    return observe.window_tokens(run) / run.seconds
