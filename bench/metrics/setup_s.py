"""Set-up seconds: process start to the window's start (weights, packing,
engine, compilation or cache load, warm-up, seating)."""


def read(run):
    return run.setup_s
