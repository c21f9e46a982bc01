"""Peak device memory of the run (``memory_stats()["peak_bytes_in_use"]``
read after the window; set-up's packing included), GB."""


def read(run):
    return run.memory_peak / 1e9 if run.peaks is not None else None
