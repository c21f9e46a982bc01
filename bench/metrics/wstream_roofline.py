"""Roofline share of the weight-stream kernels (``_bsmm_mc_kernel`` walk,
``_fused_kernel`` gate+up): the least time their needed operations and
bytes take at the device's peaks (``stream_calls`` of the block's work
module) over the summed device time of those kernels in the trace, %."""

from bench import counts, observe

KERNELS = ("_bsmm_mc_kernel", "_fused_kernel")


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    spent = sum(run.trace.kernel(k)[0] for k in KERNELS)
    if spent <= 0:
        return None
    calls = []
    for step in observe.decode_contexts(run):
        if step:
            calls += run.work.stream_calls(len(step))
    chunks = run.stats1["prefill_chunks"] - run.stats0["prefill_chunks"]
    calls += run.work.stream_calls(run.prefill_chunk) * chunks
    least, bound = counts.least_time(calls, run.peaks)
    return {"value": 100.0 * least / spent, "bound": bound}
