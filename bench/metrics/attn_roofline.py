"""Roofline share of the paged decode attention kernel
(``_paged_decode_kernel``): least time of the int8 K/V pages and scales the
live sequences need at the device's peaks over the kernel's summed device
time in the trace, %."""

from bench import counts, observe

KERNEL = "_paged_decode_kernel"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    spent, _ = run.trace.kernel(KERNEL)
    if spent <= 0:
        return None
    calls = [run.work.attn_call(step) for step in observe.decode_contexts(run)
             if step] * run.work.attn_layers
    least, bound = counts.least_time(calls, run.peaks)
    return {"value": 100.0 * least / spent, "bound": bound}
