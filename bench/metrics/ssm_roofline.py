"""Roofline share of the Mamba-2 decode state update (``_ssm_decode_kernel``):
least time of the float32 state and inputs the live rows need (``ssm_call``
of the block's work module, one call per Mamba layer and decode step) at the
device's peaks over the kernel's summed device time in the trace, %."""

from bench import counts, observe

KERNEL = "_ssm_decode_kernel"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    ssm_call = getattr(run.work, "ssm_call", None)
    if ssm_call is None:
        return None
    spent, _ = run.trace.kernel(KERNEL)
    if spent <= 0:
        return None
    calls = [ssm_call(len(step)) for step in observe.decode_contexts(run)
             if step] * run.work.ssm_layers
    least, bound = counts.least_time(calls, run.peaks)
    return {"value": 100.0 * least / spent, "bound": bound}
