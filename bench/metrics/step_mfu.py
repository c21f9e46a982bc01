"""Operations the window's tokens need (the block's work module,
``bench/work/<name>.py``: surviving weights, routed experts only, attention
over the real context, the LM head where a logit is used) over the window's
seconds times the device's bf16 peak, %."""

from bench import observe


def read(run):
    if run.peaks is None:
        return None
    w = run.work
    flops = sum(w.decode_flops(c) for step in observe.decode_contexts(run)
                for c in step)
    flops += sum(w.prefill_flops(p) for p in observe.prefilled_prompts(run))
    return 100.0 * flops / (run.seconds * run.peaks["bf16_flops"])
