"""Readings for the limits of ``check.NUMBERS`` (run on the chip).

    python3 -m bench.control --workload <cell> --seeds 1,2,3 --seconds 30

For each seed, in one process: a whole run of the cell (set-up, window),
then on the run's check sample the program's readings of every number in
``check.NUMBERS`` (gaps of the served tokens below the reference's best)
and the control's: the reference computed with float8 (e4m3) activations
put in the program's place, read from the gaps of the tokens the control
puts first at each of the same positions.  One JSON line per seed, with
``program_correct`` and ``control_correct`` judged against the limits of
the traffic file.  A limit lies between the largest program reading and
the smallest control reading (PERF.md).
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np

from bench import check, run, sut


def readings(s, control_dtype, dump: str | None = None, wider: int = 0) -> dict:
    """Program and control readings on the finished sample of session ``s``.
    With ``dump``, the gaps and the reference's margins of a sample of
    ``wider`` requests (the check's sample is its first ``requests``) are
    written to ``<dump>/<seed>.npz`` for a later look."""
    sz = sut.sizes(s.conf, s.rehearse)
    c = s.mix["check"]
    picked = check.sample(s.finished(), s.seed, max(c["requests"], wider))
    if not picked:
        return {"seed": s.seed, "sampled_tokens": 0}
    seqs, want, served = check.sequences(picked)
    s.release()
    ref = check.reference_logits(sz["model"], sz["plan"], s.conf["reference"],
                                 s.built.shapes, s.seed, seqs, want)
    ctl = check.reference_logits(sz["model"], sz["plan"], s.conf["reference"],
                                 s.built.shapes, s.seed, seqs, want,
                                 act_dtype=control_dtype)
    if dump:
        os.makedirs(dump, exist_ok=True)
        np.savez_compressed(os.path.join(dump, f"{s.seed}.npz"), **{
            f"{k}{i}": v for i, (r, t, x) in enumerate(zip(ref, served, ctl))
            for k, v in (("served", t), ("ref_best", r.argmax(-1)),
                         ("ctl_best", x.argmax(-1)), ("gap", check.gaps(r, t)),
                         ("ctl_gap", check.gaps(r, x.argmax(-1))),
                         ("margin", np.diff(np.sort(r, axis=-1)[:, -2:], axis=-1)[:, 0]),
                         ("prompt", seqs[i][:len(seqs[i]) - len(t) + 1]))})
    n = c["requests"]
    ref, served, ctl = ref[:n], served[:n], ctl[:n]
    prog = np.concatenate([check.gaps(r, t) for r, t in zip(ref, served)])
    ctrl = np.concatenate([check.gaps(r, x.argmax(-1)) for r, x in zip(ref, ctl)])
    every = {name: 1.0 for name in check.NUMBERS}
    return {"seed": s.seed, "sampled_tokens": int(prog.size),
            **{f"program.{k}": v["value"]
               for k, v in check.numbers(every, prog).items()},
            **{f"control.{k}": v["value"]
               for k, v in check.numbers(every, ctrl).items()},
            "limits": {k: c[k] for k in check.NUMBERS if k in c},
            "program_correct": check.judge(check.numbers(c, prog), 0),
            "control_correct": check.judge(check.numbers(c, ctrl), 0),
            "control_mismatch": float((ctrl > 0).mean()),
            **diagnosis(ref, served, prog, ctrl)}


def diagnosis(ref, served, prog, ctrl) -> dict:
    """What sets the program's tail on this seed: how many tokens differ
    from the reference's best, the widest gaps, how many of them are
    repeats of one position's value, the reference's margins between its
    best and second logit, and each sampled request's length, distinct
    tokens and widest gap."""
    top = np.sort(prog)[::-1][:max(1, int(np.ceil(0.02 * prog.size)))]
    margins = np.concatenate([np.diff(np.sort(r, axis=-1)[:, -2:], axis=-1)[:, 0]
                              for r in ref])
    tails = {f"{side}.{k}": float(fn(g)) for side, g in (("program", prog),
                                                         ("control", ctrl))
             for k, fn in (("mean_gap", np.mean),
                           ("logit_gap_p95", lambda g: np.percentile(g, 95)),
                           ("logit_gap_p999", lambda g: np.percentile(g, 99.9)))}
    return {**tails, "program_mismatch": float((prog > 0).mean()),
            "program_top_gaps": [round(float(x), 5) for x in top[:24]],
            "program_top_distinct": int(np.unique(np.round(top, 5)).size),
            "top_gap_count": int(top.size),
            "ref_margin_q": [float(np.quantile(margins, q))
                             for q in (0.01, 0.05, 0.25, 0.5)],
            "ref_row_std_median": float(np.median(np.concatenate(
                [r.std(axis=-1) for r in ref]))),
            "requests": [[int(t.size), int(np.unique(t).size),
                          float(check.gaps(r, t).max()),
                          int((check.gaps(r, t) > 0).sum())]
                         for r, t in zip(ref, served)]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--dump", default=None, metavar="DIR",
                    help="write each seed's gaps and margins to DIR/<seed>.npz")
    ap.add_argument("--wider", type=int, default=0, metavar="N",
                    help="with --dump: dump a sample of N requests")
    args = ap.parse_args(argv)
    import jax.numpy as jnp

    for seed in (int(x) for x in args.seeds.split(",")):
        s = run.Session(args.workload, seed, args.seconds, rehearse=args.rehearse)
        s.warm()
        s.seat()
        s.window()
        print(json.dumps({"workload": args.workload,
                          **readings(s, jnp.float8_e4m3fn, args.dump,
                                    args.wider)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
