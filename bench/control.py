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

import numpy as np

from bench import check, run, sut


def readings(s, control_dtype) -> dict:
    """Program and control readings on the finished sample of session ``s``."""
    sz = sut.sizes(s.conf, s.rehearse)
    c = s.mix["check"]
    picked = check.sample(s.finished(), s.seed, c["requests"])
    if not picked:
        return {"seed": s.seed, "sampled_tokens": 0}
    seqs, want, served = check.sequences(picked)
    s.release()
    ref = check.reference_logits(sz["model"], sz["plan"], s.conf["reference"],
                                 s.built.shapes, s.seed, seqs, want)
    ctl = check.reference_logits(sz["model"], sz["plan"], s.conf["reference"],
                                 s.built.shapes, s.seed, seqs, want,
                                 act_dtype=control_dtype)
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
            "control_mismatch": float((ctrl > 0).mean())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax.numpy as jnp

    for seed in (int(x) for x in args.seeds.split(",")):
        s = run.Session(args.workload, seed, args.seconds, rehearse=args.rehearse)
        s.warm()
        s.seat()
        s.window()
        print(json.dumps({"workload": args.workload,
                          **readings(s, jnp.float8_e4m3fn)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
