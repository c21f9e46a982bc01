"""Knee sweep of a cell (run on the chip, once, when the cell is defined).

    python3 -m bench.sweep --workload <cell> --seed 1 --rates 2,4,6 --seconds 40

One process packs the weights once; each offered rate then gets a fresh
engine, seated as the cell seats it, the cell's traffic at that rate for
``--seconds``, and one JSON line: requests completed per second in the
second half of the window and the backlog (requests due and not yet
admitted) at its middle and its end.  The knee is the highest rate whose
backlog does not grow; above it the backlog grows at the offered rate less
the knee, so each rate over the knee also estimates it.
"""

from __future__ import annotations

import argparse
import json

from bench import run, spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    bench = spec.benchmark()
    base = spec.traffic(spec.cell(bench, args.workload)["traffic"])
    built = None
    for rate in (float(x) for x in args.rates.split(",")):
        mix = {**base, "rate_rps": rate}
        if args.rehearse:
            mix["smoke"] = {**base["smoke"], "rate_rps": rate}
        s = run.Session(args.workload, args.seed, args.seconds,
                        rehearse=args.rehearse, built=built, mix=mix)
        built = s.built
        s.warm()
        s.seat()
        s.window()
        m = s.metrics("end_to_end")
        mid = s.t0 + s.seconds / 2

        def backlog(t):
            return sum(1 for r in s.records.values() if r.due is not None
                       and r.due <= t and (r.admit_t is None or r.admit_t > t))

        late = [r for r in s.records.values() if r.tokens
                and r.req.terminal and mid <= r.tokens[-1] <= s.t1]
        growth = (backlog(s.t1) - backlog(mid)) / (s.t1 - mid)
        print(json.dumps({
            "workload": args.workload, "rate_rps": rate,
            "completed_rps_2nd_half": len(late) / (s.t1 - mid),
            "backlog_mid": backlog(mid), "backlog_end": backlog(s.t1),
            "backlog_growth_rps": growth, "knee_if_over": rate - growth,
            "out_tok_s": spec.reader("out_tok_s")(s.run_record()),
            "batch_occupancy": spec.reader("batch_occupancy")(s.run_record()),
            "setup_s": m.get("setup_s", {}).get("value")}), flush=True)
        s.engine = None
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
