"""Benchmark runner — one module per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--smoke] [--json out.json] \
        [table2 table3 ... decode]

Prints ``name,us_per_call,derived`` CSV rows.  ``--smoke`` trims the
heavyweight benches (any whose ``main`` accepts a ``smoke`` parameter:
fewer sweep points, fewer timing iters); the purely analytic ones
(table2/3/4, fig7, nopt, roofline) are already cheap and run as-is.  The
CI fast lane runs ``--smoke`` over all benches so the perf scripts cannot
silently rot — a new engine- or kernel-driving bench should accept
``smoke`` or it will run full-size there.

``--json PATH`` additionally writes the rows as machine-readable JSON
(schema below, validated by ``tools/check_bench_schema.py`` and uploaded
as a CI artifact), so bench output can be diffed between perf PRs instead
of eyeballed from logs:

    {"schema_version": 1, "smoke": bool, "failed": [names],
     "rows": [{"bench": str, "name": str,
               "us_per_call": float | null, "derived": str}]}
"""

from __future__ import annotations

import inspect
import json
import sys
import traceback

from benchmarks import (
    autotune_search,
    common,
    continuous_serving,
    decode_microbench,
    degraded_serving,
    fig7_latency,
    kernel_bench,
    mixed_serving,
    nopt_validation,
    paged_serving,
    pruned_serving,
    roofline,
    sharded_serving,
    speculative_serving,
    table2_throughput,
    table3_energy,
    table4_accuracy,
)
from repro.launch.compile_cache import enable_compile_cache

ALL = {
    "table2": table2_throughput.main,
    "table3": table3_energy.main,
    "table4": table4_accuracy.main,
    "fig7": fig7_latency.main,
    "nopt": nopt_validation.main,
    "kernels": kernel_bench.main,
    "roofline": roofline.main,
    "pruned_serving": pruned_serving.main,
    "paged_serving": paged_serving.main,
    "sharded_serving": sharded_serving.main,
    "speculative_serving": speculative_serving.main,
    "degraded_serving": degraded_serving.main,
    "continuous_serving": continuous_serving.main,
    "mixed_serving": mixed_serving.main,
    "autotune": autotune_search.main,
    "decode": decode_microbench.main,
}

SCHEMA_VERSION = 1


def main() -> None:
    args = sys.argv[1:]
    smoke = "--smoke" in args
    args = [a for a in args if a != "--smoke"]
    json_path = None
    if "--json" in args:
        i = args.index("--json")
        try:
            json_path = args[i + 1]
        except IndexError:
            print("--json needs a path", file=sys.stderr)
            sys.exit(2)
        del args[i : i + 2]
    which = args or list(ALL)
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    rows = []
    for name in which:
        start = len(common.ROWS)
        try:
            fn = ALL[name]
            kwargs = {}
            if smoke and "smoke" in inspect.signature(fn).parameters:
                kwargs["smoke"] = True
            fn(**kwargs)
        except Exception:  # noqa: BLE001 — unknown names report like failures
            traceback.print_exc()
            failed.append(name)
        rows.extend(
            {"bench": name, "name": r[0],
             "us_per_call": None if r[1] is None else float(r[1]),
             "derived": r[2]}
            for r in common.ROWS[start:]
        )
    if json_path:
        doc = {"schema_version": SCHEMA_VERSION, "smoke": smoke,
               "failed": failed, "rows": rows}
        with open(json_path, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"# wrote {len(rows)} rows to {json_path}", file=sys.stderr)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
