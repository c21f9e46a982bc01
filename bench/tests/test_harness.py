"""The harness end to end on the CPU at smoke size (``--rehearse``): one run
per configuration, the result line's keys, and a broken timed path that the
check must catch."""

import json
import os
import subprocess
import sys

import pytest

from bench import spec

ROOT = spec.ROOT
BENCH = spec.benchmark()
KEYS = ["correct", "attempted", "failed", "metrics", "device", "check"]


def _cell_per_config():
    seen, out = set(), []
    for c in BENCH["workloads"]:
        if c["config"] not in seen:
            seen.add(c["config"])
            out.append(c["name"])
    return out


def _rehearse(workload, seed, trace=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", workload, "--seed",
         str(seed), "--seconds", "2", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


@pytest.mark.parametrize("workload", _cell_per_config())
def test_rehearsal_run_per_configuration(workload):
    out, err = _rehearse(workload, 2**33 + 17)
    assert list(out) == KEYS
    assert out["correct"] is True, err[-2000:]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    e2e = {m["name"] for m in spec.metrics_for(BENCH, workload, "end_to_end")}
    assert set(out["metrics"]) == e2e  # every end-to-end metric is host-timed
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    names = set(spec.traffic(spec.cell(BENCH, workload)["traffic"])["smoke"]["check"])
    assert out["check"] and set(out["check"]) <= names
    assert all(set(n) == {"value", "limit"} for n in out["check"].values())
    last = err.strip().splitlines()[-1].split()
    assert last[:2] == ["[bench]", "check"] and last[2] in out["check"]
    assert "0 compilations inside it" in err


def test_rehearsal_traced_run_reports_per_layer_metrics():
    workload = BENCH["workloads"][0]["name"]
    out, _ = _rehearse(workload, 5, trace=1)
    assert list(out) == KEYS
    host = {m["name"] for m in spec.metrics_for(BENCH, workload, "per_layer")
            if m["source"] != "device_trace" and m["name"] not in
            ("step_mfu", "peak_hbm_gb")}
    assert host <= set(out["metrics"])


def test_no_accelerator_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_checkout_without_the_program_gives_no_result(tmp_path):
    """Only BENCHMARK.json and bench/: the run fails and prints nothing."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0", "--rehearse"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
