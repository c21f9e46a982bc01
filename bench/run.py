"""Run one cell of ``BENCHMARK.json`` once and print one JSON line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the engine the way ``launch/serve.py`` does (weights from the
seed, the program's packing, ``ServingEngine``), warms every program the
window uses, and seats the traffic's steady-state batch.  The window is a
wall-clock open loop: requests are submitted when due, and
``ServingEngine.step`` runs in between.  After the window the run's answers
are checked against the plain reference (``bench/check.py``).  With
``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the line carries the
per-layer metrics.

The last line of standard output is the result; the last lines of standard
error are the numbers compared and their limits.  A run on a host without
the accelerator the cell asks for exits non-zero and prints no result,
unless ``--rehearse`` is given (tests only: the CPU at smoke size, no device
metric).
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time

from bench import spec

CACHE_DIR = spec.ROOT / ".jax_cache"
clock = time.monotonic


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Record:
    """What the harness saw of one request."""

    __slots__ = ("req", "due", "submit_t", "admit_t", "tokens", "prompt_len")

    def __init__(self, req, prompt_len):
        self.req = req
        self.due = None  # absolute scheduled arrival; None for a seated request
        self.submit_t = None
        self.admit_t = None  # first seen out of the engine's queue
        self.tokens = []  # clock time of each committed token
        self.prompt_len = prompt_len


class Run:
    """Everything a metric reader may read (``bench/metrics/*.py``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def start_jax(chips: int, rehearse: bool):
    """Import JAX with the checkout's compile cache; the devices, or exit
    when the cell's accelerator is missing."""
    # the compile cache lives in the checkout, at a path that never moves
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    if rehearse:  # CPU compiles are quick; keep the checkout's cache for the chip
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform == "cpu" and not rehearse:
        raise SystemExit("no accelerator: JAX found only the CPU")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return jax, devs


class Session:
    """One cell at one seed: set-up, window, check."""

    def __init__(self, workload: str, seed: int, seconds: float,
                 rehearse: bool = False, built=None, mix: dict | None = None):
        self.t_start = clock()
        self.bench = spec.benchmark()
        self.cell = spec.cell(self.bench, workload)
        self.conf = spec.configuration(self.bench, self.cell["config"])
        mix = mix or spec.traffic(self.cell["traffic"])
        self.mix = {**mix, **mix.get("smoke", {})} if rehearse else mix
        self.seed, self.seconds, self.rehearse = seed, seconds, rehearse
        self.jax, self.devs = start_jax(int(self.cell["chips"]), rehearse)
        from bench import peaks, sut, traffic

        self.peaks = None if rehearse else peaks.for_kind(self.devs[0].device_kind)
        self.compiles = []
        self._window_open = False
        self.phase = "build"
        self.phases = collections.defaultdict(collections.Counter)
        self.jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self.jax.monitoring.register_event_listener(self._on_count)
        self.built = built or sut.build(self.conf, seed, smoke=rehearse)
        self._log_phase("build")
        log("weights and packing: " + ", ".join(
            f"{k} {v:.3f} s" for k, v in self.built.times.items()))
        if self.built.unchecked:
            log(f"model keys the program has no field for, left to the "
                f"reference: {', '.join(self.built.unchecked)}")
        self.engine = self.built.new_engine(self.mix)
        self.P, self.cfg = self.built.P, self.built.cfg
        self.seated, self.arrivals = traffic.generate(
            self.mix, self.cfg.vocab, seed, seconds)
        self.records = {}
        self.times = dict(self.built.times)

    def _on_event(self, event, duration, **kw):
        if self._window_open and event.startswith("/jax/core/compile/"):
            self.compiles.append((event.rsplit("/", 1)[-1], kw.get("fun_name", "?")))
        if event.startswith("/jax/core/compile/"):
            name = event.rsplit("/", 1)[-1]
            self.phases[self.phase][name] += duration
            if name == "backend_compile_duration":
                self.phases[self.phase]["programs"] += 1

    def _on_count(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.phases[self.phase]["cache_hits"] += 1

    def _log_phase(self, name: str, nxt: str | None = None):
        """Log what set-up phase ``name`` compiled; then count for ``nxt``."""
        c = self.phases[name]
        log(f"{name}: {c['programs']:.0f} programs compiled or loaded "
            f"({c['cache_hits']:.0f} from the cache) in "
            f"{c['backend_compile_duration']:.1f} s, traced and lowered in "
            f"{c['jaxpr_trace_duration'] + c['jaxpr_to_mlir_module_duration']:.1f} s")
        self.phase = nxt or name

    def _on_token(self, req, tok):
        self.records[req.uid].tokens.append(clock())

    def make(self, r):
        req = self.P.Request(uid=r.uid, prompt=r.prompt, max_new_tokens=r.max_new,
                             on_token=self._on_token)
        self.records[r.uid] = Record(req, len(r.prompt))
        return req

    def _drive(self, reqs, limit_s: float, until):
        """Submit ``reqs`` and step under the wide set-up prefill budget
        until ``until(req)`` holds for all of them."""
        engine = self.engine
        budget = engine.prefill_budget
        engine.prefill_budget = max(budget, int(self.mix.get("seat_budget", budget)))
        for req in reqs:
            rec = self.records[req.uid]
            rec.submit_t = rec.admit_t = clock()
            engine.submit(req)
        t = shown = clock()
        while not all(until(r) for r in reqs):
            if clock() - t > limit_s:
                raise RuntimeError("set-up did not finish in time")
            engine.step()
            if clock() - shown > 60:
                shown = clock()
                log(f"set-up: {sum(map(until, reqs))} of {len(reqs)} "
                    f"requests after {shown - t:.0f} s")
        engine.prefill_budget = budget

    def warm(self):
        """Run every program the window runs once.  One request per distinct
        prompt length of the traffic, one token each (the engine compiles
        the scatter of a prefill into pages per prompt length), and one
        request that decodes."""
        from bench import traffic

        t = clock()
        self.phase = "warm"
        tok = self.arrivals[0].prompt[:1]
        lengths = sorted({len(r.prompt) for r in self.arrivals})
        warm = [traffic.Req(-1 - i, tok.repeat(n), 1) for i, n in enumerate(lengths)]
        warm.append(traffic.Req(-1 - len(warm), self.arrivals[0].prompt, 3))
        reqs = [self.make(r) for r in warm]
        self._drive(reqs, 1200, lambda r: r.terminal)
        for req in reqs:
            del self.records[req.uid]
        self.times["warm_s"] = clock() - t
        log(f"warm-up: {len(lengths)} prompt lengths in {self.times['warm_s']:.1f} s")
        self._log_phase("warm", "seat")

    def seat(self):
        """Seat the steady-state batch: prefill every seated request."""
        t = clock()
        Q = self.P.RequestState
        if self.seated:
            reqs = [self.make(r) for r in self.seated]
            self._drive(reqs, 900, lambda r: r.state not in (Q.QUEUED, Q.PREFILLING))
        for a in self.arrivals:
            self.make(a)
        self.jax.block_until_ready(self.engine.cache)
        self.times["seat_s"] = clock() - t
        self.setup_s = clock() - self.t_start
        log(f"seating: {len(self.seated)} requests of "
            f"{len({len(r.prompt) for r in self.seated})} context lengths in "
            f"{self.times['seat_s']:.1f} s")
        self._log_phase("seat", "window")

    def window(self, trace: bool = False, keep_trace: str | None = None):
        from bench import tracing

        jax, engine = self.jax, self.engine
        self.stats0 = _stats(engine)
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        annotate = jax.profiler.TraceAnnotation if trace else contextlib.nullcontext
        if trace:
            from bench import sut

            kernels = tracing.kernel_table(sut.lowered_steps(engine))
            log(f"kernels of the step programs: {sorted(set(kernels.values()))}")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self._window_open = True
        self.t0, self.t1, self.steps = _open_loop(
            engine, self.P, self.arrivals, self.records, self.seconds, annotate)
        jax.block_until_ready(engine.cache)
        self._window_open = False
        self.trace = None
        if trace:
            jax.profiler.stop_trace()
            path = tracing.trace_file(trace_dir)
            self.trace = tracing.reduce_file(path)
            self.trace.kernels = kernels
            if keep_trace:
                os.makedirs(keep_trace, exist_ok=True)
                shutil.copy(path, keep_trace)
                with open(os.path.join(keep_trace, "kernels.json"), "w") as f:
                    json.dump(kernels, f, indent=1)
                with open(os.path.join(keep_trace, "planes.txt"), "w") as f:
                    f.write(tracing.describe(path))
            shutil.rmtree(trace_dir, ignore_errors=True)
        self.stats1 = _stats(engine)
        mem = self.devs[0].memory_stats() or {}
        self.memory_peak = int(mem.get("peak_bytes_in_use", 0))
        log(f"set-up {self.setup_s:.3f} s: " + ", ".join(
            f"{k} {v:.3f}" for k, v in self.times.items()))
        if self.steps:
            dts = sorted(b - a for a, b, _ in self.steps)
            log(f"step ms: median {1e3 * dts[len(dts) // 2]:.2f} max "
                f"{1e3 * dts[-1]:.2f}; decode tokens per step "
                f"{sum(k for _, _, k in self.steps) / len(self.steps):.1f}")
        log(f"window {self.t1 - self.t0:.3f} s, {len(self.steps)} steps, "
            f"{len(self.compiles)} compilations inside it")
        if self.compiles:
            log("compiled in the window: "
                + str(collections.Counter(self.compiles).most_common(8)))

    def run_record(self) -> Run:
        from bench import sut

        return Run(cell=self.cell, conf=self.conf, mix=self.mix,
                   records=self.records, t0=self.t0, t1=self.t1,
                   seconds=self.t1 - self.t0, steps=self.steps,
                   stats0=self.stats0, stats1=self.stats1, setup_s=self.setup_s,
                   trace=self.trace, peaks=self.peaks,
                   work=spec.work(self.conf["reference"]).of(
                       sut.sizes(self.conf, self.rehearse)),
                   memory_peak=self.memory_peak, max_batch=self.engine.max_batch,
                   prefill_chunk=self.engine.prefill_chunk)

    def metrics(self, section: str) -> dict:
        run = self.run_record()
        out = {}
        for m in spec.metrics_for(self.bench, self.cell["name"], section):
            if self.rehearse and m["source"] == "device_trace":
                continue
            value = spec.reader(m["name"])(run)
            if value is None:
                log(f"{m['name']}: nothing to read in this run; left out")
                continue
            extra = {}
            if isinstance(value, dict):
                extra = {k: v for k, v in value.items() if k != "value"}
                value = value["value"]
            out[m["name"]] = {"value": value, "unit": m["unit"], **extra}
        return out

    def finished(self) -> list:
        """Records of requests that finished inside the window."""
        Q = self.P.RequestState
        return [r for r in self.records.values()
                if r.req.state is Q.FINISHED and r.tokens
                and self.t0 <= r.tokens[-1] <= self.t1]

    def outcome(self) -> tuple:
        """(attempted, failed) requests."""
        Q = self.P.RequestState
        attempted = sum(1 for r in self.records.values() if r.submit_t is not None)
        failed = sum(1 for r in self.records.values()
                     if r.req.state in (Q.FAILED, Q.TIMED_OUT))
        return attempted, failed

    def release(self):
        """Free the program's state before the reference runs on the device."""
        self.engine = None
        self.built.release()
        gc.collect()


def _open_loop(engine, P, arrivals, records, seconds, annotate):
    """Drive ``engine`` for ``seconds`` of wall clock; returns (t0, t1,
    steps).  ``arrivals`` are ``Req`` with ``due`` relative to the window's
    start."""
    queued = set()  # records submitted and not yet admitted
    steps = []
    t0 = clock()
    t_end = t0 + seconds
    i, n = 0, len(arrivals)
    while True:
        now = clock()
        if now >= t_end:
            break
        with annotate("bench.submit"):
            while i < n and t0 + arrivals[i].due <= now:
                rec = records[arrivals[i].uid]
                rec.due = t0 + arrivals[i].due
                rec.submit_t = clock()
                engine.submit(rec.req)
                queued.add(rec)
                i += 1
        if not engine.queue and all(r is None for r in engine.slot_req):
            wake = min(t_end, t0 + arrivals[i].due) if i < n else t_end
            with annotate("bench.wait"):
                time.sleep(max(0.0, wake - clock()))
            continue
        s0 = clock()
        with annotate("bench.step"):
            k = engine.step()
        s1 = clock()
        steps.append((s0, s1, k))
        for rec in [r for r in queued if r.req.state is not P.RequestState.QUEUED]:
            rec.admit_t = s1
            queued.discard(rec)
    return t0, clock(), steps


def _stats(engine) -> dict:
    """Every integer counter of ``engine.stats``, so that a reader can take
    a counter the program adds later."""
    s = engine.stats
    return {f.name: getattr(s, f.name) for f in dataclasses.fields(s)
            if isinstance(getattr(s, f.name), int)}


def run_once(args) -> dict:
    """One run: set-up, window, metrics, check; the result line's object."""
    from bench import check

    s = Session(args.workload, args.seed, args.seconds, rehearse=args.rehearse)
    s.warm()
    s.seat()
    s.window(trace=bool(args.trace), keep_trace=args.keep_trace)
    metrics = s.metrics("per_layer" if args.trace else "end_to_end")
    attempted, failed = s.outcome()
    done = s.finished()
    dev = s.devs[0]
    device = {"platform": dev.platform,
              "kind": "rehearsal" if args.rehearse else dev.device_kind,
              "count": len(s.devs),
              "memory_peak_bytes": 0 if args.rehearse else s.memory_peak}
    out = {"correct": False, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if s.trace is not None and not args.rehearse:
        device["busy_s"] = s.trace.busy_s
        device["window_s"] = s.trace.window_s
        out["breakdown"] = s.trace.breakdown()
    s.release()
    s.phase, t = "check", clock()
    numbers = check.check(s.conf, s.mix, s.built.shapes, s.seed, done,
                          smoke=args.rehearse)
    log(f"check: reference over the sample in {clock() - t:.1f} s")
    s._log_phase("check")
    out["correct"] = check.judge(numbers, failed)
    out["check"] = numbers
    if not numbers:
        log("check: no request finished in the window; nothing to compare")
    if failed:
        log(f"check: {failed} requests failed")
    for name, n in numbers.items():
        log(f"check {name} {n['value']!r} limit {n['limit']!r}")
    return out


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced window's .xplane.pb and a listing "
                         "of its planes into DIR")
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: the CPU at smoke size, no device metric")
    return ap


def main(argv=None) -> int:
    out = run_once(parser().parse_args(argv))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
