"""The program's own host spans in a trace, and the engine tick they show.

The engine marks its tick ``engine.step`` and each phase of it with a span
of its own (``engine.decode``, ``engine.fetch``, ``engine.sample``, ...;
``docs/architecture.md``, "Spans and counters").  ``bench.tracing.reduce``
keeps them, with their arguments, as ``Trace.program``; the tick readers
(``bench/metrics/tick_*.py``, ``prefill_chunk_ms.py``) and the command line
take the tick from there.  From a trace kept by
``python3 -m bench.run ... --trace 1 --keep-trace DIR``:

    python3 -m bench.spans DIR

prints, as JSON, the tick's readings over the harness's window.  Spans of
one host line nest: a span's parent is the innermost span of its line that
covers it.  A *decode tick* is an ``engine.step`` span inside the window
that holds an ``engine.decode``.  A span's self time is its duration less
the part its children cover.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import json
import os
import sys

from bench import tracing
from bench.tracing import Event


def read(path: str) -> tuple:
    """(``tracing.Trace``, program spans) of a ``.xplane.pb``, or of the one
    file under a directory."""
    if os.path.isdir(path):
        path = tracing.trace_file(path)
    trace = tracing.reduce_file(path)
    return trace, trace.program


def _in_window(trace, program) -> list:
    return [s for s in program if trace.t0 <= s.start and s.end <= trace.t1]


def _inside(outer, inner) -> bool:
    return (inner is not outer and inner.line == outer.line
            and outer.start <= inner.start and inner.end <= outer.end)


def _union_ns(intervals) -> int:
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def parents(spans) -> dict:
    """{id(span): its parent span, or None at the top of its line}."""
    out, stacks = {}, collections.defaultdict(list)
    for s in sorted(spans, key=lambda s: (s.line, s.start, -s.end)):
        stack = stacks[s.line]
        while stack and stack[-1].end < s.end:
            stack.pop()
        out[id(s)] = stack[-1] if stack else None
        stack.append(s)
    return out


def decode_ticks(trace, program) -> list:
    spans = _in_window(trace, program)
    decodes = [s for s in spans if s.name == "engine.decode"]
    return [t for t in spans if t.name == "engine.step"
            and any(_inside(t, d) for d in decodes)]


def per_tick_ms(trace, program, name: str, parent: str | None = None):
    """Mean over decode ticks of the milliseconds spent in spans called
    ``name`` (with ``parent``: only those whose parent is called so), or
    None where the window holds no decode tick."""
    ticks = decode_ticks(trace, program)
    if not ticks:
        return None
    spans = _in_window(trace, program)
    up = parents(spans)
    hits = [s for s in spans if s.name == name
            and (parent is None or (up[id(s)] is not None
                                    and up[id(s)].name == parent))]
    ns = sum(s.end - s.start for t in ticks for s in hits
             if s is t or _inside(t, s))
    return ns * 1e-6 / len(ticks)


def self_ms(program, span) -> float:
    """``span``'s duration less the part its child spans cover, ms."""
    inner = [(s.start, s.end) for s in program if _inside(span, s)]
    return (span.end - span.start - _union_ns(inner)) * 1e-6


def mean_ms(trace, program, name: str):
    """Mean duration of the window's spans called ``name``, ms; None where
    there is none."""
    hits = [s.end - s.start for s in _in_window(trace, program) if s.name == name]
    return sum(hits) * 1e-6 / len(hits) if hits else None


def _innermost(spans, points) -> list:
    """For each interval between consecutive ``points`` (which hold every
    edge of ``spans`` inside them): the innermost span that covers it, or
    None."""
    order = sorted(spans, key=lambda s: (s.start, -s.end))
    active, j, out = [], 0, []
    for a, b in zip(points, points[1:]):
        while j < len(order) and order[j].start <= a:
            active.append(order[j])
            j += 1
        active = [s for s in active if s.end >= b]
        out.append(max(active, key=lambda s: (s.start, -s.end)) if active else None)
    return out


def _idle_intervals(trace) -> list:
    """Intervals of the window in which no device operation ran (the gaps
    that ``Trace.idle_gaps`` labels), as Events."""
    busy = trace.busy_intervals()
    edges = [trace.t0] + [x for iv in busy for x in iv] + [trace.t1]
    return [Event(a, b, "idle") for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def idle_by_span(trace, program) -> dict:
    """Device-idle seconds of the window by the innermost program span that
    covers them; idle outside every program span goes to the harness span
    that covers it (``"none"`` outside those too).  The values add up to
    the window's idle seconds."""
    gaps = _idle_intervals(trace)
    spans = _in_window(trace, program)
    points = sorted({p for s in gaps + spans + trace.spans
                     for p in (s.start, s.end)})
    out = collections.Counter()
    for (a, b), idle, p, h in zip(zip(points, points[1:]),
                                  _innermost(gaps, points),
                                  _innermost(spans, points),
                                  _innermost(trace.spans, points)):
        if idle is not None:
            out[(p or h).name if (p or h) else "none"] += (b - a) * 1e-9
    return dict(out)


def decode_in_step_share(trace, program):
    """% of the decode step's executions (the ``_decode_impl`` program that
    ran as many times as there were decode ticks) that start inside a
    decode tick, no earlier than that tick's ``engine.decode`` began: the
    device's clock and the host's agree.  None without decode ticks."""
    ticks = sorted(decode_ticks(trace, program), key=lambda t: t.start)
    groups = collections.defaultdict(list)
    for m in trace.modules:
        if "_decode_impl" in m.name:
            groups[m.name].append(m.start)
    if not ticks or not groups:
        return None
    runs = min(groups.values(), key=lambda v: abs(len(v) - len(ticks)))
    spans = _in_window(trace, program)
    began = {id(t): min(d.start for d in spans
                        if d.name == "engine.decode" and _inside(t, d))
             for t in ticks}
    starts = [t.start for t in ticks]
    ok = 0
    for x in runs:
        i = bisect.bisect_right(starts, x) - 1
        if i >= 0 and began[id(ticks[i])] <= x <= ticks[i].end:
            ok += 1
    return 100.0 * ok / len(runs)


def tick(trace, program) -> dict | None:
    """The engine tick over the window, or None where it holds no decode
    tick (a program without spans):

    * ``tick_host_ms``: mean per decode tick of ``engine.step`` less the
      time inside ``engine.device_wait``, where the host waits for the
      device;
    * ``tick_fetch_ms``: the copy of the decode step's result to the host
      (``engine.fetch`` directly under ``engine.step``; the other fetches
      sit under a chunk, a prefill or the draft chain);
    * ``tick_sample_ms``: the commit loop (``engine.sample``);
    * ``prefill_chunk_ms``: mean of one ``engine.prefill_chunk``, dispatch
      to the read of its finite flag;
    * ``by_span``: mean ms per decode tick in each program span
      (``engine.step.self``: the tick's own time outside them);
    * ``idle_by_span`` (s) and ``idle_s``, ``window_s``;
    * ``decode_in_step``: ``decode_in_step_share``.
    """
    ticks = decode_ticks(trace, program)
    if not ticks:
        return None
    step = per_tick_ms(trace, program, "engine.step")
    names = {s.name for s in program} - {"engine.step"}
    by_span = {n: per_tick_ms(trace, program, n) for n in sorted(names)}
    by_span["engine.step.self"] = sum(self_ms(program, t) for t in ticks) / len(ticks)
    return {
        "decode_ticks": len(ticks),
        "tick_host_ms": step - by_span.get("engine.device_wait", 0.0),
        "tick_fetch_ms": per_tick_ms(trace, program, "engine.fetch",
                                     parent="engine.step"),
        "tick_sample_ms": per_tick_ms(trace, program, "engine.sample"),
        "prefill_chunk_ms": mean_ms(trace, program, "engine.prefill_chunk"),
        "by_span": by_span,
        "idle_by_span": idle_by_span(trace, program),
        "idle_s": trace.window_s - trace.busy_s,
        "window_s": trace.window_s,
        "decode_in_step": decode_in_step_share(trace, program),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path", help="a .xplane.pb, or the directory --keep-trace wrote")
    args = ap.parse_args(argv)
    out = tick(*read(args.path))
    if out is None:
        print(f"no decode tick with program spans in {args.path}", file=sys.stderr)
        return 1
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
