"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The harness wraps its own work in host spans (``bench.submit``,
``bench.step``, ``bench.wait``); the traced window runs from the first of
them to the end of the last.  On the device planes (``/device:TPU:<n>``) the
line of operations gives busy time (the union of operation intervals), the
kernels by name, and the gaps in which no operation ran; the line of XLA
modules gives each program's executions.

On the chip an operation's name is its HLO instruction, which does not
carry the Pallas kernel's name; the program's lowering (StableHLO) does.
``kernel_table`` reads, from the lowered text of the programs the window
runs, each ``tpu_custom_call``'s kernel name under its signature (operand
and result shapes), and ``Trace.kernel`` finds a kernel's operations by
that signature as well as by name.  Each idle gap is labelled with the
harness span that covered most of it, so the breakdown says what the host
was doing while the chip waited.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "engine."
_MLIR_TYPES = {"i32": "s32", "i8": "s8", "i1": "pred", "bf16": "bf16",
               "f32": "f32", "f16": "f16", "i16": "s16", "i64": "s64",
               "ui8": "u8", "ui32": "u32"}
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    start: int  # ns
    end: int
    name: str
    text: str = ""  # the event's name and string stats, for kernel matching


@dataclasses.dataclass
class Span:
    """A host span of the program, its arguments, and the host line (thread)
    it ran on."""
    start: int  # ns
    end: int
    name: str
    args: dict = dataclasses.field(default_factory=dict)
    line: str = ""


@dataclasses.dataclass
class Trace:
    t0: int
    t1: int
    chips: int
    ops: list  # Event, all device planes, clipped to the window
    modules: list  # Event
    spans: list  # Event (harness host spans)
    kernels: dict = dataclasses.field(default_factory=dict)  # signature -> name
    program: list = dataclasses.field(default_factory=list)  # Span, unclipped

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(e - s for s, e in self.busy_intervals()) * 1e-9 / max(1, self.chips)

    def busy_intervals(self) -> list:
        out = []
        for ev in sorted(self.ops, key=lambda e: e.start):
            if out and ev.start <= out[-1][1]:
                out[-1][1] = max(out[-1][1], ev.end)
            else:
                out.append([ev.start, ev.end])
        return out

    def kernel(self, name: str) -> tuple:
        """(seconds, calls) of device operations that run kernel ``name``:
        by name in the operation's text, or by the signature that the
        program's lowering gives that kernel (``kernel_table``)."""
        hits = [e for e in self.ops if name in e.text
                or self.kernels.get(op_signature(e.name)) == name]
        return sum(e.end - e.start for e in hits) * 1e-9, len(hits)

    def programs(self, fragment: str) -> dict:
        """Executions of XLA modules whose name holds ``fragment``, grouped
        by program: {name#program_id: [seconds, ...]}."""
        out = collections.defaultdict(list)
        for m in self.modules:
            if fragment in m.name:
                out[m.name].append((m.end - m.start) * 1e-9)
        return dict(out)

    def idle_gaps(self) -> list:
        """(label, seconds) of each gap between busy intervals inside the
        window, labelled by the harness span that covered most of it."""
        busy = self.busy_intervals()
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        gaps = []
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((self._label(a, b), (b - a) * 1e-9))
        return gaps

    def _label(self, a: int, b: int) -> str:
        best, cover = "none", 0
        for s in self.spans:
            c = min(b, s.end) - max(a, s.start)
            if c > cover:
                best, cover = s.name, c
        return best

    def op_label(self, hlo: str) -> str:
        """A short label of an operation: its instruction and result shape,
        and the kernel it runs where the kernel table knows it."""
        head = re.match(r"\s*(%\S+ = \w+\[[\d,]*\])", hlo)
        label = head.group(1) if head else hlo[:120]
        kernel = self.kernels.get(op_signature(hlo))
        return f"{kernel} {label}" if kernel else label

    def breakdown(self) -> dict:
        by_op = collections.Counter()
        for e in self.ops:
            by_op[self.op_label(e.name)] += (e.end - e.start) * 1e-9
        gaps = sorted(self.idle_gaps(), key=lambda g: -g[1])[:10]
        return {"device_ops": [[n, s] for n, s in by_op.most_common(10)],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _shape(dtype: str, dims: str) -> str:
    return f"{dtype}[{dims}]"


def op_signature(hlo: str):
    """``"s32[32],bf16[128,4096]->bf16[128,256]"`` of an HLO custom call's
    text (operands by their layout constraints), or None."""
    head = re.match(r"\s*%\S+ = (\w+)\[([\d,]*)\]\S* custom-call\(", hlo)
    body = re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=", hlo)
    if not head or not body or "tpu_custom_call" not in hlo:
        return None
    ins = ",".join(_shape(t, d) for t, d in
                   re.findall(r"(\w+)\[([\d,]*)\]", body.group(1)))
    return f"{ins}->{_shape(*head.groups())}"


def _tensor(t: str) -> str:
    *dims, dtype = t.split("x")
    return _shape(_MLIR_TYPES.get(dtype, dtype), ",".join(dims))


def kernel_table(lowered: str) -> dict:
    """{signature: kernel name} of every ``tpu_custom_call`` in a lowered
    (StableHLO) program's text."""
    out = {}
    for line in lowered.splitlines():
        if "@tpu_custom_call" not in line:
            continue
        name = re.search(r'kernel_name = "([^"]+)"', line)
        at = line.rfind(" : (")
        sig = re.match(r"\((.*)\)\s*->\s*(.*)$", line[at + 3:].strip())
        if not name or at < 0 or not sig:
            continue
        ins = ",".join(_tensor(t) for t in
                       re.findall(r"tensor<([^>]*)>", sig.group(1)))
        res = re.findall(r"tensor<([^>]*)>", sig.group(2))
        if len(res) == 1:
            out[f"{ins}->{_tensor(res[0])}"] = name.group(1)
    return out


def _text(ev) -> str:
    parts = [ev.name]
    for k, v in ev.stats:
        if isinstance(v, str):
            parts.append(f"{k}={v}")
    return " ".join(parts)


def _program(ev) -> str:
    """A module execution's program: its name, and its program id where
    the trace gives one (two shapes of one jitted function are two
    programs of one name)."""
    for k, v in ev.stats:
        if k == "program_id":
            return f"{ev.name}#{v}"
    return ev.name


def reduce(pd) -> Trace:
    """Reduce a ``jax.profiler.ProfileData``."""
    spans, program, ops, modules = [], [], [], []
    chips = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue
            chips += 1
            for ev in lines[OPS_LINE].events:
                s = int(ev.start_ns)
                ops.append(Event(s, s + int(ev.duration_ns), ev.name, _text(ev)))
            if MODULES_LINE in lines:
                for ev in lines[MODULES_LINE].events:
                    s = int(ev.start_ns)
                    modules.append(Event(s, s + int(ev.duration_ns),
                                         _program(ev)))
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    s = int(ev.start_ns)
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Event(s, s + int(ev.duration_ns), ev.name))
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        program.append(Span(s, s + int(ev.duration_ns), ev.name,
                                            dict(ev.stats), f"{plane.name}/{i}"))
    if not spans:
        raise ValueError("trace holds no harness span")
    t0 = min(s.start for s in spans)
    t1 = max(s.end for s in spans)

    def clip(evs):
        return [Event(max(e.start, t0), min(e.end, t1), e.name, e.text)
                for e in evs if e.end > t0 and e.start < t1]

    return Trace(t0, t1, chips, clip(ops), clip(modules), spans,
                 program=program)


def trace_file(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {directory}, "
                                f"found {len(found)}")
    return found[0]


def reduce_file(path: str) -> Trace:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path))


def describe(path: str, n: int = 8) -> str:
    """Planes, lines and the first events of each line (to read a trace by
    hand before writing code against it)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(evs)}")
            for ev in evs[:n]:
                stats = " ".join(f"{k}={v}" for k, v in ev.stats)
                out.append(f"    {ev.start_ns} +{ev.duration_ns} {ev.name} {stats}"[:400])
    return "\n".join(out)
