"""Trace reduction: busy time, idle gaps by host span, kernels by name.
Synthetic events check the arithmetic; a recorded trace
(``data/cpu-rehearsal.xplane.pb``: three engine steps of a CPU rehearsal,
trimmed) checks the reading of a real file's host spans.  From a glm4-9b-8l
run on a TPU v5e: the text of every ``tpu_custom_call`` operation in the
trace (``data/v5e-glm4-custom-calls.json``) and the kernel table that the
run's lowering of its step programs gave (``data/v5e-glm4-kernels.json``)."""

import json
import os

import pytest

from bench import tracing
from bench.tracing import Event, Trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def _trace():
    ops = [Event(10, 20, "fusion.1", "fusion.1"),
           Event(15, 30, "k", "custom-call kernel_name=_bsmm_mc_kernel"),
           Event(50, 60, "k2", "custom-call kernel_name=_paged_decode_kernel"),
           Event(90, 100, "fusion.2", "fusion.2")]
    spans = [Event(0, 40, "bench.submit"), Event(40, 100, "bench.step")]
    return Trace(0, 100, 1, ops, [Event(10, 60, "jit__decode_impl")], spans)


def test_busy_is_the_union_of_operations():
    t = _trace()
    assert t.busy_intervals() == [[10, 30], [50, 60], [90, 100]]
    assert t.busy_s == pytest.approx(40e-9)
    assert t.window_s == pytest.approx(100e-9)


def test_idle_gaps_add_up_and_carry_the_host_span():
    t = _trace()
    gaps = t.idle_gaps()
    assert sum(s for _, s in gaps) + t.busy_s == pytest.approx(t.window_s)
    assert gaps[0] == ("bench.submit", pytest.approx(10e-9))
    assert ("bench.step", pytest.approx(30e-9)) in gaps


def test_kernels_are_found_by_name():
    t = _trace()
    assert t.kernel("_bsmm_mc_kernel") == (pytest.approx(15e-9), 1)
    assert t.kernel("_fused_kernel") == (0.0, 0)
    assert t.programs("_decode_impl") == {"jit__decode_impl": [pytest.approx(50e-9)]}
    b = t.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_recorded_rehearsal_trace():
    t = tracing.reduce_file(os.path.join(DATA, "cpu-rehearsal.xplane.pb"))
    assert t.chips == 0 and t.busy_s == 0
    assert {s.name for s in t.spans} >= {"bench.step", "bench.submit"}
    gaps = t.idle_gaps()
    assert len(gaps) == 1 and gaps[0][1] == pytest.approx(t.window_s)


def _chip_data():
    with open(os.path.join(DATA, "v5e-glm4-custom-calls.json")) as f:
        ops = json.load(f)["ops"]
    with open(os.path.join(DATA, "v5e-glm4-kernels.json")) as f:
        table = json.load(f)
    return ops, table


def test_chip_operations_map_to_their_kernels():
    """Every Pallas operation of the chip's trace is found under its
    kernel's name through the signature the lowering gave it."""
    ops, table = _chip_data()
    names = {}
    for text in ops:
        sig = tracing.op_signature(text)
        assert sig in table, text[:80]
        names[text.split(" = ")[0]] = table[sig]
    assert set(names.values()) == {"_bsmm_mc_kernel", "_fused_kernel",
                                   "_paged_decode_kernel"}
    assert names["%paged_decode_attention.10"] == "_paged_decode_kernel"
    events = [Event(10 * i, 10 * i + 5, t, t) for i, t in enumerate(ops)]
    t = Trace(0, 10 * len(ops), 1, events, [], [Event(0, 10 * len(ops), "bench.step")],
              kernels=table)
    calls = {k: t.kernel(k)[1] for k in set(names.values())}
    assert calls == {k: list(names.values()).count(k) for k in calls}
    assert all(lab.split()[0] in set(names.values())
               for lab, _ in t.breakdown()["device_ops"])


def test_kernel_table_reads_the_lowering():
    line = ('    %3 = stablehlo.custom_call @tpu_custom_call(%a, %b) '
            '{backend_config = "x : (y)", kernel_name = "_fused_kernel", '
            'operand_layouts = [dense<0> : tensor<1xindex>]} : '
            '(tensor<107xi32>, tensor<128x4096xbf16>, tensor<2675x128x128xi8>)'
            ' -> tensor<128x13696xbf16>')
    assert tracing.kernel_table(line) == {
        "s32[107],bf16[128,4096],s8[2675,128,128]->bf16[128,13696]":
            "_fused_kernel"}
    assert tracing.kernel_table("%4 = stablehlo.add %a, %b") == {}
    assert tracing.op_signature("%fusion.1 = f32[8] fusion(%a)") is None
