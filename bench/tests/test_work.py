"""The decoder block's work module (``bench/work/decoder.py``) gives, at
each configuration's full size, the numbers that ``bench/counts.py``'s
``Work`` gave before it moved there: the readers ``step_mfu``,
``attn_roofline`` and ``wstream_roofline`` read the same work."""

import pytest

from bench import spec, sut

BENCH = spec.benchmark()

# (plan_bytes, decode_flops(1000), prefill_flops(512), len(stream_calls(64)),
#  the distinct stream calls in order, attn_call([100, 900]), attn_layers)
GOLDEN = {
    "glm4-9b-8l": (
        2059424064.0, 3004170240.0, 853826076672.0, 48,
        [(7180648448.0, 58485760.0), (1073741824.0, 9453568.0),
         (67108864.0, 1082368.0), (3590324224.0, 30343168.0)],
        (16384000.0, 560768.0), 8),
    "granite-moe-1b-a400m": (
        700434688.0, 578033664.0, 207089571840.0, 144,
        [(536870912.0, 18481152.0), (67108864.0, 790528.0),
         (33554432.0, 460800.0), (268435456.0, 10092544.0)],
        (4096000.0, 1096192.0), 24),
}


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_decoder_work_keeps_its_numbers(name):
    conf = spec.configuration(BENCH, name)
    assert conf["reference"] == "decoder"
    w = spec.work("decoder").of(sut.sizes(conf, smoke=False))
    plan, decode, prefill, n_calls, calls, attn, attn_layers = GOLDEN[name]
    assert w.plan_bytes() == plan
    assert w.decode_flops(1000) == decode
    assert w.prefill_flops(512) == prefill
    stream = w.stream_calls(64)
    assert len(stream) == n_calls
    assert list(dict.fromkeys(stream)) == calls
    assert w.attn_call([100, 900]) == attn
    assert w.attn_layers == attn_layers
