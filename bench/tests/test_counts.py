"""Operation and byte counters against the program's own plan accounting,
at smoke size on the CPU."""

import pytest

from bench import counts, spec, sut, weights

BENCH = spec.benchmark()
DECODER = spec.work("decoder")


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_bytes_match_the_plan(name):
    conf = spec.configuration(BENCH, name)
    P = sut.program()
    sizes = sut.sizes(conf, smoke=True)
    cfg = sut.model_config(P, conf, smoke=True)
    api = P.get_api(cfg)
    dense = weights.make_params(weights.param_shapes(api, cfg), 3,
                                sizes["plan"]["block"])
    plan = api.compress(cfg, dense, sut.plan_config(P, sizes["plan"]))
    theirs = sum(lp.bytes for p, lp in plan.leaves.items()
                 if not p.endswith("scale"))
    work = spec.work(conf["reference"]).of(sizes)
    assert work.plan_bytes() == pytest.approx(theirs, rel=0, abs=0.5)
    sparse = [p for p, lp in plan.leaves.items() if lp.kind == "quant_sparse"]
    assert sparse, "the smoke plan packs no sparse weight"
    surviving = sum(plan.leaves[p].surviving for p in sparse)
    assert work.sparse_weights() == surviving


def test_needed_work_counts_routed_experts_only():
    conf = spec.configuration(BENCH, "granite-moe-1b-a400m")
    w = DECODER.of(sut.sizes(conf, smoke=False))
    per_expert = sum(m.weights for m in w.matrices if m.count > 1)
    dense = sum(m.weights for m in w.matrices if m.count == 1)
    assert w.layer_weights_per_token() == w.layers * (8 * per_expert + dense)
    flops, byts = zip(*w.stream_calls(64))
    # all 32 experts' payloads stream, each row meets 8 of them
    assert len(flops) == w.layers * 6  # fused gate+up, w_down, wq, wk, wv, wo
    assert min(flops) > 0 and min(byts) > 0


def test_least_time_takes_each_call_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    t, bound = counts.least_time([(1000.0, 10.0), (10.0, 50.0)], peaks)
    assert t == pytest.approx(10.0 + 5.0) and bound == "compute"


def test_attention_counts_live_contexts():
    conf = spec.configuration(BENCH, "glm4-9b-8l")
    w = DECODER.of(sut.sizes(conf, smoke=False))
    f, b = w.attn_call([100, 300])
    assert f == 4.0 * 400 * 32 * 128
    assert b == pytest.approx(400 * 2 * (2 * 128 + 8) + 4.0 * 2 * 32 * 128)
