"""``sut.agree`` holds the program's model to what a configuration's model
section states, key by key."""

import types

import pytest

from bench import spec, sut

BENCH = spec.benchmark()


def _program_and_model(name):
    conf = spec.configuration(BENCH, name)
    cfg = sut.model_config(sut.program(), conf, smoke=False)
    return cfg, dict(sut.sizes(conf, smoke=False)["model"])


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_the_configurations_agree(name):
    cfg, model = _program_and_model(name)
    assert sut.agree(cfg, model) == ["norm_eps"]


@pytest.mark.parametrize("name,key,value", [
    ("glm4-9b-8l", "kv_heads", 4),  # by the field it names (n_kv_heads)
    ("glm4-9b-8l", "n_kv_heads", 4),  # a ModelConfig field of its name
    ("glm4-9b-8l", "activation", "gelu"),
    ("granite-moe-1b-a400m", "capacity_factor", 9.0),  # a MoEConfig field
])
def test_a_stated_key_the_program_contradicts_fails(name, key, value):
    cfg, model = _program_and_model(name)
    with pytest.raises(ValueError, match=key):
        sut.agree(cfg, {**model, key: value})


def test_a_stated_field_that_holds_passes():
    cfg, model = _program_and_model("granite-moe-1b-a400m")
    stated = {**model, "activation": cfg.activation,
              "capacity_factor": cfg.moe.capacity_factor}
    assert sut.agree(cfg, stated) == ["norm_eps"]


def test_a_key_the_section_leaves_out_passes():
    cfg, model = _program_and_model("glm4-9b-8l")
    for key in ("kv_heads", "head_dim", "experts", "top_k", "expert_d_ff"):
        del model[key]
    assert sut.agree(cfg, model) == ["norm_eps"]
    assert sut.agree(cfg, {}) == []


def test_a_key_with_no_field_is_named_and_passes():
    cfg, model = _program_and_model("glm4-9b-8l")
    model.update(norm_eps=0.5, state_size=128)
    assert sut.agree(cfg, model) == ["norm_eps", "state_size"]


def test_keys_with_no_field_are_named_in_the_run_log(capsys):
    """The run names them once, right after the build."""
    from bench import run

    built = types.SimpleNamespace(
        times={}, unchecked=["norm_eps"], P=None,
        cfg=types.SimpleNamespace(vocab=256), new_engine=lambda mix: None)
    run.Session(BENCH["workloads"][0]["name"], 7, 1.0, rehearse=True, built=built)
    err = capsys.readouterr().err
    assert err.count("norm_eps") == 1
    assert "no field for, left to the reference: norm_eps" in err
