"""The harness reads DeepSeek-V3-shaped configuration files (latent
attention, shared experts and a share of the routed ones, a leading dense
layer, sigmoid routing with a correction bias, yarn rope): ``model_of``
names every key, ``program_config`` holds a program to them or refuses it
by the field it lacks, and the float32 reference refuses what it does not
compute. The two real configurations read as before."""
import dataclasses
import json
import os
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, os.path.join(str(ROOT), "src"))

import pytest  # noqa: E402

from bench import reference  # noqa: E402
from bench import run as R  # noqa: E402

KIMI = Path(__file__).resolve().parent / "data" / "kimi_k2_pp5_ep48.json"
YARN = {"beta_fast": 1, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
MLA = {"q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
       "qk_rope_head_dim": 64, "v_head_dim": 128}

# model_of of the two real configurations before it read the keys above
PARENT = {
    "olmoe_1b_7b": {
        "n_layers": 16, "d_model": 2048, "n_heads": 16, "n_kv_heads": 16,
        "head_dim": 128, "d_ff": 1024, "vocab": 50304, "rope_theta": 10000.0,
        "norm_eps": 1e-05, "n_experts": 64, "top_k": 8,
        "norm_topk_prob": True, "mlp": "swiglu",
        "ovsf": {"rho": 0.5, "seg_len": 16, "min_dim": 512,
                 "alpha_dtype": ""}},
    "starcoder2_15b_pp4": {
        "n_layers": 10, "d_model": 6144, "n_heads": 48, "n_kv_heads": 4,
        "head_dim": 128, "d_ff": 24576, "vocab": 49152,
        "rope_theta": 100000.0, "norm_eps": 1e-05, "n_experts": 0,
        "top_k": 0, "norm_topk_prob": False, "mlp": "gelu_tanh",
        "ovsf": {"rho": 0.5, "seg_len": 16, "min_dim": 512,
                 "alpha_dtype": ""}},
}


def kimi() -> dict:
    return json.loads(KIMI.read_text())


def real(name: str) -> dict:
    return R.load_json(ROOT / "bench" / "configs" / f"{name}.json")


def test_model_of_reads_every_key_of_the_kimi_cut():
    m = R.model_of(kimi())
    assert m["n_experts"] == 8
    assert m["router_experts"] == 384
    assert m["n_shared_experts"] == 1
    assert m["d_ff"] == 18432 and m["moe_d_ff"] == 2048
    assert m["first_dense"] == 1
    assert m["mla"] == MLA
    assert m["head_dim"] is None
    assert m["router_scoring"] == "sigmoid"
    assert m["router_bias"] is True
    assert m["routed_scaling"] == 2.827
    assert m["rope_scaling"] == YARN
    assert (m["n_layers"], m["d_model"], m["n_heads"], m["top_k"],
            m["vocab"]) == (12, 7168, 64, 8, 20480)


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_real_configurations_read_as_before(name):
    m = R.model_of(real(name))
    assert {k: m[k] for k in PARENT[name]} == PARENT[name]
    assert {k: v for k, v in m.items() if k not in PARENT[name]} == {
        "router_experts": PARENT[name]["n_experts"], "n_shared_experts": 0,
        "moe_d_ff": PARENT[name]["d_ff"], "first_dense": 0, "mla": None,
        "router_scoring": "softmax", "router_bias": False,
        "routed_scaling": 1.0, "rope_scaling": None}
    reference.check_model(m)


@pytest.mark.parametrize("key,value", [
    ("moe_layer_freq", 2), ("n_group", 8), ("topk_group", 4),
    ("num_nextn_predict_layers", 1),
    ("rope_scaling", dict(YARN, type="linear")),
    ("scoring_func", "tanh"), ("topk_method", "group_limited_greedy"),
    ("q_lora_rank", None),
    ("quantization_config", {"activation_scheme": "dynamic", "fmt": "e4m3",
                             "quant_method": "fp8",
                             "weight_block_size": [128, 128]})])
def test_a_shape_model_of_cannot_describe_is_refused(key, value):
    c = dict(kimi(), **{key: value})
    with pytest.raises(R.BenchError, match=key):
        R.model_of(c)


def test_the_expert_share_must_add_up_and_be_listed():
    c = dict(kimi(), expert_share={"n_routed_experts": 384, "chips": 47})
    with pytest.raises(R.BenchError, match="expert_share"):
        R.model_of(c)
    c = dict(kimi(), reduced=["num_hidden_layers", "vocab_size"])
    with pytest.raises(R.BenchError, match="n_routed_experts.*reduced"):
        R.model_of(c)


def test_todays_kimi_program_is_refused_for_a_field_it_lacks():
    c = kimi()
    with pytest.raises(R.BenchError, match="has no field") as e:
        R.program_config(c, R.model_of(c))
    for field in ("router_experts", "moe_d_ff", "first_dense_layers",
                  "kv_lora_rank", "router_bias", "rope_scaling"):
        assert field in str(e.value)


def _carrying(**fields):
    """The program's Kimi config as a config class that carries every field
    the file states, at the file's values unless given."""
    from repro.configs.base import ModelConfig, get_config

    @dataclasses.dataclass(frozen=True)
    class Carries(ModelConfig):
        router_experts: int = 0
        moe_d_ff: int = 0
        first_dense_layers: int = 0
        router_scoring: str = "softmax"
        router_bias: bool = False
        routed_scaling: float = 1.0
        rope_scaling: tuple = None
        q_lora_rank: int = None
        kv_lora_rank: int = None
        qk_nope_head_dim: int = None
        qk_rope_head_dim: int = None
        v_head_dim: int = None

    base = get_config("kimi_k2_1t_a32b")
    have = {f.name: getattr(base, f.name)
            for f in dataclasses.fields(ModelConfig)}
    want = dict(MLA, router_experts=384, moe_d_ff=2048, first_dense_layers=1,
                router_scoring="sigmoid", router_bias=True,
                routed_scaling=2.827,
                rope_scaling=tuple(sorted(YARN.items())))
    return Carries(**dict(have, **dict(want, **fields)))


def test_a_program_that_carries_the_fields_is_held_to_them(monkeypatch):
    import repro.configs
    c = kimi()
    m = R.model_of(c)
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda name: _carrying())
    cfg = R.program_config(c, m)
    assert (cfg.n_layers, cfg.n_experts, cfg.router_experts,
            cfg.n_shared_experts) == (12, 8, 384, 1)
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda name: _carrying(n_shared_experts=0))
    with pytest.raises(R.BenchError, match="n_shared_experts"):
        R.program_config(c, m)
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda name: _carrying(kv_lora_rank=256))
    with pytest.raises(R.BenchError, match="kv_lora_rank"):
        R.program_config(c, m)


def test_the_default_reference_refuses_the_kimi_cut():
    c = kimi()
    m = R.model_of(c)
    with pytest.raises(ValueError, match="does not compute") as e:
        reference.check_model(m)
    for key in ("mla", "n_shared_experts", "first_dense", "router_scoring",
                "router_bias", "routed_scaling", "rope_scaling",
                "router_experts", "moe_d_ff"):
        assert f"'{key}'" in str(e.value)
    cell = types.SimpleNamespace(config=dict(c, modules={
        "weights": "weights", "reference": "reference"}), model=m)
    with pytest.raises(R.BenchError, match="does not compute"):
        R.reference_of(cell)


@pytest.mark.parametrize("key,value", [
    ("mla", MLA), ("n_shared_experts", 1), ("first_dense", 1),
    ("router_scoring", "sigmoid"), ("router_bias", True),
    ("routed_scaling", 2.827), ("rope_scaling", YARN),
    ("router_experts", 384), ("moe_d_ff", 2048)])
def test_the_reference_refuses_each_key_it_does_not_compute(key, value):
    m = dict(R.model_of(real("olmoe_1b_7b")), **{key: value})
    with pytest.raises(ValueError, match=key):
        reference.check_model(m)
