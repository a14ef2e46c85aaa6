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
from repro.configs.base import OVSFConfig  # noqa: E402

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


@dataclasses.dataclass(frozen=True)
class Today:
    """A program's config as today's registry has it: the fields
    ``program_config`` compares for a dense, softmax-routed block (``hd``
    is not compared under latent attention), ``ovsf`` and
    ``capacity_factor``, at the Kimi cut's values, and none of
    ``_program_fields``. The registry's own entry is not read."""
    n_layers: int = 12
    d_model: int = 7168
    n_heads: int = 64
    n_kv_heads: int = 64
    d_ff: int = 18432
    vocab: int = 20480
    rope_theta: float = 50000.0
    norm_eps: float = 1e-06
    n_experts: int = 8
    top_k: int = 8
    mlp_gated: bool = True
    dtype: str = "bfloat16"
    qkv_bias: bool = False
    tie_embeddings: bool = False
    capacity_factor: float = 1.0    # top-8 of 8 experts held drops none
    ovsf: OVSFConfig = OVSFConfig(enable=True, rho=0.5, seg_len=16,
                                  min_dim=512)

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class Carries(Today):
    """Today's fields and every one of ``_program_fields``, at the Kimi
    cut's values."""
    router_experts: int = 384
    n_shared_experts: int = 1
    moe_d_ff: int = 2048
    first_dense_layers: int = 1
    router_scoring: str = "sigmoid"
    router_bias: bool = True
    routed_scaling: float = 2.827
    rope_scaling: tuple = tuple(sorted(YARN.items()))
    q_lora_rank: int = MLA["q_lora_rank"]
    kv_lora_rank: int = MLA["kv_lora_rank"]
    qk_nope_head_dim: int = MLA["qk_nope_head_dim"]
    qk_rope_head_dim: int = MLA["qk_rope_head_dim"]
    v_head_dim: int = MLA["v_head_dim"]


def test_the_stubs_split_at_the_program_fields():
    fields = set(R._program_fields(R.model_of(kimi())))
    today = {f.name for f in dataclasses.fields(Today)}
    assert not today & fields
    assert {f.name for f in dataclasses.fields(Carries)} - today == fields


def test_todays_kimi_program_is_refused_for_a_field_it_lacks(monkeypatch):
    """A program config without the fields (the stub ``Today``, put in the
    registry's place) is refused for the Kimi cut, naming each field."""
    import repro.configs
    monkeypatch.setattr(repro.configs, "get_config", lambda name: Today())
    c = kimi()
    with pytest.raises(R.BenchError, match="has no field") as e:
        R.program_config(c, R.model_of(c))
    for field in ("router_experts", "moe_d_ff", "first_dense_layers",
                  "kv_lora_rank", "router_bias", "rope_scaling"):
        assert field in str(e.value)


def test_a_program_that_carries_the_fields_is_held_to_them(monkeypatch):
    import repro.configs
    c = kimi()
    m = R.model_of(c)
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda name: Carries())
    cfg = R.program_config(c, m)
    assert (cfg.n_layers, cfg.n_experts, cfg.router_experts,
            cfg.n_shared_experts) == (12, 8, 384, 1)
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda name: Carries(n_shared_experts=0))
    with pytest.raises(R.BenchError, match="n_shared_experts"):
        R.program_config(c, m)
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda name: Carries(kv_lora_rank=256))
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
    cell = types.SimpleNamespace(root=ROOT, config=dict(c, modules={
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
