"""A benchmark root in a temporary directory with tiny cells, small enough
for the harness to run end to end on the CPU."""
import json
import os
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

_OVSF = {"rho": 0.5, "seg_len": 16, "min_dim": 32, "alpha_dtype": ""}
_COMMON = {"source": "test", "norm_topk_prob": True, "num_attention_heads": 4,
           "num_hidden_layers": 2, "rms_norm_eps": 1e-05,
           "rope_theta": 10000.0, "tie_word_embeddings": False,
           "torch_dtype": "bfloat16", "vocab_size": 512, "ovsf": _OVSF,
           "modules": {"weights": "weights", "reference": "reference"}}
_PROG = {"n_layers": 2, "d_model": 128, "n_heads": 4, "head_dim": 32,
         "vocab": 512, "ovsf": {"min_dim": 32}}

# Limits as the real configurations have them, a widest gap for the dense
# model and a mean gap for the experts, set from CPU readings at this size on
# five seeds: the program's widest gap 0-0.009 and mean 0-0.0004, the fp8
# control's widest 0.011-0.68 and mean 0.0004-0.031.
CONFIGS = {
    "tiny_moe": dict(
        _COMMON, name="tiny_moe", hidden_act="silu", hidden_size=128,
        intermediate_size=64, num_experts=8, num_experts_per_tok=2,
        num_key_value_heads=4,
        program={"arch": "olmoe_1b_7b", "overrides": dict(
            _PROG, n_kv_heads=4, d_ff=64, n_experts=8, top_k=2,
            capacity_factor=4.0)},
        correct={"compare": {"mean_token_gap_std": 0.002},
                 "min_scored_tokens": 16}),
    "tiny_dense": dict(
        _COMMON, name="tiny_dense", hidden_act="gelu_pytorch_tanh",
        hidden_size=128, intermediate_size=256, num_key_value_heads=2,
        norm_type="rms_norm", use_bias=False,
        program={"arch": "starcoder2_15b", "overrides": dict(
            _PROG, n_kv_heads=2, d_ff=256)},
        correct={"compare": {"max_token_gap_std": 0.05},
                 "min_scored_tokens": 16}),
}

TRAFFIC = {"loop": "closed", "clients": 4,
           "prompt_tokens": {"dist": "lognormal", "median": 16, "sigma": 0.6,
                             "min": 4, "max": 40},
           "output_tokens": {"dist": "uniform", "min": 4, "max": 12},
           "sampling": "greedy", "eos": "ignored",
           "engine": {"slots": 4, "buffer": 64, "page_size": 16, "chunk": 16,
                      "max_step_tokens": 16},
           "check": {"requests": 4, "tokens": 24}}


def make_root(tmp: Path) -> Path:
    """BENCHMARK.json with cells tiny_moe.chat and tiny_dense.chat, their
    configuration and traffic files, and the repository's metric readers,
    weights and reference."""
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir(parents=True)
    os.symlink(REPO / "bench" / "metrics", tmp / "bench" / "metrics")
    for module in ("weights", "reference"):
        os.symlink(REPO / "bench" / f"{module}.py",
                   tmp / "bench" / f"{module}.py")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"], bench["workloads"] = [], []
    for name, c in CONFIGS.items():
        path = f"bench/configs/{name}.json"
        (tmp / path).write_text(json.dumps(c))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": path, "reduced": [], "why": "test"})
        bench["workloads"].append({"name": f"{name}.chat", "config": name,
                                   "traffic": "tiny", "chips": 1,
                                   "why": "test"})
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = cells
    (tmp / "bench" / "traffic" / "tiny.json").write_text(json.dumps(TRAFFIC))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
