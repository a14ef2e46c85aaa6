"""The harness finds a cell's configuration, traffic and per-layer metric
files by the names in BENCHMARK.json, and refuses to run where it should."""
import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

sys.path.insert(0, os.path.dirname(__file__))
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

import tiny_cell  # noqa: E402
from bench import run as R  # noqa: E402


def test_every_real_cell_resolves_and_matches_the_program():
    bench = R.load_json(ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = R.find_cell(ROOT, w["name"])
        cfg = R.program_config(cell.config, cell.model)
        assert cfg.n_layers == cell.model["n_layers"]
        for m in cell.per_layer():
            assert hasattr(R.metric_reader(ROOT, m["name"]), "read")
        assert {m["name"] for m in cell.end_to_end()} >= {"setup_s",
                                                          "output_tok_s"}


def test_a_file_added_in_a_new_root_is_found(tmp_path):
    root = tiny_cell.make_root(tmp_path)
    # a new traffic mix, a new metric and a new cell: files and entries only
    spec = dict(tiny_cell.TRAFFIC, clients=2)
    (root / "bench" / "traffic" / "pair.json").write_text(json.dumps(spec))
    os.unlink(root / "bench" / "metrics")
    (root / "bench" / "metrics").mkdir()
    (root / "bench" / "metrics" / "tokens_per_step.py").write_text(
        "def read(ctx):\n    return ctx.valid_tokens / ctx.steps\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny_moe.pair", "config": "tiny_moe",
                               "traffic": "pair", "chips": 1, "why": "t"})
    bench["per_layer"] = [{"name": "tokens_per_step", "unit": "tokens",
                           "better": "higher", "source": "program_counter",
                           "layer": "serving engine",
                           "moves": "output_tok_s",
                           "workloads": ["tiny_moe.pair"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = R.find_cell(root, "tiny_moe.pair")
    assert cell.traffic["clients"] == 2
    assert cell.config["name"] == "tiny_moe"
    assert [m["name"] for m in cell.per_layer()] == ["tokens_per_step"]
    ctx = types.SimpleNamespace(valid_tokens=30, steps=10)
    assert R.metric_reader(root, "tokens_per_step").read(ctx) == 3.0
    assert R.find_cell(root, "tiny_moe.chat").per_layer() == []
    with pytest.raises(R.BenchError):
        R.find_cell(root, "no.such")


_OWN_REFERENCE = """
from pathlib import Path

LOG = Path(__file__).parent / "order.log"


def check_model(m):
    with open(LOG, "a") as f:
        f.write("check_model\\n")


def logits(params, m, seqs, rows, prec="f32"):
    raise NotImplementedError
"""

_OWN_WEIGHTS = """
from pathlib import Path

LOG = Path(__file__).parent / "order.log"


def make(layout, key, seg):
    with open(LOG, "a") as f:
        f.write("make\\n")
    raise RuntimeError("weights made: the run stops here")
"""


def test_a_config_with_modules_of_its_own_is_found(tmp_path):
    """A configuration added in a new root names a reference and a weights
    module of its own: the harness loads them from that root, and calls the
    reference's ``check_model`` before any weight is made."""
    root = tiny_cell.make_root(tmp_path)
    own = dict(tiny_cell.CONFIGS["tiny_moe"], name="tiny_moe_own",
               modules={"weights": "own_weights",
                        "reference": "own_reference"})
    (root / "bench" / "configs" / "tiny_moe_own.json").write_text(
        json.dumps(own))
    (root / "bench" / "own_reference.py").write_text(_OWN_REFERENCE)
    (root / "bench" / "own_weights.py").write_text(_OWN_WEIGHTS)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_moe_own", "source": "test",
                             "file": "bench/configs/tiny_moe_own.json",
                             "reduced": [], "why": "t"})
    bench["workloads"].append({"name": "tiny_moe_own.chat",
                               "config": "tiny_moe_own", "traffic": "tiny",
                               "chips": 1, "why": "t"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = R.find_cell(root, "tiny_moe_own.chat")
    assert cell.config["modules"]["reference"] == "own_reference"
    ref = R.reference_of(cell)
    assert Path(ref.__file__) == root / "bench" / "own_reference.py"
    log = root / "bench" / "order.log"
    log.unlink()
    with pytest.raises(RuntimeError, match="weights made"):
        R.run(root, "tiny_moe_own.chat", 1, 1.0, False, need_tpu=False,
              cache=False)
    assert log.read_text().splitlines() == ["check_model", "make"]


def test_a_config_the_program_departs_from_is_refused():
    c = dict(tiny_cell.CONFIGS["tiny_dense"], rope_theta=5000.0)
    with pytest.raises(R.BenchError, match="rope_theta"):
        R.program_config(c, R.model_of(c))


def test_unknown_device_kind_is_an_error(monkeypatch):
    import jax
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    with pytest.raises(R.BenchError, match="TPU v99"):
        R.device_info(1, need_tpu=True)
    monkeypatch.setattr(jax, "devices", lambda: [
        types.SimpleNamespace(platform="cpu", device_kind="cpu")])
    with pytest.raises(R.BenchError, match="no TPU"):
        R.device_info(1, need_tpu=True)


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "olmoe.chat",
         "--seed", "1", "--seconds", "1", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_exits_nonzero_with_no_result():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    p = subprocess.run([sys.executable, str(tmp_path / "bench" / "run.py"),
                        "--workload", "olmoe.chat", "--seed", "1",
                        "--seconds", "1"], cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "not a checkout" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
