"""The harness end to end on the CPU at a tiny size, with the look for a chip
skipped: sound runs come out correct, and a run whose served tokens are
altered where the step produces them comes out not correct."""
import os
import sys
from pathlib import Path

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest  # noqa: E402

import tiny_cell  # noqa: E402
from bench import run as R  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_cell.make_root(tmp_path_factory.mktemp("bench_root"))


def _run(root, cell, seed, **kw):
    return R.run(root, cell, seed, 2.0, False, need_tpu=False, cache=False,
                 **kw)


@pytest.mark.parametrize("cell", ["tiny_moe.chat", "tiny_dense.chat"])
def test_a_sound_run_is_correct(root, cell):
    res, checks, extra = _run(root, cell, 2**31 + 11)
    assert res["correct"], checks
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"output_tok_s", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert extra["setup"]["compiles_in_window"] == 0
    assert checks["scored_tokens"]["value"] >= 16


def _alter_tokens(eng):
    """Every third step, each decode slot's token is replaced where the step
    produces it (and is fed back as the next input, as a served token is)."""
    core = eng.core
    inner = core.step
    vocab = eng.cfg.vocab
    n = [0]

    def step(so, last_tokens=None):
        out = inner(so, last_tokens)
        n[0] += 1
        if n[0] % 3 == 0:
            for i in out.decode_tokens:
                out.decode_tokens[i] = (out.decode_tokens[i] + 7) % vocab
        return out

    core.step = step


@pytest.mark.parametrize("cell", ["tiny_moe.chat", "tiny_dense.chat"])
def test_an_altered_token_is_not_correct(root, cell):
    res, checks, _ = _run(root, cell, 2**31 + 11, alter=_alter_tokens)
    assert not res["correct"]
    gap = checks[_compared(cell)]
    assert gap["value"] > gap["limit"]


def _compared(cell):
    (name,) = tiny_cell.CONFIGS[cell.split(".")[0]]["correct"]["compare"]
    return name


@pytest.mark.parametrize("seed", [0, 1])
def test_the_control_reads_above_the_program(root, seed):
    """The control (the reference in fp8 put in the program's place) lies
    further from the float32 reference than the bf16 program does."""
    res, checks, extra = _run(root, "tiny_dense.chat", seed, control=True)
    s = extra["scored"]
    assert res["correct"]
    assert s["control"]["max_token_gap_std"] > 0.0
    assert s["control"]["max_token_gap_std"] >= 3 * s["max_token_gap_std"]


@pytest.mark.parametrize("cell,seed", [("tiny_dense.chat", 1),
                                       ("tiny_moe.chat", 3)])
def test_the_control_is_not_correct_at_the_limits(root, cell, seed):
    """The control, judged as the program is by ``is_correct`` at the cell's
    own limits, comes out not correct where the program is correct: for a
    widest-gap (tiny_dense) and a mean-gap (tiny_moe) comparison."""
    res, checks, extra = _run(root, cell, seed, control=True)
    assert res["correct"], checks
    ctrl = extra["control_checks"]
    assert set(ctrl) == set(checks)
    assert extra["control_correct"] is False
    assert not R.is_correct(ctrl)
    gap = ctrl[_compared(cell)]
    assert gap["value"] > gap["limit"] >= checks[_compared(cell)]["value"]


def test_a_run_that_opens_at_steady_state_is_checked(tmp_path):
    """A loop that opens at steady state (``"start": "steady"``) runs end to
    end and reports its page pool's fill; its sound run is correct, and the
    same run with altered tokens is not."""
    import json
    root = tiny_cell.make_root(tmp_path)
    path = root / "bench" / "traffic" / "tiny.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    start="steady")))
    res, checks, extra = _run(root, "tiny_moe.chat", 2**31 + 29)
    assert res["correct"], checks
    assert extra["setup"]["kv_pages"]["pool"] >= \
        extra["setup"]["kv_pages"]["peak"] > 0
    assert extra["scored"]["requests"] >= 2
    res, checks, _ = _run(root, "tiny_moe.chat", 2**31 + 29,
                          alter=_alter_tokens)
    assert not res["correct"]
