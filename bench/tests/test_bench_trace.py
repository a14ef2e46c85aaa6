"""The trace reduction (bench/trace.py), on a trace recorded on a TPU v5e
(three mixed steps of olmoe.chat) and on hand-made events."""
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import pytest  # noqa: E402

from bench import trace as T  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "olmoe_chat_3s.xplane.pb.gz")
KERNEL = re.compile(r"^ovsf_gemm(\.\d+)?$").match


@pytest.fixture(scope="module")
def recorded():
    tr = T.load(DATA)
    return tr, tr.ops[0], tr.window()


def test_recorded_trace_has_device_ops_and_spans(recorded):
    tr, ops, w = recorded
    assert list(tr.ops) == [0]
    assert len(ops) > 10000
    assert [s.name for s in tr.spans].count("bench.step") == 3
    # device and host share one clock: the ops lie inside the window span
    assert w[0] <= ops[0].start and ops[-1].end <= w[1]


def test_busy_union(recorded):
    tr, ops, w = recorded
    busy = T.busy_seconds(ops, *w)
    # the scan over layers (a while op) holds every layer's ops: the union
    # is the outer ops' time, not the sum of all events
    assert busy == pytest.approx(3.0789, abs=1e-3)
    assert sum(e.seconds for e in ops) > 1.5 * busy
    assert busy <= w[1] - w[0]


def test_kernel_time_by_name(recorded):
    tr, ops, w = recorded
    by = T.seconds_by_name(ops, *w, KERNEL)
    assert sorted(by) == ["ovsf_gemm.44", "ovsf_gemm.45", "ovsf_gemm.46",
                          "ovsf_gemm.47"]
    assert sum(by.values()) == pytest.approx(0.6153, abs=1e-3)
    top = T.top_ops(ops, *w, 10)
    assert not any(name.startswith("while") for name, _ in top)
    assert {n for n, _ in top} >= set(by)


def test_idle_gaps_are_labelled_by_host_spans(recorded):
    tr, ops, w = recorded
    gaps = T.idle_gaps(ops, tr.spans, *w, 10)
    assert len(gaps) == 10
    assert all(label == "host:step" for label, _ in gaps)
    assert gaps[0][1] == pytest.approx(0.00398, abs=1e-4)
    idle = (w[1] - w[0]) - T.busy_seconds(ops, *w)
    assert sum(s for _, s in gaps) <= idle + 1e-9


def test_hand_made_events():
    E = T.Event
    ops = T.mark_leaves([E("while.1", 0.0, 4.0), E("a.1", 0.5, 1.0),
                         E("b.2", 1.0, 2.5), E("c", 6.0, 7.0)])
    spans = [E("bench.window", 0.0, 10.0), E("bench.step", 0.0, 7.0),
             E("bench.finish", 7.0, 9.0)]
    assert T.busy_seconds(ops, 0.0, 10.0) == pytest.approx(5.0)
    assert T.busy_seconds(ops, 3.0, 6.5) == pytest.approx(1.5)
    assert T.seconds_by_name(ops, 0.0, 10.0) == pytest.approx(
        {"a.1": 0.5, "b.2": 1.5, "c": 1.0})
    assert T.idle_gaps(ops, spans, 0.0, 10.0) == [
        ["host:finish", pytest.approx(3.0)], ["host:step", pytest.approx(2.0)]]
    assert T.op_name("%ovsf_gemm.46 = bf16[256,2048]{1,0} custom-call(%x)") \
        == "ovsf_gemm.46"
