"""The reader of the program's kernel counters (``ovsf_gen_macs_per_weight``)
on notes made here, and on a program without them."""
import os
import sys
from pathlib import Path

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import pytest  # noqa: E402

from bench import run as R  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def _note(d_in, d_out, nc, n_run, bj=128, seg=16):
    return dict(d_in=d_in, d_out=d_out, seg=seg, bk=128, bj=bj, nc=nc,
                n_run=n_run)


def test_gen_macs_per_weight_is_the_weight_weighted_chunk_rows(monkeypatch):
    from repro.serving import trace as program
    read = R.metric_reader(ROOT, "ovsf_gen_macs_per_weight").read
    monkeypatch.setattr(program, "kernel_notes", lambda: [
        _note(6144, 6144, 24, 1), _note(24576, 6144, 96, 1)])
    assert read(None) == pytest.approx(128.0)
    # the full loop: 96 chunks for 4 of every 5 weights, 24 for the rest
    monkeypatch.setattr(program, "kernel_notes", lambda: [
        _note(6144, 6144, 24, 24), _note(24576, 6144, 96, 96)])
    assert read(None) == pytest.approx(128.0 * (0.2 * 24 + 0.8 * 96))
    monkeypatch.setattr(program, "kernel_notes", lambda: [])
    assert read(None) is None


def test_gen_macs_per_weight_reads_none_without_the_counter(monkeypatch):
    from repro.serving import trace as program
    monkeypatch.delattr(program, "kernel_notes")
    read = R.metric_reader(ROOT, "ovsf_gen_macs_per_weight").read
    assert read(None) is None
