"""The reduction from a profiler trace to per-layer numbers."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402,F401

from bench import tracefile  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _synthetic():
    """Two devices; program 1 runs during the host's decode span, program
    2 during prefill.  Times in ns."""
    def dev(shift):
        return {
            "ops": [["%fusion.1 = bf16[8]{0} fusion(%p)", 0 + shift, 10],
                    ["%fusion.2 = bf16[8]{0} fusion(%p)", 5 + shift, 10],
                    ["%all-reduce.3 = bf16[8]{0} all-reduce(%f)",
                     30 + shift, 5],
                    ["%fusion.9 = (f32[2]{0}, s32[]) fusion(%q)", 50, 20],
                    ["%all-reduce.4 = f32[2]{0} all-reduce(%g)", 60, 2]],
            "modules": [["jit_step(111)", 0 + shift, 35],
                        ["jit_step(222)", 50, 20]],
        }
    return {"devices": {0: dev(0), 1: dev(2)},
            "spans": [["decode", 0, 40], ["after_decode", 40, 8],
                      ["prefill", 48, 30], ["wait", 78, 22]]}


def test_union_merges_overlaps():
    assert tracefile.union([(5, 15), (0, 10), (30, 35), (35, 36)]) == \
        [(0, 15), (30, 36)]


def test_reduction_on_a_synthetic_trace():
    r = tracefile.reduce(_synthetic(), 0.0, 100.0)
    # device 0 busy: [0,15] [30,35] [50,70] = 40 ns; device 1 the same
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["decode_ms"] == pytest.approx(35e-6)      # program 1
    assert r["prefill_ms"] == pytest.approx(20e-6)     # program 2
    assert r["collective_ms"] == pytest.approx(5e-6)   # in program 1 only
    # device 0 idle: 15-30 and 35-40 in decode, 40-48 after_decode,
    # 48-50 and 70-78 in prefill, 78-100 waiting
    idle = {k: v * 1e9 for k, v in r["idle_by_span"].items()}
    assert idle == pytest.approx({"decode": 20, "after_decode": 8,
                                  "prefill": 10, "wait": 22})
    assert dict(r["device_ops"])["%fusion.9 fusion"] == pytest.approx(20e-9)
    assert r["decode_runs"] == 1 and r["prefill_runs"] == 1


def test_op_labels_and_offsets():
    assert tracefile.op_label("%while.4 = (s32[]{:T(128)}, bf16[1,8]{1,0}) "
                              "while(%t), condition=%c") == \
        ("%while.4 while", "while")
    tr = {"spans": [["decode", 5000.0, 10.0], ["after_decode", 5010.0, 3.0]]}
    host = [("wait", 0.5, 1.0), ("decode", 1.0, 1.1), ("decode", 2.0, 2.1)]
    assert tracefile.host_offset_ns(tr, host, since=1.5) == 5000.0 - 2.0e9


def test_no_device_no_numbers():
    assert tracefile.reduce({"devices": {}, "spans": []}, 0.0, 1e9) == {}


def test_reduction_on_a_recorded_chip_trace():
    """200 ms of a traced run of qwen2-1.5b.chat-p80 on one v5e, around
    the end of a prompt's prefill: the prefill program (19 ms a chunk) is
    told from the engine's small eager programs that run as often."""
    trace = json.loads((DATA / "chat_trace.json").read_text())
    trace["devices"] = {int(k): v for k, v in trace["devices"].items()}
    r = tracefile.reduce(trace, trace["lo_ns"], trace["hi_ns"])
    for key, want in trace["expect"].items():
        assert r[key] == pytest.approx(want, rel=1e-9), key
    assert r["idle_by_span"] == pytest.approx(trace["idle_by_span"])
    assert 0 < r["busy_s"] < r["window_s"]
    assert 18.0 < r["prefill_ms"] < 20.0
    idle = sum(r["idle_by_span"].values())
    assert idle == pytest.approx(r["window_s"] - r["busy_s"])
