"""The program's spans and counters as the per-layer readers see them
(``bench/programspans.py``, ``bench/metrics/``)."""
from __future__ import annotations

import sys
import types
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402,F401

from bench import program_split, programspans, run, tracefile  # noqa: E402
from repro.exec.trace import Recorder  # noqa: E402

NEW = ("page_out_ms", "page_out_mb_per_step", "admit_stall_ms",
       "page_out_idle_share")
ROW = 29_360_128                       # one qwen2-1.5b cache row at 1024


class Clock:
    t = 0.0

    def __call__(self) -> float:
        return self.t


def _script():
    """A recorder with spans at set times: an iteration before the window
    (t0 = 1), one across each of its edges (t1 = 10), and inside it an
    admission, two decode iterations and an admit that admitted none."""
    clock = Clock()
    rec = Recorder(clock=clock)

    def span(name, a, b, body=(), counts=(), **kw):
        clock.t = a
        with rec.span(name, **kw):
            for k, n in counts:
                rec.count(k, n)
            for f in body:
                f()
            clock.t = b

    def decode(a, b, rows):
        def step():
            span("decode.step", a, a + 0.4,
                 counts=[("decode_steps", 1), ("active_slot_steps", rows)])
        outs = [lambda i=i: span("page_out", a + 0.4 + 0.2 * i,
                                 a + 0.5 + 0.2 * i, rid=f"r{i}",
                                 counts=[("page_out_bytes", ROW),
                                         ("page_out_rows", 1)])
                for i in range(rows)]
        span("decode", a, b, body=[step, *outs])

    def admit(a, b):
        def prefill():
            span("prefill", a + 0.1, b - 0.1, rid="q", body=[
                lambda: span("prefill.chunks", a + 0.1, a + 0.5,
                             counts=[("prefill_chunks", 3)]),
                lambda: span("page_out", a + 0.5, a + 0.6,
                             counts=[("page_out_bytes", ROW),
                                     ("page_out_rows", 1)])])
        span("admit", a, b, body=[prefill], counts=[("admitted", 1)])

    decode(0.0, 0.8, 2)
    decode(0.9, 1.3, 2)                  # across t0
    admit(1.5, 2.5)
    decode(3.0, 4.0, 2)                  # page-out 0.2 s
    decode(4.0, 5.0, 1)                  # page-out 0.1 s
    span("admit", 6.0, 6.1)              # admitted none
    decode(9.5, 10.5, 3)                 # across t1
    return rec


def _record(engine, t0=1.0, t1=10.0, **kw):
    hooks = types.SimpleNamespace(engine=engine, spans=kw.pop("spans", []),
                                  installed=set(), decode_calls=[])
    kw.setdefault("trace", None)
    kw.setdefault("tracer", None)
    return run.RunRecord(hooks=hooks, t0=t0, t1=t1, **kw)


def test_the_window_takes_whole_top_level_spans():
    w = programspans.window(_record(types.SimpleNamespace(recorder=_script())))
    assert len(w.named("decode")) == 2 and len(w.named("admit")) == 2
    assert w.counts == {"decode_steps": 2, "active_slot_steps": 3,
                        "page_out_bytes": 4 * ROW, "page_out_rows": 4,
                        "prefill_chunks": 3, "admitted": 1}


def test_the_readers_on_a_synthetic_run():
    rec = _record(types.SimpleNamespace(recorder=_script()))
    got = {name: run.read_metric(name, rec) for name in NEW}
    assert got["page_out_ms"] == pytest.approx(150.0)      # 0.3 s / 2
    assert got["page_out_mb_per_step"] == pytest.approx(4 * ROW / 2 / 1e6)
    assert got["admit_stall_ms"] == pytest.approx(1000.0)  # the one admitting
    assert got["page_out_idle_share"] is None               # not traced


def test_the_readers_report_nothing_for_a_program_without_a_recorder():
    rec = _record(types.SimpleNamespace())
    assert all(run.read_metric(name, rec) is None for name in NEW)


def test_a_window_whose_spans_were_evicted_is_refused(monkeypatch):
    from repro.exec import trace
    monkeypatch.setattr(trace, "CAPACITY", 8)
    rec = _record(types.SimpleNamespace(recorder=_script()))
    assert programspans.window(rec) is None
    assert run.read_metric("page_out_ms", rec) is None


def test_innermost_labels_the_deepest_span():
    spans = [["decode", 0, 50], ["decode.step", 0, 20], ["page_out", 20, 20],
             ["retire", 45, 5]]
    assert programspans.innermost(spans) == [
        ["decode.step", 0, 20], ["page_out", 20, 20], ["decode", 40, 5],
        ["retire", 45, 5]]


def _trace():
    """Program spans, harness spans and one device's ops, in ns."""
    program = [["decode", 0, 50], ["decode.step", 0, 20],
               ["page_out", 20, 20], ["retire", 45, 5], ["admit", 60, 20],
               ["prefill", 60, 20], ["prefill.chunks", 60, 10],
               ["page_out", 70, 5]]
    harness = [["decode", 2, 16], ["after_decode", 18, 42],
               ["after_admit", 60, 25], ["wait", 85, 15]]
    ops = [[None, 0, 15], [None, 62, 6]]
    return {"devices": {0: ops}, "program": program, "harness": harness}


WANT = {("decode.step", "decode"): 3, ("decode.step", "after_decode"): 2,
        ("page_out", "after_decode"): 20, ("decode", "after_decode"): 5,
        ("retire", "after_decode"): 5, ("-", "after_decode"): 10,
        ("prefill.chunks", "after_admit"): 4, ("page_out", "after_admit"): 5,
        ("prefill", "after_admit"): 5, ("-", "after_admit"): 5,
        ("-", "wait"): 15}


def test_idle_is_split_by_the_innermost_program_span():
    tr = _trace()
    ivs = tracefile.busy(tr["devices"][0], 0, 100)
    split = programspans.idle_split(ivs, tr["program"], tr["harness"], 0, 100)
    assert dict(split) == pytest.approx(WANT)
    assert sum(split.values()) == pytest.approx(100 - 15 - 6)


def test_the_traced_reader_reads_the_runs_trace(tmp_path, monkeypatch):
    (tmp_path / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(programspans, "load", lambda path: _trace())
    tracer = types.SimpleNamespace(log_dir=str(tmp_path), started_at=0.0,
                                   stopped_at=100e-9)
    rec = _record(types.SimpleNamespace(recorder=Recorder()),
                  trace={"busy_s": 21e-9}, tracer=tracer,
                  spans=[("decode", 2e-9, 18e-9)])
    sp = programspans.traced_split(rec)
    assert sp["window_s"] == pytest.approx(100e-9)
    assert sp["idle_s"] == pytest.approx(79e-9)
    assert run.read_metric("page_out_idle_share", rec) == pytest.approx(25.0)
    # off a chip the run's reduction is empty: nothing is read
    assert run.read_metric("page_out_idle_share",
                           _record(rec.hooks.engine, trace={},
                                   tracer=tracer)) is None


def test_program_split_sums_a_synthetic_run(tmp_path, monkeypatch):
    rec = _record(types.SimpleNamespace(recorder=_script()))
    got = program_split.summary(rec)
    assert got["rows_match"] and got["bytes_per_row"] == ROW
    assert got["spans"]["decode"] == {"n": 2, "s": pytest.approx(2.0)}
    assert got["spans"]["page_out"]["n"] == 4
    assert got["spans_per_decode"] == pytest.approx(12 / 2)
    assert got["s_per_decode"] == pytest.approx(4.5)
    assert "idle" not in got                    # not traced
    (tmp_path / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(programspans, "load", lambda path: _trace())
    tracer = types.SimpleNamespace(log_dir=str(tmp_path), started_at=0.0,
                                   stopped_at=100e-9)
    idle = program_split.summary(_record(
        rec.hooks.engine, trace={"busy_s": 21e-9}, tracer=tracer,
        spans=[("decode", 2e-9, 18e-9)]))["idle"]
    assert idle["idle_s"] == pytest.approx(79e-9)
    # all but the harness's after_* gaps outside every program span
    assert idle["covered"] == pytest.approx(64 / 79)
    assert idle["split"][0] == ["page_out", "after_decode",
                                pytest.approx(20e-9)]


def test_program_split_is_silent_without_a_recorder():
    assert program_split.summary(_record(types.SimpleNamespace())) is None


def test_recorder_cost_is_timed_per_span():
    got = program_split.recorder_cost(2, iterations=20, repeats=2)
    assert got["rows"] == 2
    assert 0 < got["us_per_span"] <= got["us_per_span_max"]


def test_load_reads_the_annotations_of_a_profile(tmp_path):
    """On the CPU the profile holds host events only: the program's spans
    and the harness's, with their prefixes taken off, on one clock."""
    import jax

    rec = Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("decode"):
            with rec.span("page_out", rid="a"):
                pass
        with jax.profiler.TraceAnnotation("bench.wait"):
            pass
    finally:
        jax.profiler.stop_trace()
    tr = programspans.load(next(tmp_path.rglob("*.xplane.pb")))
    assert tr["devices"] == {}
    prog = {name: (s, s + d) for name, s, d in tr["program"]}
    assert set(prog) == {"decode", "page_out"}
    assert prog["decode"][0] <= prog["page_out"][0] <= \
        prog["page_out"][1] <= prog["decode"][1]
    assert [h[0] for h in tr["harness"]] == ["wait"]
    assert tr["harness"][0][1] >= prog["decode"][1]


def test_the_harness_wrappers_sit_inside_the_programs_spans():
    """The harness's wrappers on ``sched.admit``, ``prefill_step.fn`` and
    ``step.fn`` still find those attributes, time inside the program's
    spans, and leave every token as it was."""
    from bench.hooks import Hooks, Served
    from bench.serve import request_of
    from repro.configs import ARCHS
    from repro.serve import Request, ServingEngine

    cfg = ARCHS["qwen2-1.5b"].reduced()
    rng = np.random.default_rng(5)
    lens = [(6, 4), (11, 3), (4, 5)]
    reqs = [Request(rid=f"h{i}", prompt_len=p, max_new=n,
                    prompt=tuple(int(t) for t in rng.integers(3, cfg.vocab, p)))
            for i, (p, n) in enumerate(lens)]
    eng = ServingEngine(cfg, slots=2, max_seq=24, block_size=4,
                        prefill_chunk=4)
    plain = eng.run(reqs).tokens()
    t0 = eng.recorder.clock()
    hooks = Hooks(eng)
    for r in reqs:
        hooks.add(Served(r.rid, r.prompt, r.max_new, 0.0), submitted=True)
    hooks.install(request_of)
    assert hooks.installed == {"admit", "prefill", "decode"}
    assert eng.run(reqs).tokens() == plain
    spans = [s for s in eng.recorder.spans() if s.start >= t0]

    def inside(label, name):
        outer = [(s.start, s.end) for s in spans if s.name == name]
        inner = [(a, b) for lab, a, b in hooks.spans if lab == label]
        assert inner
        for a, b in inner:
            assert any(s <= a and b <= e for s, e in outer), (label, a, b)
    inside("decode", "decode.step")
    inside("prefill", "prefill.chunks")
    admits = [s for s in spans if s.name == "admit"]
    wrapped = [(a, b) for lab, a, b in hooks.spans if lab == "admit"]
    for s in admits:
        assert any(s.start <= a and b <= s.end for a, b in wrapped)
