"""The TP cell's path off the chip: a tiny phi3-shaped cell at TP 4 on
four virtual CPU devices through the real harness, and the readers of
what TP adds (``collective_ms``, ``collective_gbps``,
``admit_page_out_mb``) on a synthetic run."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402,F401

from bench import run  # noqa: E402
from repro.exec.trace import Recorder  # noqa: E402

HERE = Path(__file__).resolve().parent


def test_a_tiny_tp4_backlog_cell_is_correct(tmp_path):
    """phi3-mini's registry entry at tiny widths (MHA, untied head, no
    QKV bias), split four ways: the reference on one device agrees with
    what the TP-4 engine served, and nothing compiles in the window."""
    script = textwrap.dedent(f"""
        import json, sys, time
        sys.path.insert(0, {str(HERE)!r})
        import tiny
        from bench import run
        from bench.spec import Cell

        base = tiny.cell("backlog")
        config = tiny.config(name="tiny-phi3", program_arch="phi3-mini-3.8b",
                             num_key_value_heads=4, qkv_bias=False,
                             tie_word_embeddings=False, rope_theta=10000.0)
        cell = dict(base.cell, model_parallel=4, program_overrides=dict(
            tiny.OVERRIDES, n_kv_heads=4))
        spec = Cell("tiny-phi3.tp4", 4, config, base.mix, cell,
                    base.end_to_end, ())
        out = run.run_cell(spec, 2**31 + 15, 3.0, False,
                           t_start=time.perf_counter(),
                           log=lambda *a, **k: None)
        print("RESULT", json.dumps(out))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               REPRO_PLAN_DIR=str(tmp_path / "plans"),
               REPRO_SIMCACHE_DIR=str(tmp_path / "simcache"))
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=600)
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT")]
    assert line, p.stderr[-3000:]
    out = json.loads(line[0][len("RESULT"):])
    assert out["correct"], out["checks"]
    assert out["compiles_in_window"] == 0
    assert out["device"]["count"] == 4
    assert out["metrics"]["tokens_per_s"]["value"] > 0


# --------------------------------------------------------------------------- #
# the readers, on a recorder with spans at set times
# --------------------------------------------------------------------------- #
PSUM = 3_145_728                 # phi3-mini's decode psums at 8 slots, bf16
PROMPT = 50_331_648              # 128 positions of a phi3-mini cache row


class Clock:
    t = 0.0

    def __call__(self) -> float:
        return self.t


def _script(psum=True):
    """Inside the window (1, 10): two admissions, one with a prompt of 128
    positions, one of 256, and three decode steps."""
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

    def admit(a, positions):
        page = [("page_out_bytes", positions // 128 * PROMPT),
                ("page_out_rows", 1)]
        span("admit", a, a + 1, counts=[("admitted", 1)], body=[
            lambda: span("prefill", a + 0.1, a + 0.9, rid="q", body=[
                lambda: span("prefill.chunks", a + 0.1, a + 0.5,
                             counts=[("prefill_chunks", 16)]),
                lambda: span("page_out", a + 0.5, a + 0.6, counts=page)])])

    def decode(a):
        counts = [("decode_steps", 1)] + ([("psum_bytes", PSUM)] if psum
                                          else [])
        span("decode", a, a + 0.1, body=[
            lambda: span("decode.step", a, a + 0.05, counts=counts),
            lambda: span("page_out", a + 0.05, a + 0.06,
                         counts=[("page_out_bytes", 100)])])

    admit(1.5, 128)
    for a in (3.0, 3.2, 3.4):
        decode(a)
    admit(5.0, 256)
    return rec


def _record(engine, trace=None):
    hooks = types.SimpleNamespace(engine=engine, spans=[], installed=set(),
                                  decode_calls=[])
    return run.RunRecord(hooks=hooks, t0=1.0, t1=10.0, trace=trace,
                         tracer=None)


NEW = ("collective_ms", "collective_gbps", "admit_page_out_mb")


def test_the_tp_readers_on_a_synthetic_run():
    rec = _record(types.SimpleNamespace(recorder=_script()),
                  trace={"collective_ms": 2.0})
    got = {name: run.read_metric(name, rec) for name in NEW}
    assert got["collective_ms"] == 2.0
    assert got["collective_gbps"] == pytest.approx(PSUM / 2e-3 / 1e9)
    # the decode columns are left out: only what prefill paged out
    assert got["admit_page_out_mb"] == pytest.approx(1.5 * PROMPT / 1e6)


def test_a_program_without_the_counter_reads_no_rate():
    """The parent commit's engine has no ``psum_bytes``: no rate, and
    the page-out per admission still reads."""
    rec = _record(types.SimpleNamespace(recorder=_script(psum=False)),
                  trace={"collective_ms": 2.0})
    assert run.read_metric("collective_gbps", rec) is None
    assert run.read_metric("admit_page_out_mb", rec) is not None


def test_the_tp_readers_report_nothing_untraced_or_without_a_recorder():
    assert all(run.read_metric(name, _record(types.SimpleNamespace()))
               is None for name in NEW)
    rec = _record(types.SimpleNamespace(recorder=_script()))
    assert run.read_metric("collective_ms", rec) is None
    assert run.read_metric("collective_gbps", rec) is None
