"""A run whose timed path is broken underneath reads ``correct: false``.

Each fault is planted in the program's engine after it is built, and the
rest of the run (window, sampling, reference) goes as on the chip, only
the harness's look for a chip is skipped.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

from bench import run  # noqa: E402


def state_unchanged(engine):
    """The decode step returns the cache it was given."""
    import jax
    import jax.numpy as jnp
    fn = engine.step.fn

    def broken(params, batch, cache):
        keep = jax.tree.map(jnp.copy, cache)
        nxt, _ = fn(params, batch, cache)
        return nxt, keep
    engine.step.fn = broken


def half_batch(engine):
    """Half of the active slots are left out of the decode step: they get
    the token of the first active slot (token 0 where none is left)."""
    fn = engine.step.fn

    def broken(params, batch, cache):
        active = sorted(engine.sched.active)
        nxt, cache = fn(params, batch, cache)
        nxt = np.array(nxt)
        kept, left = active[:len(active) // 2], active[len(active) // 2:]
        nxt[left] = nxt[kept[0]] if kept else 0
        return nxt, cache
    engine.step.fn = broken


def token_altered(engine):
    """Each request's first decoded token is off by one."""
    fn, vocab = engine.step.fn, engine.cfg.vocab

    def broken(params, batch, cache):
        first = [s for s, st in engine.sched.active.items()
                 if len(st.generated) == 1]
        nxt, cache = fn(params, batch, cache)
        nxt = np.array(nxt)
        nxt[first] = (nxt[first] + 1) % vocab
        return nxt, cache
    engine.step.fn = broken


def bias_dropped(engine):
    """The QKV bias add is left out: the step reads zero biases."""
    import jax.numpy as jnp
    layers = engine.params["layers"]
    attn = {k: jnp.zeros_like(v) if k in ("bq", "bk", "bv") else v
            for k, v in layers["attn"].items()}
    engine.params = dict(engine.params, layers=dict(layers, attn=attn))


def norm_scale_dropped(engine):
    """The RMSNorm scales are left out: the step reads scales of one."""
    import jax.numpy as jnp
    layers = engine.params["layers"]
    engine.params = dict(
        engine.params, ln_f=jnp.ones_like(engine.params["ln_f"]),
        layers=dict(layers, ln1=jnp.ones_like(layers["ln1"]),
                    ln2=jnp.ones_like(layers["ln2"])))


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered, bias_dropped,
                                   norm_scale_dropped],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("arrivals", ["poisson", "backlog"])
def test_a_broken_timed_path_is_not_correct(fault, arrivals):
    out = run.run_cell(tiny.cell(arrivals), 7, 3.0, False,
                       t_start=time.perf_counter(), break_path=fault,
                       log=lambda *a, **k: None)
    assert not out["correct"], out["checks"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["max"]


def test_the_exchange_between_chips_left_out_is_not_correct(tmp_path):
    """On four virtual devices at TP 4, a row-parallel psum that returns
    each device's partial sum."""
    script = textwrap.dedent(f"""
        import sys, time
        sys.path.insert(0, {str(Path(__file__).resolve().parent)!r})
        import tiny
        from bench import run
        import repro.parallel.tp as tp

        def tp4(**over):
            c = tiny.cell("backlog", **over)
            c.cell["model_parallel"] = 4
            return c

        sound = run.run_cell(tp4(), 5, 3.0, False,
                             t_start=time.perf_counter(),
                             log=lambda *a, **k: None)
        tp.psum_with_mode = lambda x, *a, **k: x
        broken = run.run_cell(tp4(), 5, 3.0, False,
                              t_start=time.perf_counter(),
                              log=lambda *a, **k: None)
        print("RESULT", sound["correct"], broken["correct"],
              sound["checks"]["logit_gap"]["value"],
              broken["checks"]["logit_gap"]["value"])
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               REPRO_PLAN_DIR=str(tmp_path / "plans"),
               REPRO_SIMCACHE_DIR=str(tmp_path / "simcache"))
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=600)
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT")]
    assert line, p.stderr[-3000:]
    _, sound, broken, g_sound, g_broken = line[0].split()
    assert sound == "True" and broken == "False", line
