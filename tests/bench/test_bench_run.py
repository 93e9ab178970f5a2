"""Whole runs of a tiny cell on the CPU: the harness drives the program's
real engine, and the reference it compares with makes the program's
weights and forward on its own."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

from bench import reference, run  # noqa: E402

#: The tiny cell's limit on the widest logit gap: the program at its own
#: bfloat16 reads at most 0.0108 on seeds 1-6 at this size, the float8
#: control at least 0.0559.
GAP_LIMIT = 0.03


def _run(arrivals, seed=12345, **kw):
    return run.run_cell(tiny.cell(arrivals, gap_limit=GAP_LIMIT), seed, 3.0,
                        False, t_start=time.perf_counter(),
                        log=lambda *a, **k: None, **kw)


def test_reference_makes_the_programs_weights_and_forward():
    """Greedy tokens of the program's own float32 forward, with the
    harness's drawn norm scales and biases, have a gap of 0 in the
    reference: same weights from the seed, same arithmetic."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from bench.serve import program_config
    from repro.models.api import get_model

    from bench.serve import with_free_params

    cfg = dataclasses.replace(program_config(tiny.config(), tiny.OVERRIDES),
                              dtype="float32")
    model = get_model(cfg)
    seed = 2**31 + 77
    params = with_free_params(jax.jit(model.init)(jax.random.PRNGKey(seed)),
                              seed, tiny.config())
    prompt = [5, 17, 200, 3, 64, 9, 31]
    toks = list(prompt)
    with jax.default_matmul_precision("highest"):
        for _ in range(6):
            logits = model.forward(params, {"tokens": jnp.asarray([toks])})
            toks.append(int(jnp.argmax(logits[0, -1])))
    out = reference.gaps(tiny.config(), seed, [(prompt, toks[len(prompt):])],
                         t=32, rows=2)
    assert out["served"][0].shape == (6,)
    assert float(np.max(out["served"][0])) < 1e-5


@pytest.mark.parametrize("arrivals", ["poisson", "backlog"])
def test_a_sound_run_is_correct(arrivals):
    out = _run(arrivals)
    assert out["correct"], out["checks"]
    assert out["checks"]["logit_gap"]["value"] <= GAP_LIMIT
    assert out["compiles_in_window"] == 0
    m = out["metrics"]
    assert m["setup_s"]["value"] > 0
    assert m["tokens_per_s"]["value"] > 0 and m["ttft_p90_s"]["value"] > 0
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_the_float8_control_fails_the_limit():
    """The control: the reference put in the program's place at float8,
    judged by the run's own verdict on the cell's limits."""
    for seed in (1, 2, 3):
        out = _run("poisson", seed=seed, control=True)
        assert out["control_correct"] is False, out["readings"]
        assert out["readings"]["control_gap"] > GAP_LIMIT, out["readings"]
        assert out["correct"], out["checks"]
