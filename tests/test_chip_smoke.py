"""chip_smoke.py off the chip: its phases at reduced width on the CPU, its
refusal to run without a TPU, and the host mesh it relies on."""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(tmp_path, **extra) -> dict:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    # plans and the simulator's window cache stay out of the checkout
    env["REPRO_PLAN_DIR"] = str(tmp_path / "plans")
    env["REPRO_SIMCACHE_DIR"] = str(tmp_path / "simcache")
    env.update(extra)
    return env


def test_serve_phase_reduced_on_cpu():
    """The default phase's serving and checks, at reduced width in f32:
    every request served in full, every retire verified, and the engine's
    prefill logits equal to the plain forward up to f32 rounding."""
    from repro.configs import ARCHS
    smoke = _load_chip_smoke()
    out = smoke.serve_phase(ARCHS["qwen2-1.5b"].reduced(), logits_tol=1e-4)
    assert out["tokens"] == 8 * 32
    assert len(out["logits_rel_l2"]) == 2
    assert max(out["logits_rel_l2"]) < 1e-4


def test_serve_phase_fails_on_a_bad_reference(monkeypatch):
    """A logits mismatch is a failure, not a printout."""
    import jax.numpy as jnp

    from repro.configs import ARCHS
    smoke = _load_chip_smoke()
    monkeypatch.setattr(smoke, "reference_last_logits",
                        lambda cfg, params, prompt: jnp.ones(cfg.vocab))
    with pytest.raises(smoke.SmokeFailure, match="rel L2"):
        smoke.serve_phase(ARCHS["qwen2-1.5b"].reduced(), n_requests=2,
                          slots=2, prompt_lens=(8, 40), gen=2, chunk=16)


def test_tp_phase_on_four_virtual_devices(tmp_path):
    """The four-chip phase on 4 CPU devices: every psum mode, auto planned
    from the mesh, matches xla_spmd on prefill and decode-step logits and
    on tokens, and serves whole at bf16.  A child process, so the device
    count is set before JAX starts."""
    code = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      {str(SCRIPT)!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        import dataclasses
        from repro.configs import ARCHS
        cfg = dataclasses.replace(ARCHS["qwen2-1.5b"].reduced(),
                                  dtype="bfloat16")
        out = smoke.tp_phase(cfg, tol=1e-5)
        assert sorted(out) == ["auto", "eject_inject", "ina"], out
        for mode, r in out.items():
            assert r["tokens_equal"] == r["tokens"] == 16, (mode, r)
            assert max(r["chunk_rel_l2"], r["step_rel_l2"]) <= 1e-5, (mode, r)
        print("TP_PHASE_OK")
    """)
    env = _env(tmp_path,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    assert "TP_PHASE_OK" in proc.stdout


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_entry_point_refuses_cpu(tmp_path, argv):
    """Off a TPU the script fails before serving and prints no ok line."""
    proc = subprocess.run([sys.executable, str(SCRIPT), *argv],
                          env=_env(tmp_path), cwd=tmp_path,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_host_mesh_refuses_model_parallel_that_does_not_divide(monkeypatch):
    import jax

    from repro.launch import mesh
    one = jax.devices()[:1]
    monkeypatch.setattr(mesh.jax, "devices", lambda: one)
    with pytest.raises(ValueError, match="does not divide"):
        mesh.make_host_mesh(4)
    assert dict(mesh.make_host_mesh(1).shape) == {"data": 1, "model": 1}
