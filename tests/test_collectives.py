"""INA collectives: numerical equivalence to psum on 8 host devices.

These tests need >1 device, so they spawn a subprocess with
``--xla_force_host_platform_device_count=8`` (the main test process keeps the
default single CPU device, per the dry-run isolation rule).
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from functools import partial

from jax import shard_map

from repro.core.collectives import (per_link_bytes, psum_ina, psum_with_mode,
                                    reduce_scatter_with_mode,
                                    ring_all_gather, ring_psum_eject_inject,
                                    ring_reduce_scatter_ina)

devs = jax.devices()
assert len(devs) == 8, devs
mesh = Mesh(np.array(devs), ("model",))

key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (8, 16, 32), jnp.float32)   # leading dim = P
ref = x.sum(axis=0)                                     # psum over the axis

def run(fn, out_spec):
    f = shard_map(fn, mesh=mesh, in_specs=P("model"), out_specs=out_spec)
    return jax.jit(f)(x)

# Each device holds x[i] (leading dim sharded); collective reduces over axis.
body = lambda xs: xs[0]

# eject/inject all-reduce == psum
out = run(lambda xs: ring_psum_eject_inject(xs[0], "model")[None], P("model"))
np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref), rtol=1e-4, atol=1e-4)

# INA ring reduce-scatter: device i holds reduced chunk i (scatter axis 0)
out = run(lambda xs: ring_reduce_scatter_ina(xs[0], "model", 0), P("model"))
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)

# INA RS on a non-leading axis, verified through a gather round-trip
def rs_then_gather(xs):
    rs = ring_reduce_scatter_ina(xs[0], "model", 1)
    return ring_all_gather(rs, "model", 1)[None]
out = run(rs_then_gather, P("model"))
np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref), rtol=1e-4, atol=1e-4)

# psum_ina (RS + AG) == psum
out = run(lambda xs: psum_ina(xs[0], "model", 0)[None], P("model"))
np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref), rtol=1e-4, atol=1e-4)

# mode dispatch: all modes agree with the reference
for mode in ("ina", "ina_ring", "eject_inject", "xla"):
    out = run(lambda xs, m=mode: psum_with_mode(xs[0], "model", m)[None],
              P("model"))
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref), rtol=1e-4, atol=1e-4)
    out = run(lambda xs, m=mode: reduce_scatter_with_mode(xs[0], "model", m, 0),
              P("model"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)

# bf16 path
xb = x.astype(jnp.bfloat16)
fb = shard_map(lambda xs: ring_reduce_scatter_ina(xs[0], "model", 0),
               mesh=mesh, in_specs=P("model"), out_specs=P("model"))
outb = jax.jit(fb)(xb)
np.testing.assert_allclose(np.asarray(outb, dtype=np.float32),
                           np.asarray(xb.astype(jnp.float32).sum(axis=0)),
                           rtol=5e-2, atol=0.5)

# traffic model sanity: INA beats eject/inject by ~P/2 when full result needed
assert per_link_bytes("eject_inject", 8, 1024) == 7 * 1024
assert per_link_bytes("ina", 8, 1024) == 2 * (7 / 8) * 1024
assert per_link_bytes("ina", 8, 1024, need_full=False) == (7 / 8) * 1024

# HLO check: eject/inject lowers to P-1 full collective-permutes, INA ring to
# P-1 chunked ones (1/P size each)
lowered = jax.jit(shard_map(lambda xs: ring_psum_eject_inject(xs[0], "model"),
                            mesh=mesh, in_specs=P("model"),
                            out_specs=P(), check_vma=False)).lower(x)
txt = lowered.as_text()
n_cp = txt.count("collective_permute") + txt.count("collective-permute")
assert n_cp >= 7, n_cp

print("COLLECTIVES_OK")
"""


@pytest.mark.slow
def test_collectives_on_8_devices():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "COLLECTIVES_OK" in proc.stdout
