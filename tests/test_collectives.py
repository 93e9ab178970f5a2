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


# --------------------------------------------------------------------------- #
# collective_bytes: traced on an abstract mesh, no devices needed
# --------------------------------------------------------------------------- #
#: A row-parallel site traced three times over by a scan: partial [2, 5, 8]
#: float32 (320 B) on a 4-way model axis.
SITE_BYTES, SPAN, TRIPS = 2 * 5 * 8 * 4, 4, 3


def _scanned_site_jaxpr(mode):
    import jax
    import jax.numpy as jnp

    from repro.parallel.tp import ParallelCtx, row_linear

    mesh = jax.sharding.AbstractMesh((SPAN,), ("model",))
    pctx = ParallelCtx(mesh=mesh, psum_mode=mode)

    def body(x, w):
        return row_linear(x, w, pctx), None

    def f(x, ws):
        return jax.lax.scan(body, x, ws)[0]

    return jax.make_jaxpr(f)(jax.ShapeDtypeStruct((2, 5, 8), jnp.float32),
                             jax.ShapeDtypeStruct((TRIPS, 8, 8), jnp.float32))


@pytest.mark.parametrize("mode,per_site", [
    ("ina", SITE_BYTES),                                  # one psum
    ("eject_inject", (SPAN - 1) * SITE_BYTES),            # full-tensor hops
    ("ina_ring", 2 * (SPAN - 1) * SITE_BYTES // SPAN),    # RS + AG chunks
    ("xla_spmd", 0),                                      # compiler's own
])
def test_collective_bytes_counts_every_site_run(mode, per_site):
    from repro.core.collectives import collective_bytes
    assert collective_bytes(_scanned_site_jaxpr(mode)) == TRIPS * per_site


def test_collective_bytes_takes_one_branch_and_refuses_a_while():
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core.collectives import collective_bytes

    mesh = jax.sharding.AbstractMesh((SPAN,), ("model",))
    psum = shard_map(lambda a: jax.lax.psum(a, "model"), mesh=mesh,
                     in_specs=P(), out_specs=P(), check_vma=False)
    x = jax.ShapeDtypeStruct((10,), jnp.float32)
    branchy = jax.make_jaxpr(lambda p, a: jax.lax.cond(
        p, psum, lambda b: psum(psum(b)), a))(True, x)
    assert collective_bytes(branchy) == 2 * 40
    looped = jax.make_jaxpr(lambda a: jax.lax.while_loop(
        lambda c: c[0] < 3, lambda c: (c[0] + 1, psum(c[1])), (0, a)))(x)
    with pytest.raises(ValueError, match="while loop"):
        collective_bytes(looped)
