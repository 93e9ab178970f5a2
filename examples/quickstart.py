"""Quickstart: the paper's INA in 60 lines.

1) the analytical model (Tables I/II),
2) the NoC simulation headline (Fig. 7: WS+INA vs WS-without),
3) the pod-scale collective analogue on 8 host devices.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import os
if not os.environ.get("XLA_FLAGS"):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from repro.core.ina_model import ina_table
from repro.core.noc.power import ws_ina_improvement
from repro.core.collectives import (per_link_bytes, psum_ina,
                                    ring_psum_eject_inject)
from repro.core.workloads import ALEXNET

# --- 1. the paper's Eq. (1)-(3): which layers need INA, how many rounds ----
print("AlexNet INA rounds (paper Table I):")
for row in ina_table(ALEXNET, n=8):
    print(f"  {row['layer']}: P#={row['P#']}  INA#={row['INA#']}")

# --- 2. NoC simulation: the headline improvement ---------------------------
imp = ws_ina_improvement("alexnet", ALEXNET, e_pes=1, sim_rounds=16)
print(f"\nWS+INA vs WS-without-INA (8x8 mesh, 1 PE/router):")
print(f"  latency improvement {imp.latency_x:.2f}x   "
      f"network-energy improvement {imp.energy_x:.2f}x")
print("  (paper: up to 1.17x latency / 2.1x power for AlexNet)")

# --- 3. the same idea at pod scale: accumulate-while-routing ----------------
mesh = Mesh(np.array(jax.devices()), ("model",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 32, 64))
ref = x.sum(0)

ej = jax.jit(shard_map(lambda xs: ring_psum_eject_inject(xs[0], "model")[None],
                       mesh=mesh, in_specs=P("model"), out_specs=P("model")))
ina = jax.jit(shard_map(lambda xs: psum_ina(xs[0], "model")[None],
                        mesh=mesh, in_specs=P("model"), out_specs=P("model")))
np.testing.assert_allclose(np.asarray(ej(x)[0]), np.asarray(ref), rtol=1e-4)
np.testing.assert_allclose(np.asarray(ina(x)[0]), np.asarray(ref), rtol=1e-4)

nbytes = x[0].nbytes
print(f"\npod-scale psum of a {nbytes/1024:.0f} KiB partial over 8 devices:")
print(f"  eject/inject moves {per_link_bytes('eject_inject', 8, nbytes)/1024:.0f}"
      f" KiB per link; INA moves {per_link_bytes('ina', 8, nbytes)/1024:.0f} KiB"
      f" ({per_link_bytes('eject_inject', 8, nbytes)/per_link_bytes('ina', 8, nbytes):.1f}x less)")
print("quickstart OK")
