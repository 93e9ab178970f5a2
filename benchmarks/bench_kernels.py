"""Chip-level INA: the K-blocked matmul's HBM-traffic contrast.

Wall-clock on CPU is meaningless for TPU kernels; the derived metric is the
compiled bytes-accessed difference between the eject/inject formulation
(per-K-block partials through HBM) and the fused single-pass matmul — the
traffic the VMEM-resident accumulator removes.  Correctness of the Pallas
kernel itself is covered by tests/test_kernels.py (interpret mode).
"""
import time

import jax
import jax.numpy as jnp

from repro.kernels import ref


def run() -> list[str]:
    lines = []
    m, k, n = 512, 4096, 512
    x = jnp.zeros((m, k), jnp.float32)
    w = jnp.zeros((k, n), jnp.float32)

    fused = jax.jit(lambda a, b: a @ b)
    eject = jax.jit(lambda a, b: ref.matmul_eject_inject(a, b, bk=512))

    cf = fused.lower(x, w).compile().cost_analysis()
    ce = eject.lower(x, w).compile().cost_analysis()
    extra = ce.get("bytes accessed", 0) - cf.get("bytes accessed", 0)
    model_extra = (k // 512) * m * n * 4 * 2      # write+read per partial

    t0 = time.time()
    fused(x, w).block_until_ready()
    us = (time.time() - t0) * 1e6
    lines.append(f"kernel_matmul_fused,{us:.0f},"
                 f"bytes={cf.get('bytes accessed', 0):.3e}")
    t0 = time.time()
    eject(x, w).block_until_ready()
    us = (time.time() - t0) * 1e6
    lines.append(f"kernel_matmul_eject_inject,{us:.0f},"
                 f"bytes={ce.get('bytes accessed', 0):.3e};"
                 f"extra_vs_fused={extra:.3e};model_extra={model_extra:.3e}")
    return lines


if __name__ == "__main__":
    print("\n".join(run()))
