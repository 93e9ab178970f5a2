"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps +
hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
# Degrades gracefully (pytest.importorskip-style) when hypothesis is absent:
# property tests are skipped, the parametrized oracle tests still run.
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ina_matmul import ina_matmul
from repro.kernels.wkv6 import wkv6

KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------------------- #
# ina_matmul
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 512, 128),
                                   (128, 1024, 256), (384, 256, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ina_matmul_shapes(m, k, n, dtype):
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (m, k), dtype)
    w = jax.random.normal(k2, (k, n), dtype)
    got = ina_matmul(x, w, bm=128, bn=128, bk=128, interpret=True)
    want = ref.matmul_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


def test_ina_matmul_equals_eject_inject():
    """Both accumulation strategies are numerically identical (fp32)."""
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (128, 512), jnp.float32)
    w = jax.random.normal(k2, (512, 128), jnp.float32)
    a = ina_matmul(x, w, bm=128, bn=128, bk=128, interpret=True)
    b = ref.matmul_eject_inject(x, w, bk=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-6,
                               atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(mb=st.integers(1, 3), kb=st.integers(1, 4), nb=st.integers(1, 3))
def test_ina_matmul_property(mb, kb, nb):
    """Property: any block-divisible shape matches the oracle."""
    m, k, n = 128 * mb, 128 * kb, 128 * nb
    x = jax.random.normal(jax.random.PRNGKey(m + k), (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(n), (k, n), jnp.float32)
    got = ina_matmul(x, w, bm=128, bn=128, bk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.matmul_ref(x, w)),
                               rtol=2e-6, atol=1e-4)


# --------------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("s,d,causal", [(256, 64, True), (256, 64, False),
                                        (512, 128, True), (1024, 64, True)])
def test_flash_attention(s, d, causal):
    k1, k2, k3 = jax.random.split(KEY, 3)
    bh = 4
    q = jax.random.normal(k1, (bh, s, d), jnp.float32)
    k = jax.random.normal(k2, (bh, s, d), jnp.float32)
    v = jax.random.normal(k3, (bh, s, d), jnp.float32)
    got = flash_attention(q, k, v, bq=128, bkv=128, causal=causal,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_flash_attention_bf16():
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (2, 256, 64), jnp.bfloat16)
    k = jax.random.normal(k2, (2, 256, 64), jnp.bfloat16)
    v = jax.random.normal(k3, (2, 256, 64), jnp.bfloat16)
    got = flash_attention(q, k, v, bq=128, bkv=128, interpret=True)
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


@settings(max_examples=6, deadline=None)
@given(nq=st.integers(1, 4), nk=st.integers(1, 4))
def test_flash_attention_property(nq, nk):
    """Rectangular Sq x Sk with causal masking matches the oracle."""
    sq, sk = 128 * nq, 128 * nk
    ks = jax.random.split(jax.random.PRNGKey(nq * 7 + nk), 3)
    q = jax.random.normal(ks[0], (2, sq, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, sk, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, sk, 64), jnp.float32)
    got = flash_attention(q, k, v, bq=128, bkv=128, causal=False,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


# --------------------------------------------------------------------------- #
# wkv6
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("s,hd,chunk", [(128, 64, 32), (256, 64, 64),
                                        (256, 128, 128)])
def test_wkv6(s, hd, chunk):
    ks = jax.random.split(KEY, 5)
    bh = 3
    r = jax.random.normal(ks[0], (bh, s, hd), jnp.float32) * 0.5
    k = jax.random.normal(ks[1], (bh, s, hd), jnp.float32) * 0.5
    v = jax.random.normal(ks[2], (bh, s, hd), jnp.float32)
    logw = -jnp.exp(jax.random.normal(ks[3], (bh, s, hd)) * 0.5 - 1.0)
    u = jax.random.normal(ks[4], (bh, hd), jnp.float32) * 0.3
    got = wkv6(r, k, v, logw, u, chunk=chunk, interpret=True)
    want = ref.wkv6_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-4)


def test_wkv6_decay_extremes():
    """Strong decay -> state forgets; near-zero decay -> state accumulates."""
    bh, s, hd = 1, 64, 64
    ks = jax.random.split(KEY, 3)
    r = jnp.ones((bh, s, hd)) * 0.1
    k = jax.random.normal(ks[0], (bh, s, hd)) * 0.3
    v = jax.random.normal(ks[1], (bh, s, hd))
    u = jnp.zeros((bh, hd))
    # saturated decay needs chunk*|logw| <= 80 for the factorized form to
    # stay exact (kernels/wkv6.py note)
    for logw_val, chunk in ((-8.0, 8), (-1e-3, 32), (-0.5, 32)):
        logw = jnp.full((bh, s, hd), logw_val)
        got = wkv6(r, k, v, logw, u, chunk=chunk, interpret=True)
        want = ref.wkv6_ref(r, k, v, logw, u)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------- #
# dispatch (kernels/ops.py)
# --------------------------------------------------------------------------- #
def test_ops_dispatch_never_interprets_unasked(monkeypatch):
    """Off a TPU: ``use_pallas=True`` raises, ``interpret=True`` runs the
    kernel in the interpreter, the default takes the jnp reference."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: False)
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (128, 256), jnp.float32)
    w = jax.random.normal(ks[1], (256, 128), jnp.float32)
    q, k, v = (jax.random.normal(kk, (2, 128, 64), jnp.float32)
               for kk in ks[2:])
    logw = -jnp.exp(q * 0.5 - 1.0)
    u = jnp.full((2, 64), 0.1)
    calls = {
        "matmul": (lambda **kw: ops.matmul(x, w, bm=128, bn=128, bk=128,
                                           **kw), ref.matmul_ref(x, w)),
        "attention": (lambda **kw: ops.attention(q, k, v, bq=128, bkv=128,
                                                 **kw),
                      ref.attention_ref(q, k, v)),
        "wkv": (lambda **kw: ops.wkv(q, k, v, logw, u, chunk=64, **kw),
                ref.wkv6_ref(q, k, v, logw, u)),
    }
    for name, (call, want) in calls.items():
        with pytest.raises(RuntimeError, match="needs a TPU"):
            call(use_pallas=True)
        for kw in ({"interpret": True}, {}):
            np.testing.assert_allclose(np.asarray(call(**kw)),
                                       np.asarray(want), rtol=1e-4,
                                       atol=1e-4, err_msg=f"{name} {kw}")
