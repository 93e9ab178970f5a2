"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import assume, given, settings, strategies as st
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.ina_model import (DEFAULT_M_BITS, DEFAULT_Q_BITS, ConvLayer,
                                  ina_rounds, needs_ina, p_num)
from repro.core.collectives import per_link_bytes
from repro.core.noc import NocConfig, NocSim
from repro.parallel.sharding import fit_spec

# --------------------------------------------------------------------------- #
# INA analytical model invariants
# --------------------------------------------------------------------------- #
layer_st = st.builds(
    ConvLayer,
    name=st.just("L"),
    R=st.sampled_from([1, 3, 5, 7, 11]),
    C=st.integers(1, 2048),
    F=st.integers(1, 2048),
    O=st.integers(1, 256),
)


@settings(max_examples=200, deadline=None)
@given(layer_st)
def test_pnum_consistent_with_eq1(layer):
    """P# > 1 exactly when Eq. (1) says INA is needed."""
    assert (p_num(layer) > 1) == needs_ina(layer)


@settings(max_examples=200, deadline=None)
@given(layer_st, st.sampled_from([4, 8, 16]))
def test_ina_rounds_monotonic_in_mesh(layer, n):
    """A bigger mesh never needs more rounds."""
    assume(needs_ina(layer))
    assume(p_num(layer) <= n)
    r_small = ina_rounds(layer, n)
    r_big = ina_rounds(layer, 2 * n)
    assert r_big <= r_small


_W = DEFAULT_M_BITS // DEFAULT_Q_BITS       # weights one PE holds
# Layers that need INA with P# <= 8 (W < C*R*R <= 8W), drawn directly:
# filtering ``layer_st`` down to them discards most examples.
ina8_layer_st = st.sampled_from([1, 3, 5, 7, 11]).flatmap(
    lambda R: st.builds(
        ConvLayer, name=st.just("L"), R=st.just(R),
        C=st.integers(_W // (R * R) + 1, min(2048, 8 * _W // (R * R))),
        F=st.integers(1, 2048), O=st.integers(1, 256)))


@settings(max_examples=100, deadline=None)
@given(ina8_layer_st, st.sampled_from([1, 2, 4, 8]))
def test_more_pes_fewer_rounds(layer, e):
    assume(needs_ina(layer) and p_num(layer) <= 8)
    r1 = ina_rounds(layer, 8, 1)
    re = ina_rounds(layer, 8, e)
    assert re <= r1
    assert re >= r1 / e - 1          # cannot be better than linear scaling


# --------------------------------------------------------------------------- #
# NoC simulator invariants
# --------------------------------------------------------------------------- #
@settings(max_examples=50, deadline=None)
@given(st.integers(0, 7), st.integers(0, 7), st.integers(0, 7),
       st.integers(0, 7), st.integers(1, 9))
def test_packet_latency_lower_bound(x1, y1, x2, y2, flits):
    """head latency >= hops*(router+link) + endpoints; tail adds flits-1."""
    cfg = NocConfig()
    sim = NocSim(cfg)
    done = {}
    sim.enqueue(0, (x1, y1), (x2, y2), flits,
                on_done=lambda t: done.setdefault("t", t))
    sim.run()
    hops = abs(x2 - x1) + abs(y2 - y1)
    lower = 2 * cfg.ni_cycles + hops * (cfg.router_cycles + cfg.link_cycles) \
        + cfg.router_cycles + flits - 1
    assert done["t"] >= lower
    # uncontended: exact
    assert done["t"] == lower


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 16), st.integers(1, 9))
def test_contention_monotone_in_load(n_pkts, flits):
    """More packets on the same path never reduce the makespan."""
    cfg = NocConfig()
    def makespan(n):
        sim = NocSim(cfg)
        for _ in range(n):
            sim.enqueue(0, (0, 0), (0, 7), flits)
        return sim.run()
    assert makespan(n_pkts + 1) >= makespan(n_pkts)


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 8), st.integers(2, 9))
def test_ina_chain_never_slower(p, flits):
    """Relay (eject/inject) is never faster than a single riding packet."""
    cfg = NocConfig()
    chain = [(0, y) for y in range(p)]
    sim1 = NocSim(cfg)
    done = {}
    sim1.chain_eject_inject(0, chain, flits,
                            on_done=lambda t: done.setdefault("relay", t))
    sim1.run()
    sim2 = NocSim(cfg)
    sim2.enqueue(0, chain[0], chain[-1], flits,
                 on_done=lambda t: done.setdefault("ina", t))
    sim2.run()
    assert done["ina"] <= done["relay"]


# --------------------------------------------------------------------------- #
# collective traffic model invariants
# --------------------------------------------------------------------------- #
@settings(max_examples=100, deadline=None)
@given(st.integers(2, 512), st.integers(1, 2 ** 24))
def test_ina_traffic_always_wins(p, nbytes):
    ej = per_link_bytes("eject_inject", p, nbytes)
    ina_full = per_link_bytes("ina", p, nbytes, need_full=True)
    ina_rs = per_link_bytes("ina", p, nbytes, need_full=False)
    assert ina_rs <= ina_full <= ej
    if p > 2:
        assert ina_full < ej


# --------------------------------------------------------------------------- #
# sharding fitter invariants
# --------------------------------------------------------------------------- #
@settings(max_examples=100, deadline=None)
@given(st.lists(st.sampled_from([1, 2, 3, 8, 15, 16, 64, 128, 524288]),
                min_size=1, max_size=5))
def test_fit_spec_always_valid(dims):
    """Fitted specs always divide their dims; axes never duplicated."""
    devs = np.array(jax.devices() * 256)[:256].reshape(16, 16)
    mesh = Mesh(devs, ("data", "model"))
    spec = P(*(["data", "model"] * 3)[:len(dims)])
    fitted = fit_spec(spec, tuple(dims), mesh)
    seen = []
    for size, entry in zip(dims, tuple(fitted) + (None,) * len(dims)):
        axes = entry if isinstance(entry, tuple) else (
            () if entry is None else (entry,))
        span = 1
        for a in axes:
            assert a not in seen
            seen.append(a)
            span *= mesh.shape[a]
        assert size % span == 0
