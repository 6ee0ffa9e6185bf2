"""Pallas kernel validation: interpret-mode vs the pure-jnp oracles,
sweeping shapes/dtypes (+ hypothesis property sweeps)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels import ops, ref
from repro.sim.antenna import sector_boresights
from repro.sim.pathloss import make_pathloss


def _net(key, n, m, k, extent=5000.0):
    k1, k2 = jax.random.split(key)
    U = jnp.concatenate([jax.random.uniform(k1, (n, 2), maxval=extent),
                         jnp.full((n, 1), 1.5)], 1)
    C = jnp.concatenate([jax.random.uniform(k2, (m, 2), maxval=extent),
                         jnp.full((m, 1), 25.0)], 1)
    return U, C, jnp.full((m, k), 5.0)


@pytest.mark.parametrize("model", ["power_law", "UMa", "RMa", "InH"])
@pytest.mark.parametrize("n,m,k", [(64, 32, 1), (100, 67, 3)])
def test_fused_sinr_vs_ref(model, n, m, k):
    U, C, Pw = _net(jax.random.PRNGKey(7), n, m, k)
    pm = make_pathloss(model)
    noise = 1e-12
    g_a, a_a, w_a, u_a = ops.fused_sinr(
        U, C, Pw, pathgain_fn=pm.get_pathgain, noise_w=noise, bn=32, bm=32)
    g_r, a_r, w_r, u_r = ref.fused_sinr_ref(U, C, Pw, pm.get_pathgain, noise)
    assert bool((a_a == a_r).all())
    np.testing.assert_allclose(np.asarray(g_a), np.asarray(g_r), rtol=2e-4)
    np.testing.assert_allclose(np.asarray(w_a), np.asarray(w_r), rtol=2e-4)


def test_fused_sinr_sectored():
    n, m, k = 48, 12, 2
    key = jax.random.PRNGKey(3)
    k1, k2 = jax.random.split(key)
    U = jnp.concatenate([jax.random.uniform(k1, (n, 2), maxval=3000.0),
                         jnp.full((n, 1), 1.5)], 1)
    sites = jnp.concatenate([jax.random.uniform(k2, (4, 2), maxval=3000.0),
                             jnp.full((4, 1), 25.0)], 1)
    C = jnp.repeat(sites, 3, axis=0)
    bore = sector_boresights(4, 3)
    Pw = jnp.full((m, k), 5.0)
    pm = make_pathloss("UMa")
    g_a, a_a, _, _ = ops.fused_sinr(
        U, C, Pw, pathgain_fn=pm.get_pathgain, noise_w=1e-12,
        boresight=bore, n_sectors=3, bn=16, bm=16)
    # oracle with antenna applied
    from repro.sim.antenna import Antenna_gain
    ant = Antenna_gain()
    d2, d3 = ref.pairwise_dist_ref(U, C)
    az = jnp.arctan2(U[:, None, 1] - C[None, :, 1],
                     U[:, None, 0] - C[None, :, 0])
    g = pm.get_pathgain(d2, d3, C[None, :, 2], U[:, None, 2]) \
        * ant.gain_linear(az, bore)
    r = g[:, :, None] * Pw[None]
    a_r = jnp.argmax(r.sum(2), 1)
    assert bool((a_a == a_r.astype(a_a.dtype)).all())


@settings(max_examples=15, deadline=None)
@given(n=st.integers(4, 70), m=st.integers(3, 70),
       k=st.integers(1, 4), seed=st.integers(0, 2 ** 16))
def test_fused_sinr_property(n, m, k, seed):
    """Property sweep: attachment always equals the oracle argmax; the
    interference vector is non-negative; SINR is finite and positive."""
    U, C, Pw = _net(jax.random.PRNGKey(seed), n, m, k)
    pm = make_pathloss("power_law", alpha=3.0)
    g_a, a_a, w_a, u_a = ops.fused_sinr(
        U, C, Pw, pathgain_fn=pm.get_pathgain, noise_w=1e-13, bn=16, bm=16)
    g_r, a_r, *_ = ref.fused_sinr_ref(U, C, Pw, pm.get_pathgain, 1e-13)
    assert bool((a_a == a_r).all())
    assert bool((np.asarray(u_a) > -1e-12).all())
    assert bool(np.isfinite(np.asarray(g_a)).all())
    assert bool((np.asarray(g_a) > 0).all())

