"""Optimizer + loss substrate tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import optim


def _quadratic_params(key):
    return {"a": jax.random.normal(key, (8, 8)), "b": jnp.ones((8,)) * 3.0}


@pytest.mark.parametrize("name", ["adamw", "adafactor", "sgdm"])
def test_optimizers_converge_on_quadratic(name):
    opt = optim.OPTIMIZERS[name](optim.constant_lr(0.1))
    params = _quadratic_params(jax.random.PRNGKey(0))
    state = opt.init(params)

    def loss(p):
        return sum(jnp.sum(x ** 2) for x in jax.tree_util.tree_leaves(p))

    l0 = float(loss(params))
    for _ in range(60):
        grads = jax.grad(loss)(params)
        params, state, metrics = opt.update(grads, state, params)
    assert float(loss(params)) < 0.05 * l0
    assert np.isfinite(float(metrics["grad_norm"]))


def test_adafactor_state_is_factored_and_small():
    opt = optim.adafactor(optim.constant_lr(1e-3))
    params = {"w": jnp.zeros((256, 512)), "tiny": jnp.zeros((4, 4))}
    st = opt.init(params)
    assert set(st["m"]["w"].keys()) == {"vr", "vc"}
    assert st["m"]["w"]["vr"].shape == (256,)
    assert st["m"]["w"]["vc"].shape == (512,)
    assert set(st["m"]["tiny"].keys()) == {"v"}
    n_state = sum(x.size for x in jax.tree_util.tree_leaves(st["m"]))
    n_param = 256 * 512 + 16
    assert n_state < 0.02 * n_param


def test_grad_clipping():
    grads = {"w": jnp.full((10,), 100.0)}
    clipped, norm = optim.clip_by_global_norm(grads, 1.0)
    assert float(norm) > 100.0
    np.testing.assert_allclose(float(optim.global_norm(clipped)), 1.0,
                               rtol=1e-5)


def test_warmup_cosine_schedule():
    lr = optim.warmup_cosine(1e-3, warmup_steps=10, total_steps=100)
    assert float(lr(0)) == 0.0
    np.testing.assert_allclose(float(lr(10)), 1e-3, rtol=1e-5)
    assert float(lr(5)) == pytest.approx(5e-4, rel=1e-4)
    assert float(lr(100)) < float(lr(50)) < float(lr(10))
