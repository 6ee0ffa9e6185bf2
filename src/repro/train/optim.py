"""Optimizers + LR schedules, built from scratch (no optax in this stack).

Each optimizer is an (init, update) pair over pytrees; a state follows
the tree structure of its parameters.

* ``adamw``     -- the default; f32 moments.
* ``adafactor`` -- factored second moment: O(n+m) state per (n, m) matrix
                   instead of O(n*m).
* ``sgdm``      -- baseline.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable
    update: Callable  # (grads, state, params) -> (new_params, new_state)


# -- schedules ----------------------------------------------------------------
def warmup_cosine(peak_lr: float, warmup_steps: int, total_steps: int,
                  min_ratio: float = 0.1):
    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = peak_lr * step / jnp.maximum(warmup_steps, 1)
        t = jnp.clip((step - warmup_steps)
                     / jnp.maximum(total_steps - warmup_steps, 1), 0.0, 1.0)
        cos = peak_lr * (min_ratio + (1 - min_ratio)
                         * 0.5 * (1 + jnp.cos(jnp.pi * t)))
        return jnp.where(step < warmup_steps, warm, cos)
    return lr


def constant_lr(lr_value: float):
    return lambda step: jnp.asarray(lr_value, jnp.float32)


# -- grad utilities ----------------------------------------------------------------
def global_norm(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree_util.tree_map(lambda g: g * scale, grads), norm


# -- AdamW ----------------------------------------------------------------------------
def adamw(lr_fn, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1,
          grad_clip=1.0):
    def init(params):
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {"mu": jax.tree_util.tree_map(zeros, params),
                "nu": jax.tree_util.tree_map(zeros, params),
                "count": jnp.zeros((), jnp.int32)}

    def update(grads, state, params):
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        c = state["count"] + 1
        lr = lr_fn(c)
        bc1 = 1 - b1 ** c.astype(jnp.float32)
        bc2 = 1 - b2 ** c.astype(jnp.float32)

        def upd(g, m, v, p):
            g = g.astype(jnp.float32)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            step = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            step = step + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * step).astype(p.dtype), m, v

        out = jax.tree_util.tree_map(upd, grads, state["mu"], state["nu"],
                                     params)
        new_p = jax.tree_util.tree_map(lambda t: t[0], out,
                                       is_leaf=lambda x: isinstance(x, tuple))
        new_m = jax.tree_util.tree_map(lambda t: t[1], out,
                                       is_leaf=lambda x: isinstance(x, tuple))
        new_v = jax.tree_util.tree_map(lambda t: t[2], out,
                                       is_leaf=lambda x: isinstance(x, tuple))
        return new_p, {"mu": new_m, "nu": new_v, "count": c}, \
            {"grad_norm": gnorm, "lr": lr}

    return Optimizer(init, update)


# -- Adafactor (factored second moments) ----------------------------------------------
def adafactor(lr_fn, decay=0.8, eps=1e-30, grad_clip=1.0,
              weight_decay=0.0, min_dim_size_to_factor=64):
    def _factored(shape):
        return (len(shape) >= 2 and shape[-1] >= min_dim_size_to_factor
                and shape[-2] >= min_dim_size_to_factor)

    def init(params):
        def state_for(p):
            if _factored(p.shape):
                return {"vr": jnp.zeros(p.shape[:-1], jnp.float32),
                        "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:],
                                        jnp.float32)}
            return {"v": jnp.zeros(p.shape, jnp.float32)}
        return {"m": jax.tree_util.tree_map(state_for, params),
                "count": jnp.zeros((), jnp.int32)}

    def update(grads, state, params):
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        c = state["count"] + 1
        lr = lr_fn(c)
        beta = 1.0 - (c.astype(jnp.float32)) ** -decay

        def upd(g, s, p):
            g = g.astype(jnp.float32)
            g2 = g * g + eps
            if "vr" in s:
                vr = beta * s["vr"] + (1 - beta) * g2.mean(-1)
                vc = beta * s["vc"] + (1 - beta) * g2.mean(-2)
                r = vr / jnp.maximum(vr.mean(-1, keepdims=True), eps)
                denom = jnp.sqrt(r[..., None] * vc[..., None, :])
                new_s = {"vr": vr, "vc": vc}
            else:
                v = beta * s["v"] + (1 - beta) * g2
                denom = jnp.sqrt(v)
                new_s = {"v": v}
            step = g / jnp.maximum(denom, 1e-30)
            # relative step-size clipping (RMS <= 1)
            rms = jnp.sqrt(jnp.mean(step * step))
            step = step / jnp.maximum(1.0, rms)
            step = step + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * step).astype(p.dtype), new_s

        # state leaves are dicts -> flatten state only down to grads' leaves
        g_flat, tdef = jax.tree_util.tree_flatten(grads)
        s_flat = tdef.flatten_up_to(state["m"])
        p_flat = jax.tree_util.tree_leaves(params)
        pairs = [upd(g, s, p) for g, s, p in zip(g_flat, s_flat, p_flat)]
        new_p = jax.tree_util.tree_unflatten(tdef, [t[0] for t in pairs])
        new_m = jax.tree_util.tree_unflatten(tdef, [t[1] for t in pairs])
        return new_p, {"m": new_m, "count": c}, \
            {"grad_norm": gnorm, "lr": lr}

    return Optimizer(init, update)


# -- SGD + momentum -------------------------------------------------------------------
def sgdm(lr_fn, momentum=0.9, grad_clip=1.0):
    def init(params):
        return {"mu": jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params),
            "count": jnp.zeros((), jnp.int32)}

    def update(grads, state, params):
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        c = state["count"] + 1
        lr = lr_fn(c)

        def upd(g, m, p):
            m = momentum * m + g.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * m).astype(p.dtype), m

        out = jax.tree_util.tree_map(upd, grads, state["mu"], params)
        new_p = jax.tree_util.tree_map(lambda t: t[0], out,
                                       is_leaf=lambda x: isinstance(x, tuple))
        new_m = jax.tree_util.tree_map(lambda t: t[1], out,
                                       is_leaf=lambda x: isinstance(x, tuple))
        return new_p, {"mu": new_m, "count": c}, \
            {"grad_norm": gnorm, "lr": lr}

    return Optimizer(init, update)


OPTIMIZERS = {"adamw": adamw, "adafactor": adafactor, "sgdm": sgdm}
