"""Plain reference of a FedAvg round (McMahan et al. 2017), with the
client selection and batch draws of the round engine it is compared
with, so that both train the same clients on the same rows.

One round, from the round key ``key``, which carries from round to
round:

  key, rk = split(key);  k_sel, rk = split(rk)
  ids     = permutation(k_sel, N)[:K]          the K clients of the round
  w_i     = n_real[id_i] / sum(n_real[ids])    FedAvg weights
  k_i     = split(rk, K)[i]                    client i's key
  client i runs ``steps`` SGD steps from the round's params; step j
  draws ``batch`` row indices uniformly from its pool with
  ``randint(split(k_i, steps)[j], (batch,), 0, pool)`` and steps
  p <- p - lr * lr_scale * grad
  p'      = p + sum_i w_i (p_i - p)            the delta summed in float32

Stored values are rounded to the reference's precision after every
update; the delta is summed in float32.  The round loss is the mean over
clients of each client's mean step loss.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from bench.reference.numerics import Numerics


def make_rounds(loss_fn: Callable, num: Numerics, *, clients: int,
                per_round: int, steps: int, batch: int, lr: float):
    """A jitted ``run(params, key, x, y, n_real, lr_scales) ->
    (params, key, losses)`` that runs ``len(lr_scales)`` rounds from
    ``key`` and returns the key the next round would start from;
    ``loss_fn(params, bx, by)`` is the model's loss."""
    f32 = jnp.float32

    def client(p, key, cx, cy, lr_scale):
        def step(p, k):
            idx = jax.random.randint(k, (batch,), 0, cx.shape[0])
            l, g = jax.value_and_grad(loss_fn)(p, cx[idx], cy[idx])
            p = jax.tree_util.tree_map(
                lambda a, b: num.cast(a.astype(f32) -
                                      (lr * lr_scale) * b.astype(f32)), p, g)
            return p, l

        p, losses = jax.lax.scan(step, p, jax.random.split(key, steps))
        return p, jnp.mean(losses)

    def one_round(carry, lr_scale):
        key, p, x, y, n_real = carry
        key, rk = jax.random.split(key)
        k_sel, rk = jax.random.split(rk)
        ids = jax.random.permutation(k_sel, clients)[:per_round]
        w = n_real[ids].astype(f32)
        wbar = w / jnp.sum(w)

        def one_client(delta, inp):
            k, i, wb = inp
            p_i, l = client(p, k, x[i], y[i], lr_scale)
            delta = jax.tree_util.tree_map(
                lambda d, a, b: d + wb * (a.astype(f32) - b.astype(f32)),
                delta, p_i, p)
            return delta, l

        zero = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, f32), p)
        delta, losses = jax.lax.scan(
            one_client, zero,
            (jax.random.split(rk, per_round), ids, wbar))
        p = jax.tree_util.tree_map(lambda a, d: num.cast(a.astype(f32) + d),
                                   p, delta)
        return (key, p, x, y, n_real), jnp.mean(losses)

    @jax.jit
    def run(params, key, x, y, n_real, lr_scales):
        carry = (key, params, x, y, n_real)
        (key, params, *_), losses = jax.lax.scan(one_round, carry, lr_scales)
        return params, key, losses

    return run
