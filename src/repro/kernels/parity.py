"""The fused update kernels against their jnp oracles, at any width.

Each case builds random operands of length ``n`` on the default
device, runs one kernel through ``repro.kernels.ops`` and scores it
against ``repro.kernels.ref`` (compression: against
``repro.fl.compression.reference_compress``).  A case returns its worst
error as a fraction of its tolerance, so ``≤ 1`` passes:

  * elementwise kernels: ``|got − want| / (atol + rtol·|want|)`` with
    the rtol of the output dtype (one bf16 rounding step, or a few f32
    ulps — Mosaic and XLA may round a division or a square root
    differently);
  * ``compress_delta``: ``|got − want| / (2·step)``, where ``step`` is
    the quantization step of the element's 128-lane block — two correct
    quantizers differ by one step where they round ``d/scale`` to
    different sides of a half-way point, and by up to another where
    their bf16 block scales round to neighbouring values.

``chip_smoke.py`` runs every case at qwen1.5-0.5b's carried flat length
with ``interpret=False`` (Mosaic on the chip);
``tests/test_fused_update.py`` runs them on small unaligned buffers in
interpret mode.  The error reductions are jitted with the oracle inside,
so at model width the oracle's outputs fuse into the reduction and are
never materialized next to the kernel's.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

RTOL = {jnp.dtype(jnp.bfloat16): 2.0 ** -7, jnp.dtype(jnp.float32): 1e-5}
ATOL = 1e-6
K_STACK = 4                     # clients in the weighted_delta stack


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _operands(key, n: int, *dtypes):
    keys = jax.random.split(key, len(dtypes))
    return [_normal(k, (n,), d) for k, d in zip(keys, dtypes)]


def _worst(got, want) -> jnp.ndarray:
    errs = []
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        w32 = w.astype(jnp.float32)
        tol = ATOL + RTOL[jnp.dtype(w.dtype)] * jnp.abs(w32)
        errs.append(jnp.max(jnp.abs(g.astype(jnp.float32) - w32) / tol))
    return functools.reduce(jnp.maximum, errs)


def _score(got, oracle: Callable, *args) -> float:
    """Worst tolerance fraction of ``got`` against ``oracle(*args)``."""
    return float(jax.jit(lambda g, *a: _worst(g, oracle(*a)))(got, *args))


def local_step(n: int, key, interpret: bool) -> float:
    p, g, m = _operands(key, n, jnp.bfloat16, jnp.bfloat16, jnp.float32)
    kw = dict(weight_decay=1e-4, momentum=0.9)
    got = ops.fused_local_step(p, g, m, None, 0.5, 0.01, interpret=interpret,
                               **kw)
    return _score(got, functools.partial(ref.local_step_ref, **kw),
                  p, g, m, None, 0.5, 0.01)


def weighted_delta(n: int, key, interpret: bool) -> float:
    k_s, k_p = jax.random.split(key)
    stacked = _normal(k_s, (K_STACK, n), jnp.bfloat16)
    (p,) = _operands(k_p, n, jnp.bfloat16)
    w = jnp.arange(1, K_STACK + 1, dtype=jnp.float32)
    w = w / jnp.sum(w)
    got = ops.fused_weighted_delta(stacked, p, w, interpret=interpret)
    return _score(got, ref.weighted_delta_ref, stacked, p, w)


def delta_accum(n: int, key, interpret: bool) -> float:
    d, w_end, p = _operands(key, n, jnp.float32, jnp.bfloat16, jnp.bfloat16)
    got = ops.fused_delta_accum(d, w_end, p, 0.25, interpret=interpret)
    return _score(got, ref.delta_accum_ref, d, w_end, p, 0.25)


def delta_accum_no_p(n: int, key, interpret: bool) -> float:
    d, w_end = _operands(key, n, jnp.float32, jnp.bfloat16)
    got = ops.fused_delta_accum(d, w_end, None, 0.25, interpret=interpret)
    return _score(got, lambda a, b: ref.delta_accum_ref(a, b, None, 0.25),
                  d, w_end)


def compress_delta(n: int, key, interpret: bool) -> float:
    from repro.fl import compression
    from repro.kernels.fused_update import LANES, QMAX, SCALE_PAD

    spec = compression.CompressionSpec(bits=8, density=0.1,
                                       error_feedback=True)
    (d,) = _operands(key, n, jnp.float32)
    tau = jax.jit(compression.topk_threshold, static_argnums=1)(
        d, compression.topk_k(spec, n))
    got = ops.fused_compress_delta(d, tau, bits=8, topk=True,
                                   with_residual=True, interpret=interpret)

    def worst(got, d):
        c_ref, r_ref = compression.reference_compress(d, spec)
        rows = -(-n // LANES)

        def blocks(x):
            return jnp.pad(x, (0, rows * LANES - n)).reshape(rows, LANES)

        amax = jnp.max(jnp.abs(blocks(jnp.where(jnp.abs(d) >= tau, d, 0.0))),
                       axis=-1, keepdims=True)
        step = 2.0 * (amax / QMAX[8]) * SCALE_PAD + ATOL
        return jnp.maximum(
            jnp.max(jnp.abs(blocks(got[0] - c_ref)) / step),
            jnp.max(jnp.abs(blocks(got[1] - r_ref)) / step))

    return float(jax.jit(worst)(got, d))


def dp_clip_noise(n: int, key, interpret: bool) -> float:
    d, z = _operands(key, n, jnp.float32, jnp.float32)
    got = ops.fused_dp_clip_noise(d, z, 0.7, 0.05, interpret=interpret)
    return _score(got, ref.dp_clip_noise_ref, d, z, 0.7, 0.05)


def _server_update(opt: str, n: int, key, interpret: bool) -> float:
    n_moments = {"none": 0, "momentum": 1, "adam": 2}[opt]
    p, delta, *moments = _operands(
        key, n, jnp.bfloat16, jnp.float32, *([jnp.float32] * n_moments))
    if opt == "adam":                    # ν is a running mean of g²
        moments[1] = moments[1] * moments[1]
    scalars = (0.01, 1.0 - 0.9 ** 3, 1.0 - 0.99 ** 3) if opt == "adam" \
        else (0.01,)
    got = ops.fused_server_update(p, delta, moments, scalars, opt=opt,
                                  interpret=interpret)
    oracle = functools.partial(ref.server_update_ref, opt=opt)
    return _score(got, lambda p, d, m: oracle(p, d, m, scalars),
                  p, delta, tuple(moments))


CASES: Dict[str, Callable[[int, jax.Array, bool], float]] = {
    "local_step": local_step,
    "weighted_delta": weighted_delta,
    "delta_accum": delta_accum,
    "delta_accum_no_p": delta_accum_no_p,
    "compress_delta": compress_delta,
    "dp_clip_noise": dp_clip_noise,
    "server_update_none": functools.partial(_server_update, "none"),
    "server_update_momentum": functools.partial(_server_update, "momentum"),
    "server_update_adam": functools.partial(_server_update, "adam"),
}
