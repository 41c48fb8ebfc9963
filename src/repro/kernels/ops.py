"""Jit'd public wrappers around the Pallas kernels.

``repro.models`` routes through these when a config selects
``attn_impl='pallas'`` / ``ssd_impl='pallas'`` (or the ``*_interpret``
variants used for CPU validation).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import fused_update as _fu
from repro.kernels import ssd_scan as _ssd


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True,
                    window: Optional[jnp.ndarray] = None,
                    q_offset=0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    if window is None:
        window = jnp.int32(2 ** 30)
    if q_offset is None:
        q_offset = 0
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset, block_q=block_q,
                               block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
        B: jnp.ndarray, C: jnp.ndarray, *, chunk: int = 128,
        interpret: bool = False) -> jnp.ndarray:
    y, _ = _ssd.ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=interpret)
    return y


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_with_state(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                   B: jnp.ndarray, C: jnp.ndarray, *, chunk: int = 128,
                   interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    return _ssd.ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=interpret)


# ---------------------------------------------------------------------------
# fused FL-update kernels (repro.kernels.fused_update over flat buffers)
# ---------------------------------------------------------------------------
#
# The FL layers call these through a ``repro.fl.local.FlatParamOps``
# (flat-first: one call per dtype/mesh-axis bucket) with
# ``interpret=fused_interpret(update_impl)``.  ``update_impl="fused"``
# lowers to Mosaic whenever the backend is a TPU; on any other backend
# Mosaic cannot lower, so it runs the Pallas interpreter — the CPU test
# path.  The entry points (``repro.launch.train``, ``chip_smoke.py``)
# print the resolution, and ``chip_smoke.py`` refuses to run off-TPU,
# so a chip run can never pass on the interpreter.
# ``update_impl="fused_interpret"`` forces the interpreter everywhere
# (parity tests, benchmarks).  All wrappers take 1-D buffers: on the
# pod, ``repro.fl.pod.ShardedFlatOps`` invokes them inside a
# ``shard_map`` on each device's contiguous local shard, so the same
# kernels serve single-host FlatView buffers and mesh-sharded
# ShardedFlatView buckets unchanged.

@functools.cache
def _backend_is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def fused_interpret(update_impl: str) -> bool:
    """interpret= flag for an ``update_impl`` value: explicit interpret
    mode, or a backend other than TPU, where Mosaic cannot lower.  The
    backend is read once per process."""
    return update_impl == "fused_interpret" or not _backend_is_tpu()


def describe_fused(update_impl: str) -> str:
    """One line naming the backend and the interpret flag the fused
    path resolved to — what the entry points print."""
    return (f"backend={jax.default_backend()} update_impl={update_impl} "
            f"interpret={fused_interpret(update_impl)}")


@functools.partial(jax.jit, static_argnames=("weight_decay", "momentum",
                                             "block_rows", "interpret"))
def fused_local_step(p: jnp.ndarray, g: jnp.ndarray,
                     m: Optional[jnp.ndarray], c: Optional[jnp.ndarray],
                     clip_scale, step_size, *, weight_decay: float = 0.0,
                     momentum: float = 0.0, block_rows: int = 0,
                     interpret: bool = False):
    """Fused client step tail over one flat buffer — clip-scaled gradient
    + scaffold correction + decoupled weight decay + heavy-ball momentum
    + axpy in one blocked pass.  Returns (p_new, m_new-or-None)."""
    return _fu.local_step(p, g, m, c, clip_scale, step_size,
                          weight_decay=weight_decay, momentum=momentum,
                          block_rows=block_rows or _fu.DEFAULT_BLOCK_ROWS,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("deltas", "block_rows",
                                             "interpret"))
def fused_weighted_delta(stacked: jnp.ndarray, p: jnp.ndarray,
                         weights: jnp.ndarray,
                         extra: Optional[jnp.ndarray] = None, *,
                         deltas: bool = False, block_rows: int = 0,
                         interpret: bool = False) -> jnp.ndarray:
    """FedAvg aggregation over a stacked (K, N) flat buffer:
    ``cast(p32 + sum_k w_k * (stacked[k] - p) (+ extra))``.  ``extra``
    is an optional f32 (N,) buffer (aggregated DP noise + secure-agg
    masks) folded into the same blocked pass.  ``deltas=True`` (static)
    reads ``stacked`` as already-formed client deltas and drops the
    per-term ``- p`` (the compressed-communication aggregate)."""
    return _fu.weighted_delta(stacked, p, weights, extra=extra,
                              deltas=deltas,
                              block_rows=block_rows or _fu.DEFAULT_BLOCK_ROWS,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bits", "topk", "with_residual",
                                             "block_rows", "interpret"))
def fused_compress_delta(d: jnp.ndarray, thresh, *, bits: int = 32,
                         topk: bool = False, with_residual: bool = False,
                         block_rows: int = 0, interpret: bool = False):
    """Compressed-communication form of one client's f32 flat delta:
    magnitude top-k masking at the traced threshold ``thresh`` (static
    ``topk`` gate) followed by blockwise symmetric int8/int16 fake
    quantization (per 128-lane-block bf16 scales; ``bits=32`` skips it
    statically).  Returns ``c``, or ``(c, r)`` with the error-feedback
    residual ``r = d - c`` when ``with_residual``."""
    return _fu.compress_delta(d, thresh, bits=bits, topk=topk,
                              with_residual=with_residual,
                              block_rows=block_rows or _fu.DEFAULT_BLOCK_ROWS,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_dp_clip_noise(d: jnp.ndarray, z: Optional[jnp.ndarray],
                        clip_scale, noise_scale, *, block_rows: int = 0,
                        interpret: bool = False) -> jnp.ndarray:
    """One client's DP upload over one flat buffer:
    ``clip_scale * d32 (+ noise_scale * z)`` in a single blocked pass
    (``z=None`` statically drops the Gaussian term)."""
    return _fu.dp_clip_noise(d, z, clip_scale, noise_scale,
                             block_rows=block_rows or _fu.DEFAULT_BLOCK_ROWS,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_delta_accum(delta: jnp.ndarray, w_end: jnp.ndarray,
                      p, coeff, *, block_rows: int = 0,
                      interpret: bool = False) -> jnp.ndarray:
    """One client's contribution to the pod backend's running f32
    weighted-delta sum: ``delta + coeff * (w_end32 - p32)``, or the
    p-free accum-only form ``delta + coeff * w_end32`` when ``p=None``
    (hierarchical per-lane partials)."""
    return _fu.delta_accum(delta, w_end, p, coeff,
                           block_rows=block_rows or _fu.DEFAULT_BLOCK_ROWS,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("opt", "beta", "b1", "b2",
                                             "eps", "block_rows",
                                             "interpret"))
def fused_server_update(p: jnp.ndarray, delta: jnp.ndarray, moments, scalars,
                        *, opt: str = "none", beta: float = 0.9,
                        b1: float = 0.9, b2: float = 0.99, eps: float = 1e-8,
                        block_rows: int = 0, interpret: bool = False):
    """Apply an aggregated f32 delta under a server optimizer
    (none / FedAvgM momentum / FedAdam).  Returns (p_new, new_moments)."""
    return _fu.server_update(p, delta, tuple(moments), tuple(scalars),
                             opt=opt, beta=beta, b1=b1, b2=b2, eps=eps,
                             block_rows=block_rows or _fu.DEFAULT_BLOCK_ROWS,
                             interpret=interpret)
