"""Flash attention for TPU — Pallas kernel (causal, GQA, sliding window).

Online-softmax attention with the canonical TPU tiling: the grid is
(batch, q_head, q_block, kv_block) with the kv axis innermost, so each
(b, h, i) q tile stays resident in VMEM while K/V stream through in
``bk``-sized chunks; running max ``m``, normalizer ``l`` and the f32
output accumulator live in VMEM scratch across kv steps.  Both matmuls
(Q·Kᵀ and P·V) hit the MXU; block sizes default to 128 to match the
MXU's 128×128 systolic tile.

GQA is handled in the index map: q head ``h`` reads kv head ``h // G``
directly — the KV tensor is never materialized per-q-head.

Dynamic quantities ride in a scalar-prefetch operand (SMEM):
  [0] q_offset  — position of q[0] relative to k[0] (decode: cache_len)
  [1] window    — sliding-window size (2^30 = full causal); traced
                  per-layer in hybrid models (Hymba SWA/global mix)
  [2] kv_len    — true #keys before padding to a bk multiple

The tests validate the kernel in interpret mode on CPU against
``ref.attention_ref``; on a TPU the same code lowers to Mosaic.  It has
no backward pass, so training paths use XLA attention.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(scal_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, bq: int, bk: int, scale: float):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)
    q_offset = scal_ref[0]
    window = scal_ref[1]
    kv_len = scal_ref[2]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, :, 0, :].astype(jnp.float32) * scale        # (bq, hd)
    k = k_ref[0, :, 0, :].astype(jnp.float32)                # (bk, hd)
    v = v_ref[0, :, 0, :].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    qpos = q_offset + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (kpos <= qpos) & (kpos > qpos - window) & (kpos < kv_len)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    # explicit zeroing of masked entries — when a whole row is masked the
    # shifted exponent would otherwise be exp(0)=1 and pollute l/acc.
    p = jnp.where(mask, jnp.exp(s - m_cur[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(j == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, :, 0, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True,
                    window: Optional[jnp.ndarray] = None,
                    q_offset=0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, S, H, hd); k/v: (B, T, KH, hd).  Returns (B, S, H, hd).

    ``window``/``q_offset`` may be traced scalars (decode / per-layer SWA).
    Non-causal is not needed by any assigned arch; ``causal`` is asserted.
    """
    assert causal, "only causal attention is implemented (decoder-only archs)"
    B, S, H, hd = q.shape
    T, KH = k.shape[1], k.shape[2]
    assert H % KH == 0, (H, KH)
    G = H // KH

    bq = min(block_q, S)
    bk = min(block_k, T)
    s_pad = (-S) % bq
    t_pad = (-T) % bk
    if s_pad:
        q = jnp.pad(q, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
    if t_pad:
        k = jnp.pad(k, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
    Sp, Tp = S + s_pad, T + t_pad

    win = jnp.int32(2 ** 30) if window is None else jnp.asarray(window, jnp.int32)
    scalars = jnp.stack([jnp.asarray(q_offset, jnp.int32), win,
                         jnp.asarray(T, jnp.int32)])

    grid = (B, H, Sp // bq, Tp // bk)
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, scale=hd ** -0.5)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, 1, hd), lambda b, h, i, j, s: (b, i, h, 0)),
                pl.BlockSpec((1, bk, 1, hd), lambda b, h, i, j, s: (b, j, h // G, 0)),
                pl.BlockSpec((1, bk, 1, hd), lambda b, h, i, j, s: (b, j, h // G, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, 1, hd), lambda b, h, i, j, s: (b, i, h, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, hd), jnp.float32),   # acc
                pltpu.VMEM((bq,), jnp.float32),      # running max m
                pltpu.VMEM((bq,), jnp.float32),      # normalizer l
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Sp, H, hd), q.dtype),
        interpret=interpret,
    )(scalars, q, k, v)
    return out[:, :S]
