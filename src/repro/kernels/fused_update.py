"""Fused FL-update Pallas kernels over FlatView buffers.

The federated hot loop spends its non-matmul time in parameter-space
algebra: every local SGD step runs clip → (scaffold) correction →
decoupled weight decay → momentum → axpy over the whole model, and every
round runs a weighted delta aggregation plus an optional server-moment
update (FedAvgM / FedAdam).  Leaf-wise ``tree_map`` makes each of those
O(n_leaves) ops; these kernels run them as ONE blocked pass over the
contiguous per-dtype buffers produced by
``repro.utils.flatten.FlatView``.

Kernels (all elementwise / VPU-bound, blocked (rows, 128) over the flat
buffer, f32 compute, cast on store):

  local_step      — the whole client step tail:
                      g ← g·clip_scale (+ c) (+ wd·p)
                      m ← g + β·m            (momentum, optional)
                      p ← p − step·(m or g)
  weighted_delta  — FedAvg aggregation over a stacked (K, N) buffer:
                      p ← cast(p₃₂ + Σₖ w̄ₖ·(wₖ − p) (+ e))
                    ``e`` is an optional f32 extra operand folded into
                    the same pass — the round's DP noise / secure-agg
                    mask total rides the aggregation kernel for free.
  compress_delta  — the compressed-communication form of the client
                    upload, one pass:
                      c ← quantize(topk(d));  r ← d − c
                    magnitude top-k masking at a prefetched threshold τ
                    plus blockwise symmetric int8/int16 fake
                    quantization (per 128-lane-block bf16 scales); the
                    optional residual r is the error-feedback carry.
  dp_clip_noise   — the privacy form of the client upload, one pass:
                      u ← clip_scale·d₃₂ (+ noise_scale·z)
                    clip_scale = min(1, C/‖d‖) clips the client delta to
                    the DP bound C; z is a standard-normal buffer and
                    noise_scale = σ·C calibrates the Gaussian mechanism.
  delta_accum     — the pod backend's sequential form, one client:
                      d ← d + coeff·(w₃₂ − p₃₂)
  server_update   — server optimizer on the pseudo-gradient g = −delta:
                      none     : p ← cast(p₃₂ + d)
                      momentum : m ← β·m + g;  p ← p − lr·m      (FedAvgM)
                      adam     : μ,ν moments + bias-corrected step (FedAdam)

Traced scalars (clip scale, step size, lr, bias corrections) ride a
scalar-prefetch operand in SMEM — same pattern as
``repro.kernels.flash_attention``.  Static algorithm constants (weight
decay, momentum, Adam betas) are compile-time kernel parameters, so
disabled terms cost nothing.

Buffers are 1-D; the wrappers pad to a (rows, 128) grid of
``block_rows``-row tiles and strip the pad on return — pad lanes stay
zero through every op above, so chaining kernels over padded buffers is
safe.  A wrapper whose operand length is not already on the grid pays a
whole-buffer pad copy per operand (and a slice copy per output), which
at model scale costs more HBM than the kernel itself.  Callers that
carry buffers across many kernel calls (the engine's FlatParamOps chunk
carries, the pod's per-shard buckets) therefore pre-pad them once, at
placement time, to :func:`padded_len` — a length on BOTH grids the
wrappers use: the one-block grid of interpret mode and the
``DEFAULT_BLOCK_ROWS``-row grid Mosaic compiles on TPU.  ``_pad_rows``
is then a reshape and the trailing ``[:n]`` strip a no-op on every
call.  The kernels lower to Mosaic on TPU; with ``interpret=True`` the
same code runs in the Pallas interpreter on any backend, which is how
the tests check them against the tree_math oracles on CPU
(tests/test_fused_update.py).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_BLOCK_ROWS = 512        # 512×128 f32 = 256 KB per operand tile
# one (8, 128) sublane×lane tile: the row alignment of both grids
GRID_ALIGN = 8 * LANES


def padded_len(n: int, block_rows: int = DEFAULT_BLOCK_ROWS) -> int:
    """Smallest length ≥ n that :func:`_grid_rows` tiles with no pad,
    interpreted or compiled: a GRID_ALIGN multiple while the buffer fits
    one ``block_rows`` block (the block clamps to the buffer), else a
    multiple of the whole ``block_rows × 128`` block."""
    if n == 0:
        return 0
    align = GRID_ALIGN if n <= block_rows * LANES else block_rows * LANES
    return -(-n // align) * align


def _grid_rows(n: int, block_rows: int, interpret: bool) -> Tuple[int, int]:
    """(padded_rows, n_blocks) for an n-element 1-D buffer: rows pad to
    a sublane multiple (8), then to a whole number of row-blocks, with
    the block clamped for small buffers so tiny models don't pay a full
    512-row tile.  The block size bounds VMEM residency on TPU; the
    interpreter has no VMEM, and per-block iteration is its dominant
    cost, so interpret mode always runs ONE whole-buffer block."""
    rows = -(-n // LANES)
    rows8 = -(-rows // 8) * 8
    br = rows8 if interpret else min(block_rows, rows8)
    rows_p = -(-rows8 // br) * br
    return rows_p, rows_p // br


def _pad_rows(buf: jnp.ndarray, rows_p: int) -> jnp.ndarray:
    pad = rows_p * LANES - buf.shape[-1]
    if pad:
        widths = [(0, 0)] * (buf.ndim - 1) + [(0, pad)]
        buf = jnp.pad(buf, widths)
    return buf.reshape(buf.shape[:-1] + (rows_p, LANES))


# ---------------------------------------------------------------------------
# local step tail
# ---------------------------------------------------------------------------

def _local_step_kernel(sc_ref, *refs, wd: float, beta: float,
                       has_m: bool, has_c: bool):
    clip_scale = sc_ref[0]
    step_size = sc_ref[1]
    it = iter(refs)
    p_ref, g_ref = next(it), next(it)
    m_ref = next(it) if has_m else None
    c_ref = next(it) if has_c else None
    p_out = next(it)
    m_out = next(it) if has_m else None

    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32) * clip_scale
    if has_c:
        g = g + c_ref[...].astype(jnp.float32)
    if wd:
        g = g + wd * p
    if has_m:
        m = g + beta * m_ref[...].astype(jnp.float32)
        m_out[...] = m.astype(m_out.dtype)
        eff = m
    else:
        eff = g
    p_out[...] = (p - step_size * eff).astype(p_out.dtype)


def local_step(p: jnp.ndarray, g: jnp.ndarray,
               m: Optional[jnp.ndarray], c: Optional[jnp.ndarray],
               clip_scale, step_size, *, weight_decay: float = 0.0,
               momentum: float = 0.0, block_rows: int = DEFAULT_BLOCK_ROWS,
               interpret: bool = False):
    """One fused client SGD step over a 1-D flat buffer.

    Returns ``(p_new, m_new)`` (``m_new`` is None when ``m`` is).  The
    op order matches repro.fl.local's tree path exactly: the RAW
    gradient is pre-scaled by ``clip_scale``, then the scaffold
    correction ``c`` is added, then decoupled weight decay, then the
    heavy-ball momentum update, then the axpy with ``step_size`` =
    lr · lr_scale.
    """
    n = p.shape[-1]
    has_m, has_c = m is not None, c is not None
    if n == 0:                       # zero-size dtype bucket: nothing to do
        return p, m
    rows_p, n_blocks = _grid_rows(n, block_rows, interpret)
    br = rows_p // n_blocks
    operands = [_pad_rows(x, rows_p)
                for x in (p, g) + ((m,) if has_m else ()) +
                ((c,) if has_c else ())]
    scalars = jnp.stack([jnp.asarray(clip_scale, jnp.float32),
                         jnp.asarray(step_size, jnp.float32)])
    out_shape = [jax.ShapeDtypeStruct((rows_p, LANES), p.dtype)]
    if has_m:
        out_shape.append(jax.ShapeDtypeStruct((rows_p, LANES), m.dtype))
    kernel = functools.partial(_local_step_kernel, wd=float(weight_decay),
                               beta=float(momentum), has_m=has_m,
                               has_c=has_c)
    blk = pl.BlockSpec((br, LANES), lambda i, sc: (i, 0))
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks,),
            in_specs=[blk] * len(operands),
            out_specs=[blk] * len(out_shape),
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(scalars, *operands)
    p_new = outs[0].reshape(-1)[:n]
    m_new = outs[1].reshape(-1)[:n] if has_m else None
    return p_new, m_new


# ---------------------------------------------------------------------------
# weighted delta aggregation (host engine, all clients at once)
# ---------------------------------------------------------------------------

def _weighted_delta_kernel(w_ref, *refs, K: int, has_extra: bool,
                           deltas: bool):
    it = iter(refs)
    s_ref, p_ref = next(it), next(it)
    e_ref = next(it) if has_extra else None
    o_ref = next(it)
    p = p_ref[...].astype(jnp.float32)
    acc = e_ref[...] if has_extra else jnp.zeros_like(p)
    for k in range(K):                      # K is static and small
        if deltas:
            acc = acc + w_ref[k] * s_ref[k].astype(jnp.float32)
        else:
            acc = acc + w_ref[k] * (s_ref[k].astype(jnp.float32) - p)
    o_ref[...] = (p + acc).astype(o_ref.dtype)


def weighted_delta(stacked: jnp.ndarray, p: jnp.ndarray,
                   weights: jnp.ndarray, *,
                   extra: Optional[jnp.ndarray] = None,
                   deltas: bool = False,
                   block_rows: int = DEFAULT_BLOCK_ROWS,
                   interpret: bool = False) -> jnp.ndarray:
    """FedAvg aggregation: ``p₃₂ + Σₖ w̄ₖ·(stacked[k] − p) (+ extra)``
    cast back to ``p.dtype``.  ``stacked`` is (K, N), ``weights`` the
    (K,) normalized client weights (must sum to 1 for the
    convex-combination reading; per-client DP clip scales fold into
    them).  ``extra`` is an optional f32 (N,) buffer added inside the
    same pass — the round's aggregated DP noise + secure-agg mask term —
    so privacy costs zero additional traversals here.  ``deltas=True``
    (static) reads ``stacked`` as already-formed client DELTAS
    ``cₖ = compress(wₖ − p)`` and drops the per-term ``− p``:
    ``p₃₂ + Σₖ w̄ₖ·cₖ`` — the compressed-communication aggregate."""
    K, n = stacked.shape
    if n == 0:
        return p
    has_extra = extra is not None
    rows_p, n_blocks = _grid_rows(n, block_rows, interpret)
    br = rows_p // n_blocks
    blk = pl.BlockSpec((br, LANES), lambda i, sc: (i, 0))
    operands = [_pad_rows(stacked, rows_p), _pad_rows(p, rows_p)]
    if has_extra:
        operands.append(_pad_rows(extra, rows_p))
    outs = pl.pallas_call(
        functools.partial(_weighted_delta_kernel, K=K, has_extra=has_extra,
                          deltas=deltas),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec((K, br, LANES), lambda i, sc: (0, i, 0))] +
                     [blk] * (len(operands) - 1),
            out_specs=blk,
        ),
        out_shape=jax.ShapeDtypeStruct((rows_p, LANES), p.dtype),
        interpret=interpret,
    )(weights.astype(jnp.float32), *operands)
    return outs.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# compressed-communication client upload: top-k mask + blockwise quantize
# ---------------------------------------------------------------------------

# blockwise-symmetric quantization constants.  Wire scales are bf16 (the
# 2-byte-per-128-lane-block format the payload accounting assumes); the
# f32 scale is nudged UP by SCALE_PAD before the bf16 round-to-nearest
# so the stored scale is always ≥ amax/qmax — quantized magnitudes then
# never exceed qmax (no clipping distortion) and the per-element error
# stays ≤ scale/2 for the WIRE scale.  bf16's 8 mantissa-free relative
# step is 2⁻⁸; 1 + 2⁻⁶ dominates it with margin.
QMAX = {8: 127.0, 16: 32767.0}
SCALE_PAD = 1.0 + 2.0 ** -6


def _compress_delta_kernel(sc_ref, *refs, bits: int, topk: bool,
                           with_residual: bool):
    it = iter(refs)
    d_ref = next(it)
    o_ref = next(it)
    r_ref = next(it) if with_residual else None
    d0 = d_ref[...].astype(jnp.float32)
    d = d0
    if topk:
        tau = sc_ref[0]
        d = jnp.where(jnp.abs(d) >= tau, d, 0.0)
    if bits != 32:
        qmax = QMAX[bits]
        amax = jnp.max(jnp.abs(d), axis=-1, keepdims=True)
        scale = (amax / qmax) * SCALE_PAD
        scale = scale.astype(jnp.bfloat16).astype(jnp.float32)
        q = jnp.where(scale > 0.0, d / jnp.where(scale > 0.0, scale, 1.0),
                      0.0)
        q = jnp.clip(jnp.round(q), -qmax, qmax)
        c = q * scale
    else:
        c = d
    o_ref[...] = c.astype(o_ref.dtype)
    if with_residual:
        r_ref[...] = (d0 - c).astype(r_ref.dtype)


def compress_delta(d: jnp.ndarray, thresh, *, bits: int = 32,
                   topk: bool = False, with_residual: bool = False,
                   block_rows: int = DEFAULT_BLOCK_ROWS,
                   interpret: bool = False):
    """Fake-quantized compressed form of one client's f32 flat delta —
    exactly the values a decompressed wire payload would carry, in ONE
    blocked pass:

      1. ``topk`` (static): magnitude sparsification ``d ← d·[|d| ≥ τ]``
         with the traced threshold ``τ = thresh`` (the caller's k-th
         largest |d|; ties at τ are kept, matching the threshold
         semantics of the NumPy oracle).
      2. ``bits ∈ {8, 16}`` (static): blockwise symmetric quantization —
         per 128-lane row, ``scale = bf16((amax/qmax)·(1+2⁻⁶))`` and
         ``c = round(d/scale)·scale`` (round half-even, clip ±qmax);
         all-zero rows keep scale 0 and emit zeros.  ``bits=32`` skips
         quantization statically.

    Returns ``c`` (f32, same length), plus the error-feedback residual
    ``r = d − c`` (f32) when ``with_residual`` — computed against the
    ORIGINAL delta, so sparsified-away mass lands in the residual.  Pad
    lanes are zero in, zero out: zero rows quantize to zero and zero
    elements always survive the ≥-threshold mask as zeros."""
    n = d.shape[-1]
    if bits not in (8, 16, 32):
        raise ValueError(f"compress_delta bits must be 8|16|32, got {bits}")
    if n == 0:
        out = d.astype(jnp.float32)
        return (out, jnp.zeros_like(out)) if with_residual else out
    rows_p, n_blocks = _grid_rows(n, block_rows, interpret)
    br = rows_p // n_blocks
    blk = pl.BlockSpec((br, LANES), lambda i, sc: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((rows_p, LANES), jnp.float32)]
    if with_residual:
        out_shape.append(jax.ShapeDtypeStruct((rows_p, LANES), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_compress_delta_kernel, bits=bits, topk=topk,
                          with_residual=with_residual),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks,),
            in_specs=[blk],
            out_specs=[blk] * len(out_shape),
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(jnp.asarray(thresh, jnp.float32).reshape(1), _pad_rows(d, rows_p))
    c = outs[0].reshape(-1)[:n]
    if with_residual:
        return c, outs[1].reshape(-1)[:n]
    return c


# ---------------------------------------------------------------------------
# DP clip + noise — the privacy form of one client's upload
# ---------------------------------------------------------------------------

def _dp_clip_noise_kernel(sc_ref, *refs, has_z: bool):
    it = iter(refs)
    d_ref = next(it)
    z_ref = next(it) if has_z else None
    o_ref = next(it)
    u = sc_ref[0] * d_ref[...].astype(jnp.float32)
    if has_z:
        u = u + sc_ref[1] * z_ref[...]
    o_ref[...] = u.astype(o_ref.dtype)


def dp_clip_noise(d: jnp.ndarray, z: Optional[jnp.ndarray],
                  clip_scale, noise_scale, *,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  interpret: bool = False) -> jnp.ndarray:
    """One client's DP upload in ONE blocked pass:
    ``u = clip_scale·d₃₂ (+ noise_scale·z)`` returned as f32.

    ``clip_scale`` is the traced ``min(1, C/(‖d‖+ε))`` factor that clips
    the delta to the sensitivity bound C, and ``noise_scale`` the
    calibrated ``σ·C`` Gaussian multiplier for the standard-normal f32
    buffer ``z`` (``z=None`` statically drops the noise term — pure
    clipping costs the same single pass).  Pad lanes stay zero: both
    terms are multiplicative in zero-padded operands."""
    n = d.shape[-1]
    has_z = z is not None
    if n == 0:
        return d.astype(jnp.float32)
    rows_p, n_blocks = _grid_rows(n, block_rows, interpret)
    br = rows_p // n_blocks
    blk = pl.BlockSpec((br, LANES), lambda i, sc: (i, 0))
    operands = [_pad_rows(d, rows_p)]
    if has_z:
        operands.append(_pad_rows(z, rows_p))
    scalars = jnp.stack([jnp.asarray(clip_scale, jnp.float32),
                         jnp.asarray(noise_scale, jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_dp_clip_noise_kernel, has_z=has_z),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks,),
            in_specs=[blk] * len(operands),
            out_specs=blk,
        ),
        out_shape=jax.ShapeDtypeStruct((rows_p, LANES), jnp.float32),
        interpret=interpret,
    )(scalars, *operands)
    return out.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# sequential delta accumulation (pod backend, one client per call)
# ---------------------------------------------------------------------------

def _delta_accum_kernel(sc_ref, d_ref, w_ref, p_ref, o_ref):
    coeff = sc_ref[0]
    o_ref[...] = d_ref[...] + coeff * (
        w_ref[...].astype(jnp.float32) - p_ref[...].astype(jnp.float32))


def _weighted_accum_kernel(sc_ref, d_ref, w_ref, o_ref):
    coeff = sc_ref[0]
    o_ref[...] = d_ref[...] + coeff * w_ref[...].astype(jnp.float32)


def delta_accum(delta: jnp.ndarray, w_end: jnp.ndarray,
                p: Optional[jnp.ndarray], coeff, *,
                block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret: bool = False) -> jnp.ndarray:
    """``delta + coeff·(w_end₃₂ − p₃₂)`` — one client's contribution to
    the running f32 weighted-delta sum (the pod FedAvg all-reduce).

    ``p=None`` is the ACCUM-ONLY form ``delta + coeff·w_end₃₂``: the
    hierarchical psum path keeps its per-lane partials p-free (the
    ``−(Σcoeff)·p`` term factors out of the lane sums and is applied
    once after the cross-pod combine), so the lane accumulator never
    needs the params resident per lane."""
    n = delta.shape[-1]
    if n == 0:
        return delta
    rows_p, n_blocks = _grid_rows(n, block_rows, interpret)
    br = rows_p // n_blocks
    blk = pl.BlockSpec((br, LANES), lambda i, sc: (i, 0))
    kernel = _delta_accum_kernel if p is not None else _weighted_accum_kernel
    operands = [_pad_rows(delta, rows_p), _pad_rows(w_end, rows_p)]
    if p is not None:
        operands.append(_pad_rows(p, rows_p))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks,),
            in_specs=[blk] * len(operands),
            out_specs=blk,
        ),
        out_shape=jax.ShapeDtypeStruct((rows_p, LANES), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(coeff, jnp.float32).reshape(1), *operands)
    return out.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# server update (apply delta + FedAvgM / FedAdam moments)
# ---------------------------------------------------------------------------

def _server_update_kernel(sc_ref, *refs, opt: str, beta: float, b1: float,
                          b2: float, eps: float):
    lr = sc_ref[0]
    it = iter(refs)
    p_ref, d_ref = next(it), next(it)
    p = p_ref[...].astype(jnp.float32)
    d = d_ref[...]
    if opt == "none":
        next(it)[...] = (p + d).astype(p_ref.dtype)
        return
    g = -d                                   # pseudo-gradient w − w_avg
    if opt == "momentum":
        m_ref = next(it)
        p_out, m_out = next(it), next(it)
        m = beta * m_ref[...].astype(jnp.float32) + g
        m_out[...] = m.astype(m_out.dtype)
        p_out[...] = (p - lr * m).astype(p_out.dtype)
        return
    # adam — bias corrections arrive precomputed as scalars
    bc1, bc2 = sc_ref[1], sc_ref[2]
    mu_ref, nu_ref = next(it), next(it)
    p_out, mu_out, nu_out = next(it), next(it), next(it)
    mu = b1 * mu_ref[...].astype(jnp.float32) + (1.0 - b1) * g
    nu = b2 * nu_ref[...].astype(jnp.float32) + (1.0 - b2) * g * g
    mu_out[...] = mu.astype(mu_out.dtype)
    nu_out[...] = nu.astype(nu_out.dtype)
    u = (mu / bc1) / (jnp.sqrt(nu / bc2) + eps)
    p_out[...] = (p - lr * u).astype(p_out.dtype)


def server_update(p: jnp.ndarray, delta: jnp.ndarray,
                  moments: Tuple[jnp.ndarray, ...], scalars, *,
                  opt: str = "none", beta: float = 0.9, b1: float = 0.9,
                  b2: float = 0.99, eps: float = 1e-8,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  interpret: bool = False):
    """Apply the aggregated f32 ``delta`` to ``p`` under a server
    optimizer.  ``moments`` is () for "none", (m,) for "momentum",
    (mu, nu) for "adam"; ``scalars`` is (lr,) or (lr, bc1, bc2) for adam
    (bias corrections 1−b1^t, 1−b2^t computed by the caller, where the
    step count lives).  Returns ``(p_new, new_moments)``.
    """
    if opt not in ("none", "momentum", "adam"):
        raise ValueError(f"unknown server opt {opt!r}")
    n = p.shape[-1]
    if n == 0:
        return p, tuple(moments)
    rows_p, n_blocks = _grid_rows(n, block_rows, interpret)
    br = rows_p // n_blocks
    blk = pl.BlockSpec((br, LANES), lambda i, sc: (i, 0))
    operands = [_pad_rows(p, rows_p), _pad_rows(delta, rows_p)] + \
        [_pad_rows(m, rows_p) for m in moments]
    out_shape = [jax.ShapeDtypeStruct((rows_p, LANES), p.dtype)] + \
        [jax.ShapeDtypeStruct((rows_p, LANES), m.dtype) for m in moments]
    sc = jnp.stack([jnp.asarray(s, jnp.float32) for s in scalars])
    outs = pl.pallas_call(
        functools.partial(_server_update_kernel, opt=opt, beta=float(beta),
                          b1=float(b1), b2=float(b2), eps=float(eps)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks,),
            in_specs=[blk] * len(operands),
            out_specs=[blk] * len(out_shape),
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(sc, *operands)
    p_new = outs[0].reshape(-1)[:n]
    new_moments = tuple(o.reshape(-1)[:n] for o in outs[1:])
    return p_new, new_moments
