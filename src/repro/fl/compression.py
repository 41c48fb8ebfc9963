"""Compressed client→server communication — blockwise symmetric
quantization + magnitude top-k sparsification with error feedback,
shared by the host and pod backends.

CyclicFL's own Table IV analysis makes per-round communication volume
THE cost model of the system; this module compresses the only payload
that actually scales with the model — the P2 client upload
``δᵢ = wᵢ − w`` — before it enters the round aggregate, so the
aggregation consumes exactly the values a decompressed wire payload
would carry.

The mechanism (:class:`CompressionSpec`), applied per flat per-dtype
bucket (``repro.utils.flatten.FlatView`` / ``ShardedFlatView``):

top-k sparsification (``density < 1``)
    Keep the ``k = max(1, ceil(density·n))`` largest-magnitude elements
    of the bucket, zero the rest.  Implemented as a THRESHOLD mask
    ``d·[|d| ≥ τ]`` with ``τ`` = the k-th largest ``|d|`` — ties at τ
    are all kept, which keeps the kernel one elementwise pass and makes
    the pod's shard-local form exact (each shard thresholds its own
    ``k`` over its own ``per_shard`` elements: zero collectives).

blockwise symmetric quantization (``bits ∈ {8, 16}``)
    Per 128-lane block, ``scale = bf16((amax/qmax)·SCALE_PAD)`` and
    ``c = round(d/scale)·scale`` (round half-even, clip ±qmax).  Scales
    ship as bf16 — 2 bytes per 128 elements — because the padded-up
    cast guarantees ``scale ≥ amax/qmax`` (no clipping distortion,
    per-element error ≤ scale/2) while keeping the int8 payload ratio
    at 4/(1 + 2/128) ≈ 3.94×; f32 scales would cap it at 3.88×.

error feedback (``error_feedback=True``)
    The compression error ``r = δ − compress(δ + r_prev)`` is carried
    per client and added to the NEXT round's delta before compression,
    so sparsified/quantized-away mass is deferred, not lost (SEC-style
    memory).  Residuals are per-client flat f32 rows behind the
    unchanged ClientStateStore contract (``algo_state["ef_residuals"]``)
    — dense, sparse and sharded-sparse stores all carry them, so they
    survive LRU eviction/host spill at 10^6-client scale.

The identity spec (``bits=32, density=1.0``) is STATICALLY off —
``compression_on`` returns False and every caller keeps the exact
baseline program, bitwise (tests/test_compression.py).  Lossy
compression composes with neither ``secure_agg`` (pairwise masks cancel
only over exact-real uploads) nor DP (the sensitivity bound is
certified on the exact clipped delta) — both are rejected at spec
construction (:func:`validate_compression`).

Parity chain: :func:`numpy_compress` (host NumPy, the ground-truth
oracle) == :func:`reference_compress` (pure jnp) ==
``repro.kernels.fused_update.compress_delta`` (the blocked Pallas
kernel), bitwise; :func:`tree_compressed_aggregate` is the engine-level
reference the fused host aggregate must match bitwise.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fused_update import LANES, QMAX, SCALE_PAD

Pytree = Any

# bytes per wire element: quantized values ship at bits/8, coordinates
# of surviving top-k elements as int32, block scales as bf16
_INDEX_BYTES = 4
_SCALE_BYTES = 2
_FULL_BYTES = 4                 # uncompressed deltas ship as f32


@dataclasses.dataclass(frozen=True)
class CompressionSpec:
    """Static compressed-communication parameters for the P2 upload.

    Frozen + hashable so it rides ``LocalSpec`` through the engine's
    lru-cached strategy/chunk builders.  ``bits=32, density=1.0`` is the
    identity spec — statically OFF, callers keep the exact baseline
    program (the same contract as ``DPSpec(inf, 0)``).
    """
    bits: int = 32
    density: float = 1.0
    error_feedback: bool = False

    def __post_init__(self):
        if self.bits not in (8, 16, 32):
            raise ValueError(f"compression bits must be one of 8|16|32, "
                             f"got {self.bits!r}")
        if not (0.0 < self.density <= 1.0):
            raise ValueError(f"compression density must be in (0, 1], "
                             f"got {self.density!r}")
        if self.error_feedback and self.identity:
            raise ValueError(
                "error_feedback=True needs lossy compression (bits<32 or "
                "density<1): the identity spec has a zero residual by "
                "definition")

    @property
    def quantizes(self) -> bool:
        return self.bits != 32

    @property
    def sparsifies(self) -> bool:
        return self.density < 1.0

    @property
    def identity(self) -> bool:
        """Statically-off spec: no quantization, no sparsification."""
        return not self.quantizes and not self.sparsifies

    @property
    def lossy(self) -> bool:
        return not self.identity


def compression_on(spec: Optional[CompressionSpec]) -> bool:
    """Whether the round aggregate needs the compressed path at all —
    None and the identity spec both compile to the exact baseline."""
    return spec is not None and spec.lossy


def validate_compression(spec: Optional[CompressionSpec], *,
                         dp=None, secure_agg: bool = False) -> None:
    """Reject invalid mechanism combinations at construction time
    (mirrors ``repro.fl.local.validate_update_impl``: fail loudly at the
    spec, not deep inside a traced round body)."""
    if not compression_on(spec):
        return
    if secure_agg:
        raise ValueError(
            "secure_agg=True is incompatible with lossy compression "
            "(bits<32 or density<1): pairwise masks cancel only over "
            "exact-real uploads — quantizing or sparsifying the masked "
            "field breaks the telescoping sum (see docs/ARCHITECTURE.md, "
            "'Compressed communication')")
    if dp is not None:
        raise ValueError(
            "dp is incompatible with lossy compression: the DP "
            "sensitivity bound is certified on the exact clipped delta, "
            "not its quantized form — run DP-FedAvg uncompressed or "
            "compression without DP")


# ---------------------------------------------------------------------------
# top-k threshold
# ---------------------------------------------------------------------------

def topk_k(spec: CompressionSpec, n: int) -> int:
    """Elements kept per bucket of LOGICAL size n (never 0, never > n)."""
    return min(n, max(1, int(math.ceil(spec.density * n))))


def topk_threshold(d: jnp.ndarray, k: int) -> jnp.ndarray:
    """τ = the k-th largest |d| (traced), the exact value
    ``np.partition(|d|, n-k)[n-k]`` selects.  Appending zero padding to
    ``d`` never changes τ as long as ``k`` counts LOGICAL elements, so
    callers may pass carry-padded buffers with a logical ``k``.

    Selection runs as a 31-step binary search over the IEEE-754 bit
    space: |x| is non-negative, so its uint32 pattern orders like the
    float and the greedy MSB→LSB prefix with ``count(bits ≥ t) ≥ k``
    lands exactly on the k-th largest element's bits.  Each step is one
    vectorized compare-and-count pass — O(31·n) streaming work instead
    of ``lax.top_k``'s O(n·log n) sort, whose CPU lowering costs more
    than an entire fused round at benchmark sizes."""
    a = jnp.abs(d.reshape(-1).astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)

    def step(prefix, shift):
        cand = prefix | (jnp.uint32(1) << shift)
        keep = jnp.sum(bits >= cand) >= jnp.uint32(k)
        return jnp.where(keep, cand, prefix), None

    prefix, _ = jax.lax.scan(step, jnp.uint32(0),
                             jnp.arange(30, -1, -1, dtype=jnp.uint32))
    return jax.lax.bitcast_convert_type(prefix, jnp.float32)


# ---------------------------------------------------------------------------
# reference compressors — jnp twin and NumPy ground truth of the kernel
# ---------------------------------------------------------------------------

def reference_compress(d: jnp.ndarray, spec: CompressionSpec, *,
                       logical_size: Optional[int] = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pure-jnp twin of ``repro.kernels.ops.fused_compress_delta`` —
    bitwise equal to the kernel in interpret mode (same elementwise f32
    ops over the same 128-lane block boundaries; zero padding to the
    kernel grid changes neither block scales nor τ).  Returns ``(c, r)``
    with the residual against the ORIGINAL delta.  ``logical_size``
    overrides the top-k population when ``d`` carries trailing zero
    padding."""
    n = d.shape[-1]
    d32 = d.astype(jnp.float32)
    x = d32
    if spec.sparsifies:
        tau = topk_threshold(d32, topk_k(spec, logical_size or n))
        x = jnp.where(jnp.abs(x) >= tau, x, 0.0)
    if spec.quantizes:
        rows = -(-n // LANES)
        xb = jnp.pad(x, (0, rows * LANES - n)).reshape(rows, LANES)
        qmax = QMAX[spec.bits]
        amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
        scale = ((amax / qmax) * SCALE_PAD) \
            .astype(jnp.bfloat16).astype(jnp.float32)
        q = jnp.where(scale > 0.0, xb / jnp.where(scale > 0.0, scale, 1.0),
                      0.0)
        q = jnp.clip(jnp.round(q), -qmax, qmax)
        x = (q * scale).reshape(-1)[:n]
    return x, d32 - x


def numpy_compress(d: np.ndarray, spec: CompressionSpec, *,
                   logical_size: Optional[int] = None
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Host-NumPy ground truth of the compress kernel (bitwise: same f32
    elementwise ops, same half-even rounding, same bf16 scale cast via
    ml_dtypes).  The parity anchor of tests/test_compression.py."""
    import ml_dtypes                       # ships with jax
    d = np.asarray(d, np.float32)
    n = d.shape[-1]
    x = d
    if spec.sparsifies:
        k = topk_k(spec, logical_size or n)
        a = np.abs(d)
        tau = np.partition(a, a.size - k)[a.size - k]
        x = np.where(a >= tau, x, np.float32(0.0))
    if spec.quantizes:
        rows = -(-n // LANES)
        xb = np.pad(x, (0, rows * LANES - n)).reshape(rows, LANES)
        qmax = np.float32(QMAX[spec.bits])
        amax = np.max(np.abs(xb), axis=-1, keepdims=True)
        scale = ((amax / qmax) * np.float32(SCALE_PAD)) \
            .astype(ml_dtypes.bfloat16).astype(np.float32)
        q = np.where(scale > 0.0, xb / np.where(scale > 0.0, scale,
                                                np.float32(1.0)),
                     np.float32(0.0))
        q = np.clip(np.round(q), -qmax, qmax)
        x = (q * scale).reshape(-1)[:n].astype(np.float32)
    return x, (d - x).astype(np.float32)


# ---------------------------------------------------------------------------
# wire accounting — the closed-form payload the ledger checks against
# ---------------------------------------------------------------------------

def payload_bytes(spec: Optional[CompressionSpec], sizes) -> int:
    """Closed-form wire bytes of ONE client's upload over the per-bucket
    LOGICAL element counts ``sizes`` (deltas ship as f32 when
    uncompressed).  Per lossy bucket: kept values at ``bits/8`` bytes,
    an int32 coordinate per kept value when sparsified, and one bf16
    scale per 128-lane block when quantized."""
    total = 0
    for n in sizes:
        if n == 0:
            continue
        if not compression_on(spec):
            total += _FULL_BYTES * n
            continue
        k = topk_k(spec, n) if spec.sparsifies else n
        total += k * (spec.bits // 8)
        if spec.sparsifies:
            total += _INDEX_BYTES * k
        if spec.quantizes:
            total += _SCALE_BYTES * (-(-n // LANES))
    return int(total)


def payload_ratio(spec: Optional[CompressionSpec], sizes) -> float:
    """Uncompressed-over-compressed upload bytes (1.0 when off)."""
    comp = payload_bytes(spec, sizes)
    full = _FULL_BYTES * sum(sizes)
    return (full / comp) if comp else 1.0


# ---------------------------------------------------------------------------
# round aggregates (host engine) — flat-reference oracle and fused twin
# ---------------------------------------------------------------------------

def tree_compressed_aggregate(spec: CompressionSpec, view, params: Pytree,
                              w_locals: Pytree, weights: jnp.ndarray,
                              residuals: Optional[Dict[str, jnp.ndarray]]
                              = None):
    """The compressed FedAvg aggregate on the TREE path — the parity
    reference for the fused twin.  Compression is defined on the flat
    per-dtype buckets (block boundaries are a property of the packing,
    not of any leaf), so the reference flattens through the SAME
    ``FlatView`` the fused path uses, compresses each client's delta
    with :func:`reference_compress`, and aggregates
    ``cast(p₃₂ + Σₖ w̄ₖ·cₖ)`` in the kernel's accumulation order —
    bitwise equal to the fused host aggregate.

    ``residuals`` (error feedback) is the gathered per-client rows
    ``{bucket: (K, n)}``; returns ``(new_params_tree, new_residuals)``
    with ``new_residuals=None`` when error feedback is off."""
    wbar = (weights / jnp.sum(weights)).astype(jnp.float32)
    p_bufs = view.flatten(params)
    stacked = view.flatten_stacked(w_locals)
    new_p: Dict[str, jnp.ndarray] = {}
    new_r: Dict[str, jnp.ndarray] = {}
    for name, s in stacked.items():
        p32 = p_bufs[name].astype(jnp.float32)
        d = s.astype(jnp.float32) - p32[None]
        if residuals is not None:
            d = d + residuals[name]
        K = d.shape[0]
        cs, rs = [], []
        for k in range(K):                 # K is static and small
            c, r = reference_compress(d[k], spec)
            cs.append(c)
            rs.append(r)
        acc = jnp.zeros_like(p32)
        for k in range(K):                 # kernel accumulation order
            acc = acc + wbar[k] * cs[k]
        new_p[name] = (p32 + acc).astype(p_bufs[name].dtype)
        new_r[name] = jnp.stack(rs)
    out_params = view.unflatten(new_p)
    return out_params, (new_r if spec.error_feedback else None)


def fused_compressed_aggregate(spec: CompressionSpec, fops,
                               p_bufs: Dict[str, jnp.ndarray],
                               stacked_bufs: Dict[str, jnp.ndarray],
                               weights: jnp.ndarray,
                               residuals: Optional[Dict[str, jnp.ndarray]]
                               = None):
    """The compressed aggregate on the flat path: per client,
    ``δₖ = stacked[k] − p (+ rₖ)`` → ``(cₖ, rₖ′) = compress(δₖ)``
    (vmapped over K — one blocked kernel pass per bucket per client),
    then ONE ``weighted_delta(deltas=True)`` pass consumes the stacked
    compressed deltas: ``cast(p₃₂ + Σₖ w̄ₖ·cₖ)``.  Returns
    ``(new_p_bufs, new_residual_rows-or-None)``."""
    wbar = (weights / jnp.sum(weights)).astype(jnp.float32)

    def one_client(w_row, r_row):
        d = {name: w_row[name].astype(jnp.float32) -
             p_bufs[name].astype(jnp.float32) for name in w_row}
        if r_row is not None:
            d = {name: d[name] + r_row[name] for name in d}
        return fops.compress_delta(d, spec)

    if residuals is None:
        c_stacked, r_new = jax.vmap(lambda w: one_client(w, None))(
            stacked_bufs)
    else:
        c_stacked, r_new = jax.vmap(one_client)(stacked_bufs, residuals)
    new_p = fops.weighted_delta(p_bufs, c_stacked, wbar, deltas=True)
    return new_p, (r_new if spec.error_feedback else None)
