"""Pure-jnp oracles for the Pallas kernels.

These are the correctness references the kernel tests sweep against
(``assert_allclose`` over shapes/dtypes, kernels in interpret mode).
They are deliberately naive-but-exact; repro.models uses its own fused
XLA paths in production mode.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, window: Optional[int] = None,
                  q_offset: int = 0) -> jnp.ndarray:
    """Dense attention oracle.

    q: (B, S, H, hd); k/v: (B, T, KH, hd) with H % KH == 0 (GQA).
    window w keeps keys with qpos - w < kpos <= qpos.
    """
    B, S, H, hd = q.shape
    T, KH = k.shape[1], k.shape[2]
    G = H // KH
    qg = q.reshape(B, S, KH, G, hd).astype(jnp.float32)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k.astype(jnp.float32))
    logits *= hd ** -0.5
    qpos = q_offset + jnp.arange(S)
    kpos = jnp.arange(T)
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, hd).astype(q.dtype)


def ssd_ref(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
            B: jnp.ndarray, C: jnp.ndarray,
            initial_state: Optional[jnp.ndarray] = None):
    """Naive sequential SSD recurrence (exact oracle).

    x: (b, s, h, p); dt: (b, s, h) positive; A: (h,) negative;
    B/C: (b, s, g, n).  Returns (y (b,s,h,p), final_state (b,h,p,n)).

    state_t = state_{t-1} * exp(dt_t A_h) + dt_t * x_t B_t^T
    y_t     = C_t · state_t
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    hpg = h // g
    Bh = jnp.repeat(B, hpg, axis=2) if g != h else B
    Ch = jnp.repeat(C, hpg, axis=2) if g != h else C

    def step(state, inp):
        xt, dtt, Bt, Ct = inp
        decay = jnp.exp(dtt * A[None, :])
        state = state * decay[:, :, None, None] + \
            dtt[:, :, None, None] * xt[:, :, :, None] * Bt[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", state, Ct)
        return state, y

    init = (jnp.zeros((b, h, p, n), jnp.float32) if initial_state is None
            else initial_state.astype(jnp.float32))
    final, ys = jax.lax.scan(
        step, init,
        (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
         jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
         jnp.moveaxis(Bh, 1, 0).astype(jnp.float32),
         jnp.moveaxis(Ch, 1, 0).astype(jnp.float32)))
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), final


# ---------------------------------------------------------------------------
# fused FL-update kernels (repro.kernels.fused_update) — same op order,
# f32 compute, cast on store
# ---------------------------------------------------------------------------

def local_step_ref(p, g, m, c, clip_scale, step_size, *,
                   weight_decay: float = 0.0, momentum: float = 0.0):
    """Oracle of ``fused_update.local_step``: ``(p_new, m_new)``."""
    p32 = p.astype(jnp.float32)
    g = g.astype(jnp.float32) * clip_scale
    if c is not None:
        g = g + c.astype(jnp.float32)
    if weight_decay:
        g = g + weight_decay * p32
    m_new = None
    if m is not None:
        m32 = g + momentum * m.astype(jnp.float32)
        m_new, g = m32.astype(m.dtype), m32
    return (p32 - step_size * g).astype(p.dtype), m_new


def weighted_delta_ref(stacked, p, weights, extra=None, *,
                       deltas: bool = False):
    """Oracle of ``fused_update.weighted_delta``."""
    p32 = p.astype(jnp.float32)
    w32 = weights.astype(jnp.float32)
    acc = jnp.zeros_like(p32) if extra is None else extra
    for k in range(stacked.shape[0]):       # the kernel's summation order
        s32 = stacked[k].astype(jnp.float32)
        acc = acc + w32[k] * (s32 if deltas else s32 - p32)
    return (p32 + acc).astype(p.dtype)


def delta_accum_ref(delta, w_end, p, coeff):
    """Oracle of ``fused_update.delta_accum`` (``p=None``: accum-only)."""
    w32 = w_end.astype(jnp.float32)
    if p is not None:
        w32 = w32 - p.astype(jnp.float32)
    return delta + coeff * w32


def dp_clip_noise_ref(d, z, clip_scale, noise_scale):
    """Oracle of ``fused_update.dp_clip_noise``."""
    u = clip_scale * d.astype(jnp.float32)
    return u if z is None else u + noise_scale * z


def server_update_ref(p, delta, moments, scalars, *, opt: str = "none",
                      beta: float = 0.9, b1: float = 0.9, b2: float = 0.99,
                      eps: float = 1e-8):
    """Oracle of ``fused_update.server_update``: ``(p_new, moments)``."""
    p32 = p.astype(jnp.float32)
    if opt == "none":
        return (p32 + delta).astype(p.dtype), ()
    g = -delta
    lr = scalars[0]
    if opt == "momentum":
        (m,) = moments
        m32 = beta * m.astype(jnp.float32) + g
        return (p32 - lr * m32).astype(p.dtype), (m32.astype(m.dtype),)
    mu, nu = moments
    bc1, bc2 = scalars[1], scalars[2]
    mu32 = b1 * mu.astype(jnp.float32) + (1.0 - b1) * g
    nu32 = b2 * nu.astype(jnp.float32) + (1.0 - b2) * g * g
    u = (mu32 / bc1) / (jnp.sqrt(nu32 / bc2) + eps)
    return ((p32 - lr * u).astype(p.dtype),
            (mu32.astype(mu.dtype), nu32.astype(nu.dtype)))
