"""Plain reference of a dense decoder-only LM of the Qwen1.5 kind: token
embedding, pre-norm blocks of RMSNorm -> multi-head attention with
rotary positions and q/k/v biases -> residual, RMSNorm -> SwiGLU MLP ->
residual, a final RMSNorm and a head tied to the embedding; the loss is
the mean next-token cross-entropy.

Departures from a float32 reference, each as the configuration states:
stored values and matmul operands are rounded to the configuration's
precision (``numerics``); attention scores, softmax, norms and rotary
angles are computed in float32, as the published models do.

The parameter tree is laid out as the program lays out its own (blocks
stacked on a leading layer axis), so that the benchmark can hand one
seeded tree to both.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.reference.numerics import HIGHEST, Numerics, count

STACKED = ("blocks",)


@dataclasses.dataclass(frozen=True)
class LM:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    eps: float
    theta: float
    qkv_bias: bool


def from_config(c: dict) -> LM:
    if not c["tie_word_embeddings"]:
        raise ValueError("this reference ties the head to the embedding")
    if c["hidden_act"] != "silu":
        raise ValueError(f"unsupported activation {c['hidden_act']!r}")
    heads = c["num_attention_heads"]
    return LM(layers=c["num_hidden_layers"], d=c["hidden_size"],
              heads=heads, kv_heads=c["num_key_value_heads"],
              head_dim=c.get("head_dim", c["hidden_size"] // heads),
              ff=c["intermediate_size"], vocab=c["vocab_size"],
              eps=c["rms_norm_eps"], theta=c["rope_theta"],
              qkv_bias=c["qkv_bias"])


def init_params(key, m: LM, num: Numerics):
    """Seeded weights: embedding N(0, 0.02), each projection
    N(0, 1/fan_in), zero biases, unit norm scales."""
    L, d, hd = m.layers, m.d, m.head_dim
    ks = iter(jax.random.split(key, 8))

    def w(shape, std):
        return num.cast(jax.random.normal(next(ks), shape) * std)

    def proj(d_in, d_out, bias):
        p = {"w": w((L, d_in, d_out), d_in ** -0.5)}
        if bias:
            p["b"] = num.cast(jnp.zeros((L, d_out)))
        return p

    ones = num.cast(jnp.ones((L, d)))
    blocks = {
        "attn_norm": {"scale": ones},
        "attn": {"wq": proj(d, m.heads * hd, m.qkv_bias),
                 "wk": proj(d, m.kv_heads * hd, m.qkv_bias),
                 "wv": proj(d, m.kv_heads * hd, m.qkv_bias),
                 "wo": proj(m.heads * hd, d, False)},
        "ffn_norm": {"scale": ones},
        "mlp": {"w_gate": proj(d, m.ff, False),
                "w_up": proj(d, m.ff, False),
                "w_down": proj(m.ff, d, False)},
    }
    return {"embed": w((m.vocab, d), 0.02), "blocks": blocks,
            "final_norm": {"scale": num.cast(jnp.ones((d,)))}}


def _rmsnorm(scale, x, eps, num):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return num.cast(y * scale.astype(jnp.float32))


def _rope(x, theta):
    """Rotary positions on (B, S, H, hd), rotating the two halves."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs    # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _linear(p, x, num):
    y = num.cast(num.mm("bsd,df->bsf", x, p["w"]))
    if "b" in p:
        y = num.cast(y + num.cast(p["b"]))
    return y


def _block(m: LM, num: Numerics, h, p):
    B, S, _ = h.shape
    H, KH, hd = m.heads, m.kv_heads, m.head_dim
    x = _rmsnorm(p["attn_norm"]["scale"], h, m.eps, num)
    q = _linear(p["attn"]["wq"], x, num).reshape(B, S, H, hd)
    k = _linear(p["attn"]["wk"], x, num).reshape(B, S, KH, hd)
    v = _linear(p["attn"]["wv"], x, num).reshape(B, S, KH, hd)
    q = num.cast(_rope(q, m.theta)).astype(jnp.float32)
    k = num.cast(_rope(k, m.theta)).astype(jnp.float32)
    q = q.reshape(B, S, KH, H // KH, hd)
    s = jnp.einsum("bskgd,btkd->bkgst", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -1e30)
    a = jnp.einsum("bkgst,btkd->bskgd", jax.nn.softmax(s, axis=-1),
                   v.astype(jnp.float32), precision=HIGHEST)
    a = num.cast(a.reshape(B, S, H * hd))
    h = num.cast(h + _linear(p["attn"]["wo"], a, num))
    x = _rmsnorm(p["ffn_norm"]["scale"], h, m.eps, num)
    g = num.cast(jax.nn.silu(_linear(p["mlp"]["w_gate"], x, num)))
    u = _linear(p["mlp"]["w_up"], x, num)
    return num.cast(h + _linear(p["mlp"]["w_down"], num.cast(g * u), num))


def loss(m: LM, num: Numerics, params, tokens, labels):
    """Mean next-token cross-entropy of ``tokens`` (B, S) against
    ``labels`` (B, S)."""
    h = num.cast(params["embed"][tokens])

    # each block's activations are recomputed in the backward pass, so
    # that the whole model's never sit in memory at once
    @jax.checkpoint
    def body(h, p):
        return _block(m, num, h, p), None

    h, _ = jax.lax.scan(body, h, params["blocks"])
    h = _rmsnorm(params["final_norm"]["scale"], h, m.eps, num)
    logits = num.cast(num.mm("bsd,vd->bsv", h, params["embed"]))
    logits = logits.astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def n_params(m: LM) -> int:
    shapes = jax.eval_shape(lambda k: init_params(k, m, Numerics(
        "float32", jnp.float32)), jax.ShapeDtypeStruct((2,), jnp.uint32))
    return count(shapes)


def train_flops_per_sample(m: LM, seq_len: int) -> float:
    """Forward plus backward FLOPs of one sequence, with no recompute:
    6 N per token, N counting the tied embedding once (its matmul is the
    head), plus the attention score and value matmuls over the full
    S x S grid the model computes, 12 L S H hd per token."""
    per_token = 6.0 * n_params(m) + 12.0 * m.layers * seq_len * \
        m.heads * m.head_dim
    return per_token * seq_len
