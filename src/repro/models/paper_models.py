"""The paper's evaluation models, in pure JAX.

Table I models:
  - LeNet-5        (CIFAR-10): 2 conv + 3 FC          [LeCun et al. 1998]
  - ResNet-8       (CIFAR-100): 3 basic residual blocks + BN-free GroupNorm*
  - CNN-FEMNIST    (FEMNIST): 2 conv + 1 FC
  - CNN-Fashion    (Fashion-MNIST): 2 conv + dropout + 2 FC
  - CharLSTM-256   (Shakespeare): embed + 2-layer LSTM(256) + FC

*BatchNorm is notoriously broken under non-IID FL (client statistics
diverge); the paper uses BN in ResNet-8 but aggregates running stats via
FedAvg.  We keep an exact-BN variant for fidelity (train-mode batch
stats, aggregated like weights) — GroupNorm can be selected with
``norm='group'`` for the robustness ablation.

Each model is an (init, apply) pair over dict params; apply signature is
``apply(params, x, train=False, rng=None) -> logits``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.extend
import jax.numpy as jnp
from jax.interpreters import ad, batching, mlir

from repro.models.layers import he_normal, normal_init
from repro.utils.registry import Registry

Pytree = Any
PAPER_MODELS: Registry = Registry("paper_model")


# ---------------------------------------------------------------------------
# conv/pool/norm primitives (NHWC)
# ---------------------------------------------------------------------------

def init_conv(key, k: int, c_in: int, c_out: int, dtype=jnp.float32) -> Pytree:
    w = he_normal(key, (k, k, c_in, c_out), fan_in=k * k * c_in, dtype=dtype)
    return {"w": w, "b": jnp.zeros((c_out,), dtype)}


_NHWC = ("NHWC", "HWIO", "NHWC")

# The side of the MXU's tile: a merged kernel gradient stacks at most this
# many input channels and this many output channels.
MXU_SIDE = 128
# Kernels whose gradients merge the clients: C_in below the first, C_out
# at most the second.  On a v5e at K=10 the merged gradient beats the
# grouped one 2-5x at (C_in, C_out) = (3, 6), (6, 16) and (1, 16), ties at
# (3, 16), and loses at (16, 16), (1, 32) and every wider paper conv.
MERGED_C_IN, MERGED_C_OUT = 16, 16
# Platforms (``lax.platform_dependent`` names) whose vmapped kernel gradient
# merges the clients.  Elsewhere the grouped form stays: XLA's CPU backend
# runs it well, and computes the merged form's thrown-away blocks in full
# (the CPU tests ran 5x slower with it).
MERGED_PLATFORMS = ("tpu",)


def conv2d(p: Pytree, x: jnp.ndarray, stride: int = 1, padding: str = "SAME") -> jnp.ndarray:
    """NHWC convolution plus bias.

    The convolution and its two transposes, the input and the kernel
    gradient, are one primitive (``paper_conv``), so that the kernel
    gradient has a batching rule of its own and every form of autodiff
    (forward, reverse, and either over the other) still goes through
    it.  Each lowers to the convolution that ``conv_general_dilated``
    and its autodiff give; one model, outside a ``vmap`` (the P1 relay,
    evaluation), compiles as before.

    Under the client ``vmap`` each client has its own kernel, and the
    vmapped kernel gradient is a convolution grouped over the clients
    whose window is the whole image.  Where both sides of the kernel are
    narrow (C_in < ``MERGED_C_IN``, C_out <= ``MERGED_C_OUT``) that form
    runs slowly on the TPU, and the batching rule stacks the channels of ``g``
    clients instead and runs one ungrouped convolution over them, whose
    diagonal blocks are the clients' own gradients (``client_groups``,
    ``_clients_kernel_grad``): as many steps as one client's, each ``g``
    clients wide.  ``g`` is at most ``MXU_SIDE // max(C_in, C_out)``, the
    clients whose stacked inputs and outputs both fit one MXU tile, so
    the blocks thrown away take no extra pass.  Of the paper's vision
    models, LeNet-5's conv1 (3 -> 6) and conv2 (6 -> 16), ResNet-8's stem
    (3 -> 16) and CNN-Fashion's c1 (1 -> 16) merge; every other conv,
    CNN-FEMNIST's c1 (1 -> 32) among them, keeps the grouped gradient,
    which is faster there.
    """
    w = p["w"].astype(x.dtype)
    y = _conv_p.bind(x, w, role="y", stride=stride, padding=padding,
                     kernel=w.shape[:2], image=x.shape[1:3])
    return y + p["b"].astype(x.dtype)


def _conv(a, b, *, role, stride, padding, kernel, image):
    """One model's convolution as three bilinear maps, by ``role``: "y"
    is the convolution of ``(x, w)``, "w" its kernel gradient from
    ``(x, dy)``, "x" its input gradient from ``(dy, w)``."""
    def conv(x, w):
        return jax.lax.conv_general_dilated(x, w, (stride, stride), padding,
                                            dimension_numbers=_NHWC)

    if role == "y":
        return conv(a, b)
    if role == "w":
        x, dy = a, b
        w = jax.ShapeDtypeStruct((*kernel, x.shape[-1], dy.shape[-1]), x.dtype)
        return jax.linear_transpose(lambda w: conv(x, w), w)(dy)[0]
    dy, w = a, b
    x = jax.ShapeDtypeStruct((dy.shape[0], *image, w.shape[2]), dy.dtype)
    return jax.linear_transpose(lambda x: conv(x, w), x)(dy)[0]


def _abstract_conv(a, b, **params):
    out = jax.eval_shape(functools.partial(_conv, **params), a, b)
    return jax.core.ShapedArray(out.shape, out.dtype)


def _transpose_first(ct, a, b, *, role, **params):
    # y = conv(x, w) -> dx = X(ct, w); dw = W(x, dy) -> dx = X(dy, ct);
    # dx = X(dy, w) -> ddy = conv(ct, w)
    if role == "w":
        return _conv_p.bind(b, ct, role="x", **params)
    return _conv_p.bind(ct, b, role="x" if role == "y" else "y", **params)


def _transpose_second(ct, a, b, *, role, **params):
    # y = conv(x, w) -> dw = W(x, ct); dw = W(x, dy) -> ddy = conv(x, ct);
    # dx = X(dy, w) -> dw = W(ct, dy)
    if role == "x":
        return _conv_p.bind(ct, a, role="w", **params)
    return _conv_p.bind(a, ct, role="w" if role == "y" else "y", **params)


def _batch_conv(args, dims, *, role, **params):
    one = functools.partial(_conv, role=role, **params)
    if role == "w" and None not in dims:
        x, dy = (jnp.moveaxis(a, d, 0) for a, d in zip(args, dims))
        groups = client_groups(x.shape[0], x.shape[-1], dy.shape[-1])
        if len(groups) < x.shape[0]:
            merged = functools.partial(_clients_kernel_grad, groups=groups,
                                       **params)
            with jax.named_scope("fl_conv_wgrad"):
                return jax.lax.platform_dependent(
                    x, dy, default=jax.vmap(one),
                    **dict.fromkeys(MERGED_PLATFORMS, merged)), 0
    return jax.vmap(one, in_axes=dims)(*args), 0


_conv_p = jax.extend.core.Primitive("paper_conv")
_conv_p.def_impl(_conv)
_conv_p.def_abstract_eval(_abstract_conv)
ad.defbilinear(_conv_p, _transpose_first, _transpose_second)
batching.primitive_batchers[_conv_p] = _batch_conv
mlir.register_lowering(_conv_p, mlir.lower_fun(_conv, multiple_results=False))


def client_groups(n: int, c_in: int, c_out: int) -> Tuple[int, ...]:
    """The sizes of the groups in which ``n`` clients' kernel gradients
    are merged: as few groups as hold at most
    ``MXU_SIDE // max(c_in, c_out)`` clients each, of sizes that differ
    by at most one; ``n`` groups of one, the grouped gradient, unless
    c_in < ``MERGED_C_IN`` and c_out <= ``MERGED_C_OUT``."""
    if c_in >= MERGED_C_IN or c_out > MERGED_C_OUT:
        return (1,) * n
    m = -(-n // (MXU_SIDE // max(c_in, c_out)))
    return tuple(n // m + (i < n % m) for i in range(m))


def _clients_kernel_grad(x, dy, *, groups, stride, padding, kernel, image):
    """The kernel gradients of ``n`` clients, ``(n, kh, kw, C_in, C_out)``,
    from their inputs ``(n, B, H, W, C_in)`` and output gradients
    ``(n, B, Ho, Wo, C_out)``: per group of ``g`` clients, one kernel
    gradient over their ``g * C_in`` and ``g * C_out`` stacked channels,
    whose diagonal blocks are the clients' own (the rest, client
    against client, is thrown away)."""
    c_in, c_out = x.shape[-1], dy.shape[-1]
    out, start = [], 0
    for g in groups:
        xs, dys = x[start:start + g], dy[start:start + g]
        start += g
        dw = _conv(jnp.moveaxis(xs, 0, -2).reshape(*xs.shape[1:-1], g * c_in),
                   jnp.moveaxis(dys, 0, -2).reshape(*dys.shape[1:-1],
                                                    g * c_out),
                   role="w", stride=stride, padding=padding, kernel=kernel,
                   image=image)
        dw = jnp.diagonal(dw.reshape(*kernel, g, c_in, g, c_out), 0, 2, 4)
        out.append(jnp.moveaxis(dw, -1, 0))
    return jnp.concatenate(out) if len(out) > 1 else out[0]


def maxpool(x: jnp.ndarray, k: int = 2) -> jnp.ndarray:
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, k, k, 1), (1, k, k, 1), "VALID")


def avgpool_global(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean(x, axis=(1, 2))


def init_fc(key, d_in: int, d_out: int, dtype=jnp.float32) -> Pytree:
    return {"w": he_normal(key, (d_in, d_out), fan_in=d_in, dtype=dtype),
            "b": jnp.zeros((d_out,), dtype)}


def fc(p: Pytree, x: jnp.ndarray) -> jnp.ndarray:
    return x @ p["w"].astype(x.dtype) + p["b"].astype(x.dtype)


def init_bn(c: int, dtype=jnp.float32) -> Pytree:
    return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}


def batchnorm(p: Pytree, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """Train-mode BN (batch statistics).  FL simulation always trains;
    evaluation uses the same batch statistics, matching common FL-repo
    practice where running stats are unreliable under non-IID."""
    mu = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.var(x, axis=(0, 1, 2), keepdims=True)
    xhat = (x - mu) * jax.lax.rsqrt(var + eps)
    return xhat * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def groupnorm(p: Pytree, x: jnp.ndarray, groups: int = 8, eps: float = 1e-5) -> jnp.ndarray:
    N, H, W, C = x.shape
    g = math.gcd(groups, C)
    xg = x.reshape(N, H, W, g, C // g)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + eps)
    return xg.reshape(N, H, W, C) * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


# ---------------------------------------------------------------------------
# LeNet-5 (CIFAR-10)
# ---------------------------------------------------------------------------

def lenet5_init(key, n_classes: int = 10, in_ch: int = 3) -> Pytree:
    ks = jax.random.split(key, 5)
    return {
        "c1": init_conv(ks[0], 5, in_ch, 6),
        "c2": init_conv(ks[1], 5, 6, 16),
        "f1": init_fc(ks[2], 16 * 8 * 8, 120),
        "f2": init_fc(ks[3], 120, 84),
        "f3": init_fc(ks[4], 84, n_classes),
    }


def lenet5_apply(p: Pytree, x: jnp.ndarray, train: bool = False, rng=None) -> jnp.ndarray:
    x = maxpool(jax.nn.relu(conv2d(p["c1"], x)))
    x = maxpool(jax.nn.relu(conv2d(p["c2"], x)))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(fc(p["f1"], x))
    x = jax.nn.relu(fc(p["f2"], x))
    return fc(p["f3"], x)


# ---------------------------------------------------------------------------
# ResNet-8 (CIFAR-100): conv stem + 3 basic blocks + linear
# ---------------------------------------------------------------------------

def _init_basic_block(key, c_in: int, c_out: int, stride: int, norm: str) -> Pytree:
    ks = jax.random.split(key, 3)
    p = {
        "conv1": init_conv(ks[0], 3, c_in, c_out),
        "n1": init_bn(c_out),
        "conv2": init_conv(ks[1], 3, c_out, c_out),
        "n2": init_bn(c_out),
        "stride": stride,
    }
    if stride != 1 or c_in != c_out:
        p["proj"] = init_conv(ks[2], 1, c_in, c_out)
    return p


def _basic_block(p: Pytree, x: jnp.ndarray, norm_fn) -> jnp.ndarray:
    s = p["stride"]
    h = jax.nn.relu(norm_fn(p["n1"], conv2d(p["conv1"], x, stride=s)))
    h = norm_fn(p["n2"], conv2d(p["conv2"], h))
    sc = conv2d(p["proj"], x, stride=s) if "proj" in p else x
    return jax.nn.relu(h + sc)


def resnet8_init(key, n_classes: int = 100, in_ch: int = 3, norm: str = "batch") -> Pytree:
    ks = jax.random.split(key, 6)
    return {
        "stem": init_conv(ks[0], 3, in_ch, 16),
        "stem_n": init_bn(16),
        "b1": _init_basic_block(ks[1], 16, 16, 1, norm),
        "b2": _init_basic_block(ks[2], 16, 32, 2, norm),
        "b3": _init_basic_block(ks[3], 32, 64, 2, norm),
        "head": init_fc(ks[4], 64, n_classes),
        "norm_kind": norm,
    }


def resnet8_apply(p: Pytree, x: jnp.ndarray, train: bool = False, rng=None) -> jnp.ndarray:
    norm_fn = batchnorm if p.get("norm_kind", "batch") == "batch" else groupnorm
    x = jax.nn.relu(norm_fn(p["stem_n"], conv2d(p["stem"], x)))
    x = _basic_block(p["b1"], x, norm_fn)
    x = _basic_block(p["b2"], x, norm_fn)
    x = _basic_block(p["b3"], x, norm_fn)
    return fc(p["head"], avgpool_global(x))


# ---------------------------------------------------------------------------
# CNN-FEMNIST: 2 conv + 1 FC
# ---------------------------------------------------------------------------

def cnn_femnist_init(key, n_classes: int = 62, in_ch: int = 1) -> Pytree:
    ks = jax.random.split(key, 3)
    return {
        "c1": init_conv(ks[0], 5, in_ch, 32),
        "c2": init_conv(ks[1], 5, 32, 64),
        "f1": init_fc(ks[2], 64 * 7 * 7, n_classes),
    }


def cnn_femnist_apply(p: Pytree, x: jnp.ndarray, train: bool = False, rng=None) -> jnp.ndarray:
    x = maxpool(jax.nn.relu(conv2d(p["c1"], x)))
    x = maxpool(jax.nn.relu(conv2d(p["c2"], x)))
    return fc(p["f1"], x.reshape(x.shape[0], -1))


# ---------------------------------------------------------------------------
# CNN-Fashion: 2 conv + dropout + 2 FC
# ---------------------------------------------------------------------------

def mlp_init(key, n_classes: int = 10, in_ch: int = 1, d_hidden: int = 64,
             img: int = 28) -> Pytree:
    """Two-layer MLP — not a paper model; the matmul-only workload used by
    dispatch/throughput microbenchmarks where conv cost would mask the
    effect being measured."""
    ks = jax.random.split(key, 2)
    return {
        "f1": init_fc(ks[0], img * img * in_ch, d_hidden),
        "f2": init_fc(ks[1], d_hidden, n_classes),
    }


def mlp_apply(p: Pytree, x: jnp.ndarray, train: bool = False, rng=None) -> jnp.ndarray:
    x = x.reshape(x.shape[0], -1)
    return fc(p["f2"], jax.nn.relu(fc(p["f1"], x)))


def cnn_fashion_init(key, n_classes: int = 10, in_ch: int = 1) -> Pytree:
    ks = jax.random.split(key, 4)
    return {
        "c1": init_conv(ks[0], 5, in_ch, 16),
        "c2": init_conv(ks[1], 5, 16, 32),
        "f1": init_fc(ks[2], 32 * 7 * 7, 128),
        "f2": init_fc(ks[3], 128, n_classes),
    }


def cnn_fashion_apply(p: Pytree, x: jnp.ndarray, train: bool = False,
                      rng=None, drop: float = 0.5) -> jnp.ndarray:
    x = maxpool(jax.nn.relu(conv2d(p["c1"], x)))
    x = maxpool(jax.nn.relu(conv2d(p["c2"], x)))
    x = x.reshape(x.shape[0], -1)
    if train and rng is not None:
        keep = jax.random.bernoulli(rng, 1 - drop, x.shape).astype(x.dtype)
        x = x * keep / (1 - drop)
    x = jax.nn.relu(fc(p["f1"], x))
    return fc(p["f2"], x)


# ---------------------------------------------------------------------------
# CharLSTM-256 (Shakespeare): embed(8) + 2x LSTM(256) + FC
# ---------------------------------------------------------------------------

def _init_lstm_cell(key, d_in: int, d_hidden: int) -> Pytree:
    ks = jax.random.split(key, 2)
    scale = (d_in + d_hidden) ** -0.5
    return {
        "wx": normal_init(ks[0], (d_in, 4 * d_hidden), std=scale),
        "wh": normal_init(ks[1], (d_hidden, 4 * d_hidden), std=scale),
        "b": jnp.zeros((4 * d_hidden,)),
    }


def _lstm_cell(p: Pytree, carry, x):
    h, c = carry
    gates = x @ p["wx"] + h @ p["wh"] + p["b"]
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return (h, c), h


def charlstm_init(key, vocab: int = 64, d_embed: int = 8, d_hidden: int = 256) -> Pytree:
    ks = jax.random.split(key, 4)
    return {
        "embed": normal_init(ks[0], (vocab, d_embed), std=0.1),
        "lstm1": _init_lstm_cell(ks[1], d_embed, d_hidden),
        "lstm2": _init_lstm_cell(ks[2], d_hidden, d_hidden),
        "head": init_fc(ks[3], d_hidden, vocab),
    }


def charlstm_apply(p: Pytree, tokens: jnp.ndarray, train: bool = False,
                   rng=None) -> jnp.ndarray:
    """tokens: (B, S) int -> logits (B, S, vocab) for next-char prediction."""
    B, S = tokens.shape
    x = p["embed"][tokens]                                  # (B, S, e)
    d_hidden = p["lstm1"]["wh"].shape[0]

    def run_layer(cell, seq):
        init = (jnp.zeros((B, d_hidden), seq.dtype), jnp.zeros((B, d_hidden), seq.dtype))
        _, hs = jax.lax.scan(lambda c, xt: _lstm_cell(cell, c, xt),
                             init, jnp.moveaxis(seq, 1, 0))
        return jnp.moveaxis(hs, 0, 1)

    h = run_layer(p["lstm1"], x)
    h = run_layer(p["lstm2"], h)
    return fc(p["head"], h)


# ---------------------------------------------------------------------------
# registry: name -> (init_fn(key, n_classes), apply_fn, kind)
# ---------------------------------------------------------------------------

PAPER_MODELS.register("lenet5")((lenet5_init, lenet5_apply, "vision"))
PAPER_MODELS.register("resnet8")((resnet8_init, resnet8_apply, "vision"))
PAPER_MODELS.register("cnn_femnist")((cnn_femnist_init, cnn_femnist_apply, "vision"))
PAPER_MODELS.register("cnn_fashion")((cnn_fashion_init, cnn_fashion_apply, "vision"))
PAPER_MODELS.register("mlp")((mlp_init, mlp_apply, "vision"))
PAPER_MODELS.register("charlstm")((charlstm_init, charlstm_apply, "charlm"))
