"""Plain reference of LeNet-5 on CIFAR-10, the model the CyclicFL paper
(arXiv:2301.12193) trains there: two 5x5 convolutions (6 and 16
channels), each followed by ReLU and 2x2 max pooling, then fully
connected layers of 120, 84 and ``n_classes`` units with ReLU between;
the loss is the mean softmax cross-entropy.  Images are NHWC.  The
paper states no padding; the convolutions here are 'same', as in the
program's LeNet-5 (see the configuration's ``assumed``).

The parameter tree is laid out as the program lays out its own, so that
the benchmark can hand one seeded tree to both.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.reference.numerics import Numerics, count

STACKED = ()


@dataclasses.dataclass(frozen=True)
class LeNet:
    classes: int
    channels: int
    size: int
    conv: tuple          # ((kernel, out_channels), ...)
    fc: tuple            # hidden widths


def from_config(c: dict) -> LeNet:
    return LeNet(classes=c["n_classes"], channels=c["in_channels"],
                 size=c["image_size"],
                 conv=tuple(tuple(x) for x in c["conv"]),
                 fc=tuple(c["fc"]))


def _shapes(m: LeNet):
    shapes, c_in, hw = {}, m.channels, m.size
    for i, (k, c_out) in enumerate(m.conv, 1):
        shapes[f"c{i}"] = ((k, k, c_in, c_out), k * k * c_in)
        c_in, hw = c_out, hw // 2
    d_in = c_in * hw * hw
    for i, d_out in enumerate(m.fc + (m.classes,), 1):
        shapes[f"f{i}"] = ((d_in, d_out), d_in)
        d_in = d_out
    return shapes


def init_params(key, m: LeNet, num: Numerics):
    """Seeded weights: He-normal kernels (std sqrt(2/fan_in)), zero
    biases."""
    shapes = _shapes(m)
    keys = jax.random.split(key, len(shapes))
    return {name: {"w": num.cast(jax.random.normal(k, shape)
                                 * (2.0 / fan_in) ** 0.5),
                   "b": num.cast(jnp.zeros(shape[-1:]))}
            for k, (name, (shape, fan_in)) in zip(keys, shapes.items())}


def _conv_same(x, w, num: Numerics):
    """A stride-1 'same' convolution as one matmul over the k x k
    patches of each pixel (a convolution at ``Precision.HIGHEST`` takes
    the TPU's compiler many minutes; a matmul does not)."""
    k = w.shape[0]
    lo = (k - 1) // 2
    H, W = x.shape[1], x.shape[2]
    xp = jnp.pad(x, ((0, 0), (lo, k - 1 - lo), (lo, k - 1 - lo), (0, 0)))
    patches = jnp.concatenate([xp[:, i:i + H, j:j + W, :]
                               for i in range(k) for j in range(k)], -1)
    return num.mm("bhwp,po->bhwo", patches, w.reshape(-1, w.shape[-1]))


def _forward(m: LeNet, num: Numerics, p, x):
    x = num.cast(x)
    for i in range(1, len(m.conv) + 1):
        c = p[f"c{i}"]
        x = jax.nn.relu(num.cast(_conv_same(x, c["w"], num) +
                                 num.cast(c["b"])))
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    n_fc = len(m.fc) + 1
    for i in range(1, n_fc + 1):
        f = p[f"f{i}"]
        x = num.cast(num.mm("bd,df->bf", x, f["w"]) + num.cast(f["b"]))
        if i < n_fc:
            x = jax.nn.relu(x)
    return x


def loss(m: LeNet, num: Numerics, params, images, labels):
    logits = _forward(m, num, params, images).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def n_params(m: LeNet) -> int:
    return count(jax.eval_shape(
        lambda k: init_params(k, m, Numerics("float32", jnp.float32)),
        jax.ShapeDtypeStruct((2,), jnp.uint32)))


def train_flops_per_sample(m: LeNet, seq_len: int = 0) -> float:
    """Forward plus backward FLOPs of one image: three times the forward
    multiply-adds of the convolutions and the dense layers, counted from
    the shapes (2 per multiply-add)."""
    del seq_len
    fwd, hw = 0.0, m.size
    for name, (shape, fan_in) in _shapes(m).items():
        if name.startswith("c"):
            fwd += 2.0 * hw * hw * shape[-1] * fan_in
            hw //= 2
        else:
            fwd += 2.0 * shape[0] * shape[1]
    return 3.0 * fwd
