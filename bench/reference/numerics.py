"""The precision a reference computes in, and the per-leaf norms the
comparison reads.

A reference holds its arrays in a *carrier* dtype and may round every
stored value and every matmul operand through a narrower dtype: that is
how it computes "in float8" on a chip with no float8 matmul.  Matmuls
run at ``Precision.HIGHEST`` unless the configuration states the
TPU's default (one bfloat16 pass for a float32 matmul) as its own.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MATMUL = {"highest": HIGHEST, "default": jax.lax.Precision.DEFAULT}


@dataclasses.dataclass(frozen=True)
class Numerics:
    name: str
    carrier: Any
    through: Optional[Any] = None
    precision: Any = HIGHEST

    def cast(self, x):
        if self.through is not None:
            x = x.astype(self.through)
        return x.astype(self.carrier)

    def mm(self, spec: str, a, b):
        return jnp.einsum(spec, self.cast(a), self.cast(b),
                          precision=self.precision)


NUMERICS = {
    "float32": Numerics("float32", jnp.float32),
    "bfloat16": Numerics("bfloat16", jnp.bfloat16),
    "float8_e4m3fn": Numerics("float8_e4m3fn", jnp.bfloat16,
                              jnp.float8_e4m3fn),
}

# the nearest precision below each: the control of a configuration that
# states the key is the reference computed in the value
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def numerics(name: str, matmul: str = "highest") -> Numerics:
    try:
        return dataclasses.replace(NUMERICS[name], precision=MATMUL[matmul])
    except KeyError:
        raise ValueError(f"unknown precision {name!r} / matmul precision "
                         f"{matmul!r}; known: {sorted(NUMERICS)}, "
                         f"{sorted(MATMUL)}") from None


def _path_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def leaf_names(tree, stacked: Sequence[str] = ()) -> List[str]:
    """One name per compared leaf.  A leaf under a top-level key in
    ``stacked`` holds one layer per row of its leading axis, and each row
    counts as a leaf of its own."""
    names = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = _path_name(path)
        if path and _path_name(path[:1]) in stacked:
            names += [f"{name}[{i}]" for i in range(x.shape[0])]
        else:
            names.append(name)
    return names


def diff_norms(a, b, stacked: Sequence[str] = ()) -> jnp.ndarray:
    """``||a - b||`` in float32 for every leaf that :func:`leaf_names`
    names, in the same order, as one array."""
    out = []
    flat_a = jax.tree_util.tree_flatten_with_path(a)[0]
    flat_b = jax.tree_util.tree_leaves(b)
    for (path, x), y in zip(flat_a, flat_b):
        d = jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32))
        if path and _path_name(path[:1]) in stacked:
            out.append(jnp.sqrt(jnp.sum(d.reshape(d.shape[0], -1), axis=1)))
        else:
            out.append(jnp.sqrt(jnp.sum(d))[None])
    return jnp.concatenate(out)


def same_layout(a, b) -> Tuple[bool, str]:
    """Whether two trees have the same structure, shapes and dtypes."""
    sa = jax.tree_util.tree_structure(a)
    sb = jax.tree_util.tree_structure(b)
    if sa != sb:
        return False, f"structure {sa} != {sb}"
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                            jax.tree_util.tree_leaves(b)):
        if x.shape != y.shape or x.dtype != y.dtype:
            return False, (f"{_path_name(path)}: {x.shape} {x.dtype} != "
                           f"{y.shape} {y.dtype}")
    return True, ""


def count(tree) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(tree))

