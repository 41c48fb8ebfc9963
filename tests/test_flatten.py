"""FlatView / ShardedFlatView pack/unpack tests — deterministic
invariants plus hypothesis property sweeps.

The fused update path is only correct if flatten/unflatten is a perfect
bijection over arbitrary parameter pytrees — mixed dtypes, scalar
leaves, empty subtrees, any nesting.  For ShardedFlatView the bijection
must additionally commute with the mesh decomposition: leaves bucket
per (dtype × mesh-axis group), per-shard offsets are static, and
device_put with the bucket shardings round-trips exactly.  The
deterministic tests below always run; the hypothesis sweeps (random
tree shapes/dtypes/nesting/pspecs) skip cleanly when the optional dev
dep is absent (requirements-dev.txt), same policy as
tests/test_properties.py.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.utils.flatten import FlatView, ShardedFlatView

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # optional dev dep
    HAVE_HYPOTHESIS = False


def _assert_trees_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


# ---------------------------------------------------------------------------
# deterministic invariants (always run)
# ---------------------------------------------------------------------------

MIXED_TREE = {
    "emb": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
    "blk": [{"w": jnp.ones((2, 2), jnp.bfloat16),
             "step": jnp.int32(7)},
            {"w": jnp.full((2, 2), 2.0, jnp.bfloat16),
             "step": jnp.int32(9)}],
    "scalar": jnp.float32(1.5),
    "empty": {},
}


def test_mixed_dtype_roundtrip():
    view = FlatView.of(MIXED_TREE)
    bufs = view.flatten(MIXED_TREE)
    assert set(bufs) == {"float32", "bfloat16", "int32"}
    assert view.buffer_sizes == {"float32": 13, "bfloat16": 8, "int32": 2}
    for name, buf in bufs.items():
        assert buf.ndim == 1 and jnp.dtype(buf.dtype).name == name
    _assert_trees_equal(view.unflatten(bufs), MIXED_TREE)


def test_slots_are_contiguous_per_buffer():
    view = FlatView.of(MIXED_TREE)
    cursor = {}
    total = 0
    for s in view.slots:
        assert s.offset == cursor.get(s.buffer, 0)
        assert s.size == int(np.prod(s.shape, dtype=np.int64))
        cursor[s.buffer] = s.offset + s.size
        total += s.size
    assert cursor == view.buffer_sizes
    assert total == view.total_size == 23


def test_stacked_roundtrip():
    base = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": jnp.arange(3, dtype=jnp.float32)}
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.stack([x, x + 1, x + 2]), base)
    view = FlatView.of(base)
    bufs = view.flatten_stacked(stacked)
    assert bufs["float32"].shape == (3, 9)
    _assert_trees_equal(view.unflatten_stacked(bufs), stacked)
    # row i of the stacked buffer is the flat packing of element i
    _assert_trees_equal(view.unflatten({"float32": bufs["float32"][1]}),
                        jax.tree_util.tree_map(lambda x: x[1], stacked))


def test_empty_tree():
    for empty in ({}, (), [], {"a": {}, "b": ()}):
        view = FlatView.of(empty)
        assert view.slots == () and view.flatten(empty) == {}
        back = view.unflatten({})
        assert jax.tree_util.tree_structure(back) == \
            jax.tree_util.tree_structure(empty)


def test_scalar_leaves_occupy_one_element():
    tree = {"s": jnp.float32(3.5), "v": jnp.arange(4, dtype=jnp.float32)}
    view = FlatView.of(tree)
    assert view.buffer_sizes == {"float32": 5}
    back = view.unflatten(view.flatten(tree))
    assert back["s"].shape == () and float(back["s"]) == 3.5


def test_structure_mismatch_raises():
    view = FlatView.of({"a": jnp.zeros(3)})
    with pytest.raises(ValueError, match="structure mismatch"):
        view.flatten({"b": jnp.zeros(3)})


def test_zeros_and_dtype_override():
    tree = {"a": jnp.zeros((2, 2), jnp.bfloat16), "b": jnp.zeros(3)}
    view = FlatView.of(tree)
    z = view.zeros()
    assert z["bfloat16"].dtype == jnp.bfloat16 and z["float32"].shape == (3,)
    z32 = view.zeros(jnp.float32)
    assert all(b.dtype == jnp.float32 for b in z32.values())


def test_of_works_on_shape_structs_and_tracers():
    specs = {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32),
             "b": jax.ShapeDtypeStruct((4,), jnp.float32)}
    view = FlatView.of(specs)
    assert view.buffer_sizes == {"float32": 20}

    @jax.jit
    def roundtrip(tree):
        v = FlatView.of(tree)          # leaves are tracers here
        return v.unflatten(v.flatten(tree))

    tree = {"w": jnp.ones((4, 4)), "b": jnp.arange(4, dtype=jnp.float32)}
    _assert_trees_equal(roundtrip(tree), tree)


def test_view_is_hashable_and_stable():
    t1 = {"a": jnp.zeros(3), "b": jnp.ones((2, 2))}
    t2 = {"a": jnp.full(3, 7.0), "b": jnp.zeros((2, 2))}
    assert FlatView.of(t1) == FlatView.of(t2)
    assert hash(FlatView.of(t1)) == hash(FlatView.of(t2))


# ---------------------------------------------------------------------------
# ShardedFlatView — deterministic invariants
# ---------------------------------------------------------------------------

AXIS_SIZES = {"pod": 1, "data": 2, "model": 2}

SHARDED_TREE = {
    "embed": jnp.arange(48, dtype=jnp.float32).reshape(8, 6),
    "wo": {"w": jnp.arange(24, dtype=jnp.bfloat16).reshape(4, 6),
           "b": jnp.arange(6, dtype=jnp.float32)},
    "gate": jnp.arange(32, dtype=jnp.float32).reshape(4, 8),
    "scale": jnp.float32(2.0),
}
SHARDED_PSPECS = {
    "embed": P("model", "data"),
    "wo": {"w": P("model", "data"), "b": P(None)},
    "gate": P("data", "model"),
    "scale": P(),
}


def _sharded_view():
    return ShardedFlatView.of(SHARDED_TREE, SHARDED_PSPECS, AXIS_SIZES)


def test_sharded_roundtrip_and_buckets():
    view = _sharded_view()
    bufs = view.flatten(SHARDED_TREE)
    # leaves bucket per (dtype, mesh-axis group); size-1 axes drop out
    assert set(bufs) == {"float32@data+model", "bfloat16@data+model",
                         "float32"}
    assert view.buffer_shapes == {"float32@data+model": (4, 20),
                                  "bfloat16@data+model": (4, 6),
                                  "float32": (1, 7)}
    _assert_trees_equal(view.unflatten(bufs), SHARDED_TREE)


def test_sharded_offsets_are_static_and_contiguous():
    view = _sharded_view()
    cursor = {}
    for s in view.slots:
        assert s.offset == cursor.get(s.buffer, 0)
        n_shards = view.group_map[s.buffer].n_shards
        assert s.size * n_shards == int(np.prod(s.shape, dtype=np.int64))
        cursor[s.buffer] = s.offset + s.size
    assert cursor == {g.name: g.size for g in view.groups}


def test_sharded_rows_are_the_device_tiles():
    """Row k of a bucket must be exactly the tile device k would hold
    under the leaf's NamedSharding — shard-major in canonical (mesh)
    axis order, so sharding axis 0 over (data, model) is a no-comms
    relabel of the per-leaf layout."""
    view = _sharded_view()
    bufs = view.flatten(SHARDED_TREE)
    emb = np.arange(48, dtype=np.float32).reshape(8, 6)
    for di in range(2):
        for mi in range(2):
            tile = emb[mi * 4:(mi + 1) * 4, di * 3:(di + 1) * 3].reshape(-1)
            np.testing.assert_array_equal(
                np.asarray(bufs["float32@data+model"][di * 2 + mi, :12]),
                tile)


def test_sharded_roundtrip_under_named_sharding():
    """device_put with the bucket shardings (n_shards axis over the
    group's axes) then unflatten reproduces the tree exactly."""
    from jax.sharding import NamedSharding
    from repro.launch.mesh import make_mesh
    from repro.sharding.rules import flat_buffer_pspec

    n = jax.device_count()
    mesh = make_mesh((1, 1, n), ("pod", "data", "model"))
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    view = ShardedFlatView.of(SHARDED_TREE, SHARDED_PSPECS, sizes)
    bufs = view.flatten(SHARDED_TREE)
    placed = {g.name: jax.device_put(
        bufs[g.name], NamedSharding(mesh, flat_buffer_pspec(g)))
        for g in view.groups}
    _assert_trees_equal(view.unflatten(placed), SHARDED_TREE)


def test_sharded_divisibility_rejected():
    with pytest.raises(ValueError, match="divisible"):
        ShardedFlatView.of({"w": jnp.zeros((3, 4))}, {"w": P("data", None)},
                           AXIS_SIZES)


def test_sharded_zeros_and_dtype_override():
    view = _sharded_view()
    z = view.zeros()
    assert z["bfloat16@data+model"].dtype == jnp.bfloat16
    z32 = view.zeros(jnp.float32)
    assert all(b.dtype == jnp.float32 for b in z32.values())
    assert {k: b.shape for k, b in z32.items()} == view.buffer_shapes


def test_sharded_view_hashable_and_jit_compatible():
    assert hash(_sharded_view()) == hash(_sharded_view())

    @jax.jit
    def roundtrip(tree):
        v = ShardedFlatView.of(tree, SHARDED_PSPECS, AXIS_SIZES)
        return v.unflatten(v.flatten(tree))

    _assert_trees_equal(roundtrip(SHARDED_TREE), SHARDED_TREE)


def test_sharded_single_device_collapses_to_one_bucket_per_dtype():
    """All axes size 1 → no sharding survives, one (1, total) bucket
    per dtype — the host-mesh degeneration the parity tests rely on."""
    view = ShardedFlatView.of(SHARDED_TREE, SHARDED_PSPECS,
                              {"pod": 1, "data": 1, "model": 1})
    assert set(view.buffer_shapes) == {"float32", "bfloat16"}
    assert all(shape[0] == 1 for shape in view.buffer_shapes.values())
    _assert_trees_equal(view.unflatten(view.flatten(SHARDED_TREE)),
                        SHARDED_TREE)


# ---------------------------------------------------------------------------
# hypothesis property sweeps (optional dev dep)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    DTYPES = ["float32", "bfloat16", "int32"]

    @st.composite
    def leaf_arrays(draw, max_dims=3, max_side=5):
        shape = tuple(draw(st.lists(st.integers(1, max_side), min_size=0,
                                    max_size=max_dims)))
        dtype = draw(st.sampled_from(DTYPES))
        seed = draw(st.integers(0, 2 ** 30))
        rng = np.random.default_rng(seed)
        if dtype == "int32":
            return jnp.asarray(rng.integers(-100, 100, size=shape), jnp.int32)
        return jnp.asarray(rng.normal(size=shape), dtype)

    @st.composite
    def pytrees(draw, depth=2):
        """Nested dict/list/tuple trees of arrays, incl. empty subtrees
        and scalar (0-d) leaves."""
        if depth == 0:
            return draw(leaf_arrays())
        branch = draw(st.sampled_from(["leaf", "dict", "list", "tuple",
                                       "empty"]))
        if branch == "leaf":
            return draw(leaf_arrays())
        if branch == "empty":
            return draw(st.sampled_from([{}, (), []]))
        children = draw(st.lists(pytrees(depth=depth - 1), min_size=1,
                                 max_size=3))
        if branch == "dict":
            return {f"k{i}": c for i, c in enumerate(children)}
        return children if branch == "list" else tuple(children)

    @given(tree=pytrees())
    @settings(max_examples=40, deadline=None)
    def test_flatten_roundtrip_sweep(tree):
        view = FlatView.of(tree)
        bufs = view.flatten(tree)
        assert set(bufs) == set(view.buffer_sizes)
        for name, buf in bufs.items():
            assert buf.ndim == 1 and buf.shape[0] == view.buffer_sizes[name]
            assert jnp.dtype(buf.dtype).name == name
        _assert_trees_equal(view.unflatten(bufs), tree)

    @given(tree=pytrees(), k=st.integers(1, 4))
    @settings(max_examples=25, deadline=None)
    def test_flatten_stacked_roundtrip_sweep(tree, k):
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * k), tree)
        view = FlatView.of(tree)
        bufs = view.flatten_stacked(stacked)
        for buf in bufs.values():
            assert buf.ndim == 2 and buf.shape[0] == k
        _assert_trees_equal(view.unflatten_stacked(bufs), stacked)

    @given(tree=pytrees())
    @settings(max_examples=25, deadline=None)
    def test_slot_invariants_sweep(tree):
        view = FlatView.of(tree)
        cursor = {}
        for s in view.slots:
            assert s.offset == cursor.get(s.buffer, 0)
            assert s.size == int(np.prod(s.shape, dtype=np.int64))
            cursor[s.buffer] = s.offset + s.size
        assert cursor == view.buffer_sizes

    # -- ShardedFlatView sweeps --------------------------------------------

    SWEEP_AXES = {"data": 2, "model": 3}

    def _random_pspecs(tree, seed):
        """A valid pspec tree for ``tree``: per dim, maybe shard over an
        unused axis that divides it (mirrors the rules' degradation)."""
        rng = np.random.default_rng(seed)
        entries = [None, "data", "model", ("data", "model")]

        def leaf_spec(leaf):
            used, spec = set(), []
            for dim in leaf.shape:
                e = entries[rng.integers(0, len(entries))]
                axes = (e,) if isinstance(e, str) else (e or ())
                n = int(np.prod([SWEEP_AXES[a] for a in axes] or [1]))
                if e is None or used & set(axes) or dim % n or dim < n:
                    spec.append(None)
                else:
                    used |= set(axes)
                    spec.append(e)
            return P(*spec)

        return jax.tree_util.tree_map(leaf_spec, tree)

    @given(tree=pytrees(), seed=st.integers(0, 2 ** 16))
    @settings(max_examples=40, deadline=None)
    def test_sharded_roundtrip_sweep(tree, seed):
        pspecs = _random_pspecs(tree, seed)
        view = ShardedFlatView.of(tree, pspecs, SWEEP_AXES)
        bufs = view.flatten(tree)
        for g in view.groups:
            buf = bufs[g.name]
            assert buf.shape == (g.n_shards, g.size)
            assert jnp.dtype(buf.dtype).name == g.dtype
            assert g.n_shards == int(np.prod(
                [SWEEP_AXES[a] for a in g.axes] or [1]))
        _assert_trees_equal(view.unflatten(bufs), tree)

    @given(tree=pytrees(), seed=st.integers(0, 2 ** 16))
    @settings(max_examples=25, deadline=None)
    def test_sharded_slot_invariants_sweep(tree, seed):
        """Per-shard offsets are static and contiguous per bucket, and
        every leaf's per-shard size × n_shards recovers its element
        count (no padding, no overlap)."""
        view = ShardedFlatView.of(tree, _random_pspecs(tree, seed),
                                  SWEEP_AXES)
        cursor = {}
        for s in view.slots:
            assert s.offset == cursor.get(s.buffer, 0)
            n_shards = view.group_map[s.buffer].n_shards
            assert s.size * n_shards == int(np.prod(s.shape,
                                                    dtype=np.int64))
            cursor[s.buffer] = s.offset + s.size
        assert cursor == {g.name: g.size for g in view.groups}
        assert view.total_size == sum(
            int(np.prod(s.shape, dtype=np.int64)) for s in view.slots)
