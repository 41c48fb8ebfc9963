"""FlatView — pack a pytree into contiguous per-dtype 1-D buffers.

The FL update hot loop (clip / correct / decay / momentum / axpy per
local SGD step, weighted delta accumulation + server moments per round)
is pure elementwise algebra over the parameter pytree.  Leaf-wise
``tree_map`` turns each of those into O(n_leaves) tiny ops; packing the
tree into one contiguous buffer per dtype turns them into O(1) blocked
kernels (repro.kernels.fused_update) regardless of model depth.

The contract:

  view = FlatView.of(tree)          # shapes/dtypes only — works on tracers
  bufs = view.flatten(tree)         # {dtype_name: (total,) 1-D buffer}
  tree == view.unflatten(bufs)      # exact round-trip, any nesting

Leaves are grouped by canonical dtype name ("float32", "bfloat16", ...)
in first-seen traversal order; each leaf owns a static ``[offset,
offset+size)`` slice of its dtype's buffer (``slots``), so flatten is
reshape+concatenate and unflatten is static-slice+reshape — pure data
movement XLA folds into neighbouring ops.  Scalar leaves occupy one
element; empty (sub)trees contribute no slots and an empty buffer dict.

``flatten_stacked`` / ``unflatten_stacked`` handle trees whose leaves
carry a shared leading axis (the engine's vmapped ``(K, ...)`` client
stacks): buffers come out ``(K, total)`` with the same per-leaf offsets.

FlatView is a frozen, hashable value (treedef + slot tuple), so it can
key caches and ride static arguments.

Trainable-slice partitioning (federated PEFT): ``of(tree, filter=...)``
takes a per-leaf boolean mask (True = trainable, tree_flatten order —
repro.sharding.rules.trainable_mask builds one from a path pattern) and
routes frozen leaves into separate ``"frozen:"``-prefixed buckets with
their own static offsets.  Every emitting method — ``flatten``,
``zeros``, ``normal``, the stacked variants, ``buffer_sizes`` — then
speaks TRAINABLE buckets only, so gradients, momentum, deltas, server
moments and upload accounting all shrink to the optimized slice without
any caller-side masking; the frozen constants pack once via
``flatten_frozen`` and merge back at the ``unflatten(bufs, frozen=...)``
boundary (absent frozen buckets zero-fill, for moment trees).  With
``filter=None`` there are no frozen buckets and every path is the exact
unfiltered program.

:class:`ShardedFlatView` is the mesh-aware sibling: leaves are bucketed
per *(dtype, mesh-axis group)* — the group being the set of mesh axes
their PartitionSpec shards them over — and each bucket packs into a
``(n_shards, per_shard)`` buffer whose leading axis is sharded over
exactly those axes.  Per-shard offsets are static, so every device holds
one contiguous local buffer per bucket and the fused update kernels run
shard-locally (see repro.fl.pod.ShardedFlatOps) without giving up the
FSDP×TP layout.  The view itself is pure data movement
(reshape/transpose), value-like and hashable; placement is the caller's
job (repro.sharding.rules builds the views and NamedShardings).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Pytree = Any

# frozen leaves pack into buckets under this name prefix; the plain
# bucket name (dtype / dtype@axes) follows the prefix unchanged
FROZEN_PREFIX = "frozen:"


def is_frozen_bucket(name: str) -> bool:
    return name.startswith(FROZEN_PREFIX)


def _check_filter(filter, n_leaves: int):
    """Normalize a per-leaf trainable mask (None = all trainable)."""
    if filter is None:
        return None
    mask = tuple(bool(b) for b in filter)
    if len(mask) != n_leaves:
        raise ValueError(f"trainable filter has {len(mask)} entries for a "
                         f"{n_leaves}-leaf tree")
    return mask


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """One leaf's static slice of its dtype buffer."""
    buffer: str                 # canonical dtype name, e.g. "float32"
    offset: int                 # element offset into the buffer
    size: int                   # number of elements (1 for scalar leaves)
    shape: Tuple[int, ...]      # original leaf shape


@dataclasses.dataclass(frozen=True)
class FlatView:
    """Static packing plan for one pytree structure (see module doc)."""
    treedef: Any
    slots: Tuple[LeafSlot, ...]

    @classmethod
    def of(cls, tree: Pytree, filter=None) -> "FlatView":
        """Build a view from shapes/dtypes only — leaves may be tracers,
        ShapeDtypeStructs or concrete arrays.  ``filter`` is an optional
        per-leaf trainable mask (tree_flatten order): False routes the
        leaf into a ``frozen:``-prefixed bucket (see module doc)."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        mask = _check_filter(filter, len(leaves))
        sizes: Dict[str, int] = {}
        slots = []
        for i, leaf in enumerate(leaves):
            name = jnp.dtype(leaf.dtype).name
            if mask is not None and not mask[i]:
                name = FROZEN_PREFIX + name
            size = int(math.prod(leaf.shape))
            off = sizes.get(name, 0)
            slots.append(LeafSlot(buffer=name, offset=off, size=size,
                                  shape=tuple(leaf.shape)))
            sizes[name] = off + size
        return cls(treedef=treedef, slots=tuple(slots))

    # -- introspection ------------------------------------------------------

    @property
    def buffer_sizes(self) -> Dict[str, int]:
        """Total elements per TRAINABLE dtype buffer, first-seen order
        (everything the round program optimizes and communicates)."""
        sizes: Dict[str, int] = {}
        for s in self.slots:
            if not is_frozen_bucket(s.buffer):
                sizes[s.buffer] = s.offset + s.size
        return sizes

    @property
    def frozen_sizes(self) -> Dict[str, int]:
        """Total elements per frozen bucket ({} without a filter)."""
        sizes: Dict[str, int] = {}
        for s in self.slots:
            if is_frozen_bucket(s.buffer):
                sizes[s.buffer] = s.offset + s.size
        return sizes

    @property
    def total_size(self) -> int:
        return sum(self.buffer_sizes.values())

    def _check(self, tree: Pytree) -> list:
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if treedef != self.treedef:
            raise ValueError(f"tree structure mismatch: {treedef} != "
                             f"{self.treedef}")
        return leaves

    # -- pack / unpack ------------------------------------------------------

    def flatten(self, tree: Pytree) -> Dict[str, jnp.ndarray]:
        """Pack ``tree``'s trainable leaves into ``{dtype_name: (total,)
        buffer}`` (all leaves without a filter)."""
        leaves = self._check(tree)
        parts: Dict[str, list] = {}
        for slot, leaf in zip(self.slots, leaves):
            if is_frozen_bucket(slot.buffer):
                continue
            parts.setdefault(slot.buffer, []).append(
                jnp.asarray(leaf).reshape(-1))
        return {name: jnp.concatenate(chunks)
                for name, chunks in parts.items()}

    def flatten_frozen(self, tree: Pytree) -> Dict[str, jnp.ndarray]:
        """Pack the FROZEN leaves into their ``frozen:`` buckets — the
        once-per-phase read-only constant dict ({} without a filter)."""
        leaves = self._check(tree)
        parts: Dict[str, list] = {}
        for slot, leaf in zip(self.slots, leaves):
            if not is_frozen_bucket(slot.buffer):
                continue
            parts.setdefault(slot.buffer, []).append(
                jnp.asarray(leaf).reshape(-1))
        return {name: jnp.concatenate(chunks)
                for name, chunks in parts.items()}

    def frozen_zeros(self) -> Dict[str, jnp.ndarray]:
        """Zero frozen buckets at their recorded dtypes — the fill-in
        for unflattening a trainable-only wrapper pytree (server
        moments) whose frozen slots have no values."""
        return {name: jnp.zeros((size,),
                                name[len(FROZEN_PREFIX):])
                for name, size in self.frozen_sizes.items()}

    def unflatten(self, bufs: Dict[str, jnp.ndarray],
                  frozen: Dict[str, jnp.ndarray] = None) -> Pytree:
        """Inverse of :meth:`flatten` (accepts buffers of any dtype —
        leaves are cast back to the slot's recorded dtype by reshape,
        not re-cast; pass matching dtypes for an exact round-trip).
        With a filter, ``frozen`` supplies the ``frozen:`` buckets
        (:meth:`flatten_frozen`); absent frozen buckets zero-fill."""
        if self.frozen_sizes:
            merged = dict(bufs)
            merged.update(frozen if frozen else self.frozen_zeros())
            bufs = merged
        leaves = [bufs[s.buffer][s.offset:s.offset + s.size].reshape(s.shape)
                  for s in self.slots]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    # -- stacked variants (leading shared axis, e.g. (K, ...) clients) ------

    def flatten_stacked(self, tree: Pytree) -> Dict[str, jnp.ndarray]:
        """Pack a tree whose (trainable) leaves carry one shared leading
        axis K into ``{dtype_name: (K, total) buffers}``."""
        leaves = self._check(tree)
        parts: Dict[str, list] = {}
        for slot, leaf in zip(self.slots, leaves):
            if is_frozen_bucket(slot.buffer):
                continue
            leaf = jnp.asarray(leaf)
            parts.setdefault(slot.buffer, []).append(
                leaf.reshape(leaf.shape[0], -1))
        return {name: jnp.concatenate(chunks, axis=1)
                for name, chunks in parts.items()}

    def unflatten_stacked(self, bufs: Dict[str, jnp.ndarray],
                          frozen: Dict[str, jnp.ndarray] = None) -> Pytree:
        """Inverse of :meth:`flatten_stacked`.  With a filter, frozen
        slots broadcast the shared constant bucket (``frozen``, 1-D per
        :meth:`flatten_frozen`; zero-filled when absent) over the K
        axis — every row shares the same frozen base."""
        fz = None
        if self.frozen_sizes:
            fz = dict(frozen) if frozen else self.frozen_zeros()
        # an empty tree has no buffers; its K is never read
        K = next((b.shape[0] for b in bufs.values()), 0)
        leaves = []
        for s in self.slots:
            if is_frozen_bucket(s.buffer):
                row = fz[s.buffer][s.offset:s.offset + s.size].reshape(s.shape)
                leaves.append(jnp.broadcast_to(row[None], (K,) + s.shape))
                continue
            buf = bufs[s.buffer]
            leaves.append(buf[:, s.offset:s.offset + s.size].reshape(
                (buf.shape[0],) + s.shape))
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    # -- constructors over the same plan ------------------------------------

    def zeros(self, dtype=None) -> Dict[str, jnp.ndarray]:
        """Zero buffers with this view's sizes; ``dtype`` overrides the
        per-buffer dtype (e.g. an f32 delta accumulator over bf16
        params)."""
        return {name: jnp.zeros((size,), dtype or name)
                for name, size in self.buffer_sizes.items()}

    def normal(self, key) -> Dict[str, jnp.ndarray]:
        """Standard-normal f32 buffers over this plan, drawn PER LEAF:
        leaf ``i`` (tree_flatten order) draws with
        ``fold_in(key, i)`` at the leaf's original shape, then packs
        like :meth:`flatten`.  Keying and shaping the draws by leaf —
        not by buffer — makes the bits independent of the packing, so a
        tree-side twin (repro.fl.privacy.tree_normal) and the
        ShardedFlatView flavor produce the SAME values per parameter.
        Non-inexact (integer) slots draw zeros.  Frozen slots are never
        noised, masked or uploaded — they emit nothing (the per-leaf
        fold_in index stays the GLOBAL slot index, so a filtered view
        draws the same bits per trainable parameter as the full view)."""
        parts: Dict[str, list] = {}
        for i, slot in enumerate(self.slots):
            if is_frozen_bucket(slot.buffer):
                continue
            if jnp.issubdtype(jnp.dtype(slot.buffer), jnp.inexact):
                draw = jax.random.normal(jax.random.fold_in(key, i),
                                         slot.shape, jnp.float32)
            else:
                draw = jnp.zeros(slot.shape, jnp.float32)
            parts.setdefault(slot.buffer, []).append(draw.reshape(-1))
        return {name: jnp.concatenate(chunks)
                for name, chunks in parts.items()}


# ---------------------------------------------------------------------------
# sharded flat view — per-(dtype × mesh-axis-group) buffers
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedLeafSlot:
    """One leaf's static slice of its bucket, PER SHARD."""
    buffer: str                   # bucket name, e.g. "float32@data+model"
    offset: int                   # element offset within each shard row
    size: int                     # elements per shard for this leaf
    shape: Tuple[int, ...]        # global (unsharded) leaf shape
    # mesh axes tiling each dim, in the dim's tiling order (() = unsharded)
    dim_axes: Tuple[Tuple[str, ...], ...]


@dataclasses.dataclass(frozen=True)
class ShardGroup:
    """One bucket: all leaves of one dtype sharded over one axis set."""
    name: str
    dtype: str
    axes: Tuple[str, ...]         # canonical (mesh) order; () = replicated
    n_shards: int
    size: int                     # elements per shard (bucket total)


def _spec_entries(pspec, rank: int) -> Tuple[Tuple[str, ...], ...]:
    """Normalize a PartitionSpec-like into per-dim axis-name tuples,
    right-padded with () to the leaf rank."""
    entries = tuple(pspec) if pspec is not None else ()
    out = []
    for e in entries:
        if e is None:
            out.append(())
        elif isinstance(e, str):
            out.append((e,))
        else:
            out.append(tuple(e))
    out += [()] * (rank - len(out))
    return tuple(out[:rank])


@dataclasses.dataclass(frozen=True)
class ShardedFlatView:
    """Static packing plan bucketing leaves per (dtype, mesh-axis group).

    Each bucket's buffer is ``(n_shards, per_shard)``: axis 0 enumerates
    the shards of the group's mesh axes in canonical (mesh-order)
    row-major order, and every leaf owns the static per-shard slice
    ``[offset, offset + size)`` of axis 1 — so sharding axis 0 over the
    group's axes puts each leaf's local tile in one contiguous run of
    the device-local buffer.  flatten/unflatten are pure
    reshape/transpose data movement and work on tracers.
    """
    treedef: Any
    slots: Tuple[ShardedLeafSlot, ...]
    groups: Tuple[ShardGroup, ...]
    axis_sizes: Tuple[Tuple[str, int], ...]   # canonical order, all axes

    @classmethod
    def of(cls, tree: Pytree, pspecs: Pytree,
           axis_sizes: Dict[str, int], filter=None) -> "ShardedFlatView":
        """Build a view from leaf shapes/dtypes plus a matching
        PartitionSpec tree (e.g. repro.sharding.rules.param_pspecs).

        ``axis_sizes`` maps mesh axis name -> size, in canonical mesh
        order; size-1 axes never shard anything and are dropped, so the
        same rules produce bit-identical single-device views.
        ``filter`` is the per-leaf trainable mask (see
        :class:`FlatView`): frozen leaves bucket into
        ``frozen:``-prefixed groups that keep their (dtype × mesh-axis
        group) decomposition — the frozen base stays FSDP-sharded — but
        never appear in the trainable emitters."""
        from jax.sharding import PartitionSpec
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        mask = _check_filter(filter, len(leaves))
        spec_leaves, _ = jax.tree_util.tree_flatten(
            pspecs, is_leaf=lambda x: x is None or
            isinstance(x, PartitionSpec))
        if len(spec_leaves) != len(leaves):
            raise ValueError(
                f"pspec tree has {len(spec_leaves)} leaves for a "
                f"{len(leaves)}-leaf param tree")
        order = tuple(axis_sizes)
        cursor: Dict[str, int] = {}
        meta: Dict[str, Tuple[str, Tuple[str, ...], int]] = {}
        slots = []
        for i, (leaf, pspec) in enumerate(zip(leaves, spec_leaves)):
            shape = tuple(leaf.shape)
            dtype = jnp.dtype(leaf.dtype).name
            dim_axes = tuple(
                tuple(a for a in entry if axis_sizes.get(a, 1) > 1)
                for entry in _spec_entries(pspec, len(shape)))
            used = [a for entry in dim_axes for a in entry]
            if len(set(used)) != len(used):
                raise ValueError(f"mesh axis repeated in spec {pspec}")
            for dim, entry in zip(shape, dim_axes):
                n = math.prod(axis_sizes[a] for a in entry)
                if n > 1 and dim % n != 0:
                    raise ValueError(
                        f"dim {dim} not divisible by axes {entry} ({n})")
            axes = tuple(a for a in order if a in used)
            n_shards = math.prod(axis_sizes[a] for a in axes)
            name = dtype + ("@" + "+".join(axes) if axes else "")
            if mask is not None and not mask[i]:
                name = FROZEN_PREFIX + name
            size = int(math.prod(shape)) // max(n_shards, 1)
            off = cursor.get(name, 0)
            slots.append(ShardedLeafSlot(buffer=name, offset=off, size=size,
                                         shape=shape, dim_axes=dim_axes))
            cursor[name] = off + size
            meta[name] = (dtype, axes, n_shards)
        groups = tuple(ShardGroup(name=name, dtype=m[0], axes=m[1],
                                  n_shards=m[2], size=cursor[name])
                       for name, m in meta.items())
        return cls(treedef=treedef, slots=tuple(slots), groups=groups,
                   axis_sizes=tuple((a, int(axis_sizes[a])) for a in order))

    # -- introspection ------------------------------------------------------

    @property
    def group_map(self) -> Dict[str, ShardGroup]:
        return {g.name: g for g in self.groups}

    @property
    def trainable_groups(self) -> Tuple[ShardGroup, ...]:
        return tuple(g for g in self.groups if not is_frozen_bucket(g.name))

    @property
    def frozen_groups(self) -> Tuple[ShardGroup, ...]:
        return tuple(g for g in self.groups if is_frozen_bucket(g.name))

    @property
    def buffer_shapes(self) -> Dict[str, Tuple[int, int]]:
        return {g.name: (g.n_shards, g.size) for g in self.trainable_groups}

    @property
    def total_size(self) -> int:
        return sum(g.n_shards * g.size for g in self.trainable_groups)

    def _axis_size(self, name: str) -> int:
        return dict(self.axis_sizes)[name]

    # -- per-leaf shard transform ------------------------------------------

    def _perm_info(self, slot: ShardedLeafSlot):
        """(expanded shape, factor->front permutation, n_shards) for one
        leaf: every sharded dim splits into its axis factors, and the
        factors move to the front in canonical (mesh) order."""
        order = [a for a, _ in self.axis_sizes]
        expanded, factor_pos = [], {}
        for dim, entry in zip(slot.shape, slot.dim_axes):
            for a in entry:
                factor_pos[a] = len(expanded)
                expanded.append(self._axis_size(a))
                dim //= self._axis_size(a)
            expanded.append(dim)
        block_pos = [i for i in range(len(expanded))
                     if i not in factor_pos.values()]
        perm = [factor_pos[a] for a in order if a in factor_pos] + block_pos
        n_shards = math.prod(self._axis_size(a) for a in factor_pos)
        return expanded, perm, n_shards

    def _leaf_to_shards(self, leaf: jnp.ndarray,
                        slot: ShardedLeafSlot) -> jnp.ndarray:
        """(global leaf) -> (n_shards, per_shard) rows, shard-major in
        canonical axis order."""
        expanded, perm, n_shards = self._perm_info(slot)
        out = jnp.asarray(leaf).reshape(expanded).transpose(perm)
        return out.reshape(n_shards, slot.size)

    def _shards_to_leaf(self, rows: jnp.ndarray,
                        slot: ShardedLeafSlot) -> jnp.ndarray:
        expanded, perm, _ = self._perm_info(slot)
        inv = [perm.index(i) for i in range(len(perm))]
        mid = rows.reshape([expanded[i] for i in perm])
        return mid.transpose(inv).reshape(slot.shape)

    # -- pack / unpack ------------------------------------------------------

    def _check(self, tree: Pytree) -> list:
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if treedef != self.treedef:
            raise ValueError(f"tree structure mismatch: {treedef} != "
                             f"{self.treedef}")
        return leaves

    def flatten(self, tree: Pytree) -> Dict[str, jnp.ndarray]:
        """Pack ``tree``'s trainable leaves into ``{bucket: (n_shards,
        per_shard)}`` (all leaves without a filter)."""
        leaves = self._check(tree)
        parts: Dict[str, list] = {}
        for slot, leaf in zip(self.slots, leaves):
            if is_frozen_bucket(slot.buffer):
                continue
            parts.setdefault(slot.buffer, []).append(
                self._leaf_to_shards(leaf, slot))
        return {name: jnp.concatenate(rows, axis=1)
                for name, rows in parts.items()}

    def flatten_frozen(self, tree: Pytree) -> Dict[str, jnp.ndarray]:
        """Pack the FROZEN leaves into their ``frozen:`` buckets, same
        per-group shard decomposition ({} without a filter)."""
        leaves = self._check(tree)
        parts: Dict[str, list] = {}
        for slot, leaf in zip(self.slots, leaves):
            if not is_frozen_bucket(slot.buffer):
                continue
            parts.setdefault(slot.buffer, []).append(
                self._leaf_to_shards(leaf, slot))
        return {name: jnp.concatenate(rows, axis=1)
                for name, rows in parts.items()}

    def frozen_zeros(self) -> Dict[str, jnp.ndarray]:
        """Zero frozen buckets at their recorded dtypes/shapes."""
        return {g.name: jnp.zeros((g.n_shards, g.size), g.dtype)
                for g in self.frozen_groups}

    def unflatten(self, bufs: Dict[str, jnp.ndarray],
                  frozen: Dict[str, jnp.ndarray] = None) -> Pytree:
        if self.frozen_groups:
            merged = dict(bufs)
            merged.update(frozen if frozen else self.frozen_zeros())
            bufs = merged
        leaves = [self._shards_to_leaf(
            bufs[s.buffer][:, s.offset:s.offset + s.size], s)
            for s in self.slots]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def zeros(self, dtype=None) -> Dict[str, jnp.ndarray]:
        """Zero buffers with this view's trainable shapes; ``dtype``
        overrides the per-bucket dtype (e.g. the pod's f32 delta
        accumulator)."""
        return {g.name: jnp.zeros((g.n_shards, g.size), dtype or g.dtype)
                for g in self.trainable_groups}

    def normal(self, key) -> Dict[str, jnp.ndarray]:
        """Standard-normal f32 buckets, drawn per leaf with
        ``fold_in(key, i)`` at the GLOBAL leaf shape and then
        shard-split — bit-identical per parameter to
        ``FlatView.normal`` / the tree twin for the same key, whatever
        the mesh layout (the draw precedes the pure-data-movement shard
        transform).  Non-inexact slots draw zeros; frozen slots emit
        nothing (fold_in keeps the global slot index, like
        ``FlatView.normal``)."""
        gm = self.group_map
        parts: Dict[str, list] = {}
        for i, slot in enumerate(self.slots):
            if is_frozen_bucket(slot.buffer):
                continue
            if jnp.issubdtype(jnp.dtype(gm[slot.buffer].dtype), jnp.inexact):
                draw = jax.random.normal(jax.random.fold_in(key, i),
                                         slot.shape, jnp.float32)
            else:
                draw = jnp.zeros(slot.shape, jnp.float32)
            parts.setdefault(slot.buffer, []).append(
                self._leaf_to_shards(draw, slot))
        return {name: jnp.concatenate(rows, axis=1)
                for name, rows in parts.items()}
