"""Pod-scale backends for the federated round engine.

The host engine (repro.fl.engine) and the pod driver used to be two
parallel codepaths; this module makes the pod a *backend* of the same
``RoundStrategy`` stack.  ``PodRelayStrategy`` / ``PodAggregateStrategy``
reuse the engine's round bodies (the same ``make_local_fn`` inner loop,
the same key derivation, on-device client sampling and chunked
``lax.scan`` dispatch) and add the mesh placement decisions:

  * params enter/leave every round pinned to ``rules.param_shardings``
    (FSDP × TP), and the compiled chunk program carries explicit
    in/out shardings so ``chunk_size`` rounds run as ONE SPMD dispatch;
  * the stacked client data ``(n_clients, n_per_client, ...)`` is
    device_put with the sample pool sharded over (pod, data) —
    ``rules.fl_batch_pspec(batch_axis=1)`` — so every local step's
    gathered batch is data-parallel across the whole mesh ("the mesh
    accelerates one client at a time", DESIGN.md §3);
  * per-client algorithm state lives in a ``ShardedClientStateStore``:
    the ``(n_clients, ...)`` stacks shard their leading client axis over
    the mesh ``data`` axis, rows for the selected K clients are gathered
    inside the program and scattered back — scaffold/moon at pod scale
    without replicating an (n_clients, model) tensor.

P2 aggregation differs from the host backend in schedule only.  The
default topology runs clients *sequentially* (``lax.scan``)
accumulating a weighted f32 delta — at LLM scale a per-client parameter
copy per vmap lane is exactly what does not fit, so peak memory is
~2×params independent of K, and the delta accumulation IS the FedAvg
all-reduce on the mesh.  ``aggregation="hierarchical"`` trades memory
back for critical path: clients group into ``n_pods`` pods (default:
the mesh ``data``-axis size), each pod accumulates a shard-local
partial delta over its own clients (one vmap lane per pod), and a
single cross-pod combine — one per-bucket sum over the lane partials —
produces the global delta, cutting the aggregation critical path from
O(K) to O(K/n_pods) local runs (see PodAggregateStrategy).  Either way
the math is identical to the host vmap+weighted-mean path up to
summation order, which is what the host↔pod parity tests pin down.

Per-client algorithm state scales past dense populations the same way
the host engine does: ``PodFLConfig(store="sparse")`` swaps the dense
``ShardedClientStateStore`` for ``ShardedSparseClientStateStore`` — the
participation-indexed ``(capacity, ...)`` active-set table of
repro.fl.engine with its row axis sharded over the mesh ``data`` axis,
LRU residency managed on the host between chunk dispatches.

The delta accumulation (and the whole client step tail) has two
implementations behind ``PodFLSpec.update_impl``: the per-leaf
``tree_map`` algebra ("tree", the parity oracle) and the FLAT-FIRST
fused path ("fused"/"fused_interpret").  Fused no longer trades away
the mesh layout: params ride the chunk as
:class:`repro.utils.flatten.ShardedFlatView` buffers — leaves bucketed
per (dtype × mesh-axis group) straight from the ``param_shardings``
rules, each bucket a ``(n_shards, per_shard)`` buffer sharded over
exactly its group's axes — so every device holds one contiguous local
buffer per bucket and the fused kernels
(repro.kernels.fused_update) run SHARD-LOCALLY under ``shard_map``
(:class:`ShardedFlatOps`).  The FSDP×TP decomposition is preserved
bit-for-bit (same tiles, packed), the donated chunk carries are the
sharded buffers themselves, and the local step differentiates w.r.t.
them (trees materialize only at the model's forward/backward
boundary), so fused updates run under real multi-device layouts — the
pod CLI defaults to ``--update-impl fused``.

Server-side optimizers (``server_opt="momentum"|"adam"`` — FedAvgM /
FedAdam) run at pod scale too: the optimizer moments mirror the param
tree, so ``rules.param_shardings`` applied to the ``OptState`` pytree
shards every moment exactly like the parameter it tracks (the scalar
step count replicates), and the state rides the donated chunk carry —
one sharded optimizer state per run, zero host round-trips.  The
in-program eval stream's test batches shard their per-batch sample axis
over (pod, data), same policy as the training pool.

``PodCyclicConfig`` / ``PodFLConfig`` are the declarative phase entries:
they register with ``core.pipeline`` so ``run_phase_schedule`` drives
multi-cycle P1↔P2 alternation and switch policies identically on both
backends.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

# canonical seed host-RNG stream offsets (P1 drew from seed+31, P2 from
# seed+17) — imported, not re-declared, so host↔pod sampling="host"
# parity cannot silently diverge
from repro.core.cyclic import HOST_RNG_OFFSET_P1
from repro.data.federated import FederatedDataset
from repro.fl.engine import (
    DENSE_STORE,
    AggregateStrategy,
    RelayStrategy,
    RoundSchedule,
    SparseClientStateStore,
    run_rounds,
    stack_copies,
    tree_rows,
    tree_set_rows,
)
from repro.fl import compression, privacy
from repro.fl.local import (
    FlatParamOps, LocalSpec, effective_trainable_filter, make_local_fn)
from repro.fl.simulation import HOST_RNG_OFFSET_P2
from repro.fl.task import Task
from repro.kernels import ops
from repro.kernels.fused_update import LANES, padded_len
from repro.sharding import rules
from repro.utils import tree_math as tm

Pytree = Any

POD_ALGORITHMS = ("fedavg", "fedprox", "scaffold", "moon")

# variant names for make_local_fn, keyed by aggregation algorithm
_VARIANTS = {"fedavg": "plain", "fedprox": "fedprox",
             "scaffold": "scaffold", "moon": "moon"}


@dataclasses.dataclass(frozen=True)
class PodFLSpec:
    """Static description of one pod-scale federated round."""
    local_steps: int = 8            # t_i — SGD steps per client
    batch_size: int = 8             # B — per-step local batch size
    lr: float = 0.01
    momentum: float = 0.0
    weight_decay: float = 0.0
    algorithm: str = "fedavg"       # fedavg | fedprox | scaffold | moon
    mu: float = 0.01                # fedprox proximal / moon coefficient
    temperature: float = 0.5        # moon
    grad_clip: Optional[float] = None
    # server-side optimizer (FedAvgM / FedAdam, Reddi et al.): applied to
    # the aggregated pseudo-gradient, moments sharded like params
    server_opt: str = "none"        # none | momentum | adam
    server_lr: float = 1.0
    server_momentum: float = 0.9
    # step-tail implementation: "tree" leaf-wise algebra (the parity
    # oracle) or the fused flat-first path.  On the pod the fused
    # buffers are ShardedFlatView buckets that preserve the FSDP×TP
    # layout (kernels run shard-locally under shard_map), so "fused" is
    # safe — and the CLI default — on real multi-device meshes.
    update_impl: str = "tree"       # tree | fused | fused_interpret
    # round-aggregate privacy (repro.fl.privacy): per-client delta
    # clipping + Gaussian noise (DP-FedAvg) and/or pairwise secure-agg
    # masks.  Both apply at AGGREGATION — None/False is the exact
    # baseline program.
    dp: Optional[privacy.DPSpec] = None
    secure_agg: bool = False
    # compressed P2 uploads (repro.fl.compression): block-quantized +
    # top-k sparsified client deltas, optional error feedback.  The
    # identity spec / None compile to the exact baseline program.
    compression: Optional[compression.CompressionSpec] = None
    # trainable-slice / PEFT (see repro.fl.local.LocalSpec): frozen
    # leaves stay out of the kernels, the donated carry and the wire;
    # needs the fused flat path.  P1 (relay) strips both knobs — the
    # relay hops the full model.
    peft: Optional[str] = None
    trainable_filter: Optional[str] = None

    def __post_init__(self):
        from repro.fl import compression as comp_mod
        from repro.fl.local import validate_peft, validate_update_impl
        validate_update_impl(self.update_impl)
        comp_mod.validate_compression(
            self.compression, dp=self.dp, secure_agg=self.secure_agg)
        if comp_mod.compression_on(self.compression) and \
                self.update_impl == "tree":
            raise ValueError(
                "pod lossy compression needs the fused flat path "
                "(update_impl='fused'|'fused_interpret') — the tree "
                "backend has no shard-local compress kernel")
        validate_peft(self.peft, trainable_filter=self.trainable_filter,
                      update_impl=self.update_impl)

    def local_spec(self, variant: Optional[str] = None) -> LocalSpec:
        return LocalSpec(
            n_steps=self.local_steps, batch_size=self.batch_size, lr=self.lr,
            momentum=self.momentum, weight_decay=self.weight_decay,
            variant=variant or _VARIANTS[self.algorithm], mu=self.mu,
            temperature=self.temperature, grad_clip=self.grad_clip,
            update_impl=self.update_impl, dp=self.dp,
            secure_agg=self.secure_agg, compression=self.compression,
            peft=self.peft, trainable_filter=self.trainable_filter)


# ---------------------------------------------------------------------------
# client-state store sharded over the mesh data axis
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedClientStateStore:
    """Per-client state stacks with the leading client axis sharded over
    the mesh ``data`` axis (see the ClientStateStore contract in
    repro.fl.engine).  Gather pulls the K selected rows into the round
    program; scatter writes them back and re-pins the stack's layout so
    the carry stays sharded across chunks."""
    mesh: Any

    def _shardings(self, tree: Pytree) -> Pytree:
        return rules.client_axis_shardings(tree, self.mesh)

    def init(self, template: Pytree, n_clients: int) -> Pytree:
        stacked = stack_copies(template, n_clients)
        return jax.device_put(stacked, self._shardings(stacked))

    def gather(self, state: Pytree, ids: jnp.ndarray) -> Pytree:
        return tree_rows(state, ids)

    def scatter(self, state: Pytree, ids: jnp.ndarray, rows: Pytree) -> Pytree:
        out = tree_set_rows(state, ids, rows)
        return jax.lax.with_sharding_constraint(out, self._shardings(out))

    needs_host_ids = False

    def population(self, state: Pytree) -> int:
        return jax.tree_util.tree_leaves(state)[0].shape[0]

    def prepare_chunk(self, state: Pytree, ids_block) -> Pytree:
        return state

    def shardings(self, template: Pytree, n_clients: int, mesh=None) -> Pytree:
        mesh = mesh or self.mesh
        return jax.tree_util.tree_map(
            lambda leaf: jax.sharding.NamedSharding(
                mesh, rules.client_axis_pspec(mesh, len(leaf.shape) + 1,
                                              n_clients)),
            template)


@dataclasses.dataclass(frozen=True, eq=False)
class ShardedSparseClientStateStore(SparseClientStateStore):
    """The participation-indexed store on a mesh: the active-set table
    shards its ``capacity`` row axis over the mesh ``data`` axis (same
    policy as the dense sharded store, applied to slots instead of
    clients); the id→slot index and the LRU bookkeeping replicate —
    they are O(n_clients)·int32 and O(capacity), negligible next to one
    model row.  Residency (stage/commit, see the base class) still runs
    eagerly on the host between dispatches; the committed state re-pins
    itself so the donated chunk carry keeps the mesh layout, and staged
    refill rows land DIRECTLY on their owning data shard whenever the
    eviction plan splits evenly across shards (the in-program scatter
    pins the layout either way — placement is a transfer-cost
    optimization, not a correctness requirement)."""
    mesh: Any = None

    def _state_shardings(self, state: Pytree) -> Pytree:
        rep = rules.replicated(self.mesh)
        return {"table": rules.client_axis_shardings(state["table"], self.mesh),
                "slot_of": rep, "owner": rep, "stamp": rep}

    def init(self, template: Pytree, n_clients: int) -> Pytree:
        state = super().init(template, n_clients)
        return jax.device_put(state, self._state_shardings(state))

    def scatter(self, state: Pytree, ids: jnp.ndarray, rows: Pytree) -> Pytree:
        out = super().scatter(state, ids, rows)
        return jax.lax.with_sharding_constraint(
            out, self._state_shardings(out))

    def _refill_placement(self, victims):
        """Placement for the staged ``(n_miss, ...)`` refill rows: the
        table's row axis shards over ``data`` in equal contiguous
        blocks, and the staged victims are sorted, so when the per-shard
        eviction counts are equal the row-sharded transfer puts every
        row straight onto the shard that owns its destination slot.
        Uneven plans fall back to replicated staging."""
        if self.mesh is None:
            return None
        d = rules.mesh_axis_size(self.mesh, rules.DATA)
        cap = self._meta["owner"].shape[0]
        if d <= 1 or cap % d or victims.size % d:
            return rules.replicated(self.mesh)
        per_shard = cap // d
        counts = np.bincount(victims // per_shard, minlength=d)
        if not np.all(counts == victims.size // d):
            return rules.replicated(self.mesh)
        return jax.sharding.NamedSharding(
            self.mesh, rules.client_axis_pspec(self.mesh, 1, victims.size))

    def commit_chunk(self, state: Pytree, staged) -> Pytree:
        new = super().commit_chunk(state, staged)
        return jax.device_put(new, self._state_shardings(new))

    def shardings(self, template: Pytree, n_clients: int, mesh=None) -> Pytree:
        mesh = mesh or self.mesh
        cap = max(1, min(self.capacity, n_clients))
        rep = rules.replicated(mesh)
        table = jax.tree_util.tree_map(
            lambda leaf: jax.sharding.NamedSharding(
                mesh, rules.client_axis_pspec(mesh, len(leaf.shape) + 1, cap)),
            template)
        return {"table": table, "slot_of": rep, "owner": rep, "stamp": rep}


# ---------------------------------------------------------------------------
# sharded flat ops — the pod's flat-first representation
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedFlatOps(FlatParamOps):
    """FlatParamOps over ShardedFlatView buffers on a mesh.

    Each bucket's ``(n_shards, per_shard)`` buffer is sharded over its
    group's mesh axes, so a kernel over it is embarrassingly
    shard-local: :meth:`_run` wraps every fused-kernel call in a
    ``shard_map`` whose in/out specs are the bucket's
    ``flat_buffer_pspec`` — each device runs the blocked Pallas pass on
    its own contiguous tile with zero collectives (the only cross-shard
    communication in the whole update path is the global clip norm, a
    scalar psum XLA inserts for :meth:`FlatParamOps.grad_sqsum`).

    Carried buffers are TILED: :meth:`pad` pads each per-shard row to
    its carried length and splits it into ``(rows, 128)``, so a carry is
    ``(n_shards, rows, 128)`` — the kernel grid's own shape.  A 2-D
    ``(n_shards, per_shard)`` bf16 buffer would be laid out on TPU with
    its one-row shard axis padded to a packed sublane pair (twice its
    bytes), and every shard-local kernel call would relayout it into
    tiles and back.  Frozen buckets, which no kernel reads, take the
    same layout, so every pod bucket has one.  For the same reason
    :meth:`unflatten` slices leaf tiles out of each device's own buffer
    under ``shard_map`` (and packs the gradient back the same way)
    instead of through the global ``(n_shards, per_shard)`` view.
    """
    mesh: Any = None

    def flatten(self, tree: Pytree) -> Dict[str, jnp.ndarray]:
        # packs straight into tiled carries, per device: the global
        # (n_shards, per_shard) concatenate compiles for minutes at
        # model width on TPU (180 s for qwen1.5-0.5b)
        return _pack_on_mesh(self, tree)

    def unflatten(self, bufs: Dict[str, jnp.ndarray],
                  frozen: Optional[Dict[str, jnp.ndarray]] = None) -> Pytree:
        if self.view.frozen_groups and not frozen:
            frozen = self.frozen_zeros()
        return _mesh_unflatten(self, bufs, frozen or {})

    def pad(self, bufs: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        # fresh (n_shards, per_shard) buckets pad and tile; anything
        # already tiled (or stacked over clients) passes through
        flat = FlatParamOps.pad(self, {k: b for k, b in bufs.items()
                                       if b.ndim == 2})
        return {k: _tile(flat[k]) if k in flat else b
                for k, b in bufs.items()}

    def place(self, bufs: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        # pad each bucket's per-shard axis to the kernel grid (so the
        # shard-local kernel calls skip their pad copy), then device_put.
        # device_put is a NO-OP (returns its operand) on matching
        # placement, and the shard transform itself passes (1, N)-shaped
        # unsharded leaves straight through — copy any passthrough so
        # the engine's donated carries never delete a caller's array
        # (same hazard as PodBackendMixin._put_unaliased)
        bufs = self.pad(bufs)
        placed = jax.device_put(bufs, self.shardings())
        return jax.tree_util.tree_map(
            lambda orig, out: jnp.copy(out) if out is orig else out,
            bufs, placed)

    def shardings(self) -> Dict[str, Any]:
        return rules.flat_param_shardings(self.view, self.mesh)

    def stacked_flatten(self, tree: Pytree):
        raise NotImplementedError("the pod backend aggregates "
                                  "sequentially — no stacked buffers")

    def stacked_unflatten(self, bufs: Dict[str, jnp.ndarray], frozen=None):
        raise NotImplementedError("the pod backend aggregates "
                                  "sequentially — no stacked buffers")

    def flatten_frozen(self, tree: Pytree) -> Dict[str, jnp.ndarray]:
        # packed per device into the carries' tiled layout, like flatten
        if not self.view.frozen_groups:
            return {}
        return _pack_on_mesh(self, tree, True)

    def frozen_zeros(self) -> Dict[str, jnp.ndarray]:
        return self.pad(self.view.frozen_zeros())

    def place_frozen(self, bufs: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        # pin the (already tiled) read-only constants to their mesh
        # layout (replicated or FSDP per the group's axes) with the same
        # unaliased-copy guard as place(): these live OUTSIDE the
        # donated carry but must not alias a caller's array either.
        placed = jax.device_put(bufs, self.frozen_shardings())
        return jax.tree_util.tree_map(
            lambda orig, out: jnp.copy(out) if out is orig else out,
            bufs, placed)

    def frozen_shardings(self) -> Dict[str, Any]:
        return rules.frozen_flat_shardings(self.view, self.mesh)

    def weighted_delta(self, p_bufs, stacked_bufs, wbar, extra=None):
        raise NotImplementedError("the pod backend aggregates "
                                  "sequentially — use delta_accum")

    def _run(self, name: str, fn: Callable, bufs, scalars):
        group = self.view.group_map[name]
        bspec = rules.flat_buffer_pspec(group)
        scalars = tuple(jnp.asarray(s, jnp.float32) if not hasattr(s, "dtype")
                        else s for s in scalars)
        # per-shard length from the buffer itself, not group.size — the
        # carried buffers are pre-padded (and tiled) to the kernel grid
        local = [jax.ShapeDtypeStruct((math.prod(b.shape[1:]),), b.dtype)
                 for b in bufs]
        sc_specs = [jax.ShapeDtypeStruct(jnp.shape(s), s.dtype)
                    for s in scalars]
        n_out = len(jax.eval_shape(fn, *local, *sc_specs))

        def body(*args):
            bs, sc = args[:len(bufs)], args[len(bufs):]
            outs = fn(*[b.reshape(-1) for b in bs], *sc)
            return tuple(o.reshape(bs[0].shape) for o in outs)

        run = jax.shard_map(body, mesh=self.mesh,
                            in_specs=tuple([bspec] * len(bufs) +
                                           [P()] * len(scalars)),
                            out_specs=(bspec,) * n_out, check_vma=False)
        return run(*bufs, *scalars)

    def _logical_size(self, name: str) -> int:
        # one kernel invocation runs under shard_map on ONE shard's
        # contiguous tile, so the top-k population is the PER-SHARD
        # logical element count — compression keeps k elements per shard
        # (shard-local top-k, zero collectives), not k globally
        return self.view.group_map[name].size

    # -- hierarchical lanes: shard-local partials + one psum combine --------
    #
    # The lane layout stacks the G pod accumulators into (G, n_shards,
    # per_shard) buffers with the LANE axis sharded over the mesh `data`
    # axis (rules.lane_axis_pspec): each data shard owns one pod's whole
    # f32 partial, kept p-free (accum-only fused_delta_accum, so the
    # `−(Σc)·p` term applies once AFTER the combine instead of per lane —
    # that rewrite is what makes the partials independent of the
    # FSDP-sharded params).  The cross-pod combine is then literally one
    # jax.lax.psum over `data` per bucket — asserted on the lowered HLO
    # in tests/test_pod_engine.py.

    def lane_count(self) -> int:
        """Pod lanes the mesh can host shard-locally (= |data| axis)."""
        return rules.mesh_axis_size(self.mesh, rules.DATA)

    def lane_zeros(self, G: int) -> Dict[str, jnp.ndarray]:
        """Lane-stacked f32 zero accumulators, pinned to the lane
        layout (lane axis over ``data``)."""
        if G != self.lane_count():
            raise ValueError(
                f"lane layout needs n_pods == |data| axis "
                f"({G} != {self.lane_count()})")
        zeros = self.zeros(jnp.float32)
        lane_sh = rules.lane_shardings(self.view, self.mesh)
        return {name: jax.lax.with_sharding_constraint(
                    jnp.zeros((G,) + b.shape, b.dtype), lane_sh[name])
                for name, b in zeros.items()}

    def lane_accum(self, acc_bufs, w_bufs, coeffs) -> Dict[str, jnp.ndarray]:
        """``acc[g] += coeffs[g] · w[g]`` per lane, shard-local: each
        data shard runs the blocked accum-only kernel on its own lane's
        contiguous tile — zero collectives."""
        interpret = self.interpret
        coeffs = jnp.asarray(coeffs, jnp.float32)
        lane_spec = rules.lane_axis_pspec()

        def body(a_loc, w_loc, c_loc):
            out = ops.fused_delta_accum(a_loc.reshape(-1), w_loc.reshape(-1),
                                        None, c_loc[0], interpret=interpret)
            return out.reshape(a_loc.shape)

        run = jax.shard_map(body, mesh=self.mesh,
                            in_specs=(lane_spec, lane_spec, P(rules.DATA)),
                            out_specs=lane_spec, check_vma=False)
        return {name: run(acc, w_bufs[name], coeffs)
                for name, acc in acc_bufs.items()}

    def lane_combine(self, acc_bufs) -> Dict[str, jnp.ndarray]:
        """The single cross-pod combine: one ``psum`` over the mesh
        ``data`` axis per bucket (any same-shard lanes fold locally
        first), returning the replicated ``(n_shards, per_shard)``
        total."""
        lane_spec = rules.lane_axis_pspec()

        def body(a_loc):
            return jax.lax.psum(jnp.sum(a_loc, axis=0), rules.DATA)

        run = jax.shard_map(body, mesh=self.mesh, in_specs=(lane_spec,),
                            out_specs=P(None, None), check_vma=False)
        return {name: run(acc) for name, acc in acc_bufs.items()}


# -- leaf tiles on the mesh ---------------------------------------------------
#
# Row k of a bucket is device k's FSDP×TP tile of every leaf in it,
# flattened row-major at the leaf's static offset, so under shard_map a
# device's local leaf block is a 1-D slice of its own buffer, reshaped.
# Unflatten and its transpose (packing the leaf gradients) therefore run
# shard-locally with 1-D intermediates only; custom_vjp states the
# transpose directly rather than transposing a shard_map.

def _slot_pspec(slot) -> P:
    return P(*[(e[0] if len(e) == 1 else e) if e else None
               for e in slot.dim_axes])


def _local_shape(view, slot) -> tuple:
    sizes = dict(view.axis_sizes)
    return tuple(d // math.prod(sizes[a] for a in e)
                 for d, e in zip(slot.shape, slot.dim_axes))


def _bucket_specs(fops: "ShardedFlatOps", names) -> Dict[str, P]:
    return {name: rules.flat_buffer_pspec(fops.view.group_map[name])
            for name in names}


def _leaf_specs(view) -> Pytree:
    return jax.tree_util.tree_unflatten(
        view.treedef, [_slot_pspec(s) for s in view.slots])


def _lane_aligned(slot) -> bool:
    return slot.offset % LANES == 0 and slot.size % LANES == 0


def _unflatten_on_mesh(fops: "ShardedFlatOps", bufs, frozen) -> Pytree:
    view = fops.view

    def leaf(buf, s):
        # a lane-aligned leaf is a row range of the tiled block: no
        # (…, n) intermediate even when a vmap batches this body
        if _lane_aligned(s):
            r0 = s.offset // LANES
            tile = buf[0, r0:r0 + s.size // LANES]
        else:
            tile = buf.reshape(-1)[s.offset:s.offset + s.size]
        return tile.reshape(_local_shape(view, s))

    def body(b, fz):
        merged = {**b, **fz}
        leaves = [leaf(merged[s.buffer], s) for s in view.slots]
        return jax.tree_util.tree_unflatten(view.treedef, leaves)

    return jax.shard_map(
        body, mesh=fops.mesh,
        in_specs=(_bucket_specs(fops, bufs), _bucket_specs(fops, frozen)),
        out_specs=_leaf_specs(view), check_vma=False)(bufs, frozen)


def _flatten_on_mesh(fops: "ShardedFlatOps", tree: Pytree,
                     frozen: bool = False):
    """Pack ``tree``'s trainable (or, with ``frozen``, its frozen)
    leaves into tiled buckets — the transpose of
    :func:`_unflatten_on_mesh`."""
    view = fops.view
    groups = view.frozen_groups if frozen else view.trainable_groups
    names = [g.name for g in groups]

    def body(t):
        parts: Dict[str, list] = {}
        for s, leaf in zip(view.slots, jax.tree_util.tree_leaves(t)):
            if s.buffer in names:
                parts.setdefault(s.buffer, []).append((s, leaf))
        out = {}
        for g in groups:
            pad = padded_len(g.size) - g.size
            if all(_lane_aligned(s) for s, _ in parts[g.name]):
                rows = jnp.concatenate([x.reshape(-1, LANES)
                                        for _, x in parts[g.name]])
                rows = jnp.pad(rows, ((0, pad // LANES), (0, 0)))
            else:
                flat = jnp.concatenate([x.reshape(-1)
                                        for _, x in parts[g.name]])
                rows = _tile(jnp.pad(flat, (0, pad)))
            out[g.name] = rows.astype(g.dtype)[None]
        return out

    return jax.shard_map(
        body, mesh=fops.mesh, in_specs=(_leaf_specs(view),),
        out_specs=_bucket_specs(fops, names), check_vma=False)(tree)


_pack_on_mesh = jax.jit(_flatten_on_mesh, static_argnums=(0, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mesh_unflatten(fops, bufs, frozen):
    return _unflatten_on_mesh(fops, bufs, frozen)


def _mesh_unflatten_fwd(fops, bufs, frozen):
    return _unflatten_on_mesh(fops, bufs, frozen), None


def _mesh_unflatten_bwd(fops, _, ct):
    return _flatten_on_mesh(fops, ct), None       # frozen: no gradient


_mesh_unflatten.defvjp(_mesh_unflatten_fwd, _mesh_unflatten_bwd)


def _tile(b: jnp.ndarray) -> jnp.ndarray:
    """``(..., n)`` → ``(..., n // 128, 128)`` (n is a carried length)."""
    return b.reshape(b.shape[:-1] + (b.shape[-1] // LANES, LANES))


@functools.lru_cache(maxsize=32)
def _sharded_flat_ops(task: Task, mesh, layout: str, interpret: bool,
                      filter_spec: Optional[str] = None) -> ShardedFlatOps:
    p_specs = jax.eval_shape(task.init, jax.random.PRNGKey(0))
    view = rules.sharded_flat_view(p_specs, mesh, layout,
                                   filter_spec=filter_spec)
    return ShardedFlatOps(view=view, interpret=interpret, mesh=mesh)


# ---------------------------------------------------------------------------
# the pod backend (engine hooks shared by both strategies)
# ---------------------------------------------------------------------------

class PodBackendMixin:
    """Engine backend hooks for a sharded mesh.  Subclasses are frozen
    strategy dataclasses providing ``mesh``, ``layout`` and
    ``clients_per_round`` fields."""

    def flat_ops(self, task: Task):
        if self.spec.update_impl == "tree":
            return None
        return _sharded_flat_ops(task, self.mesh, self.layout,
                                 ops.fused_interpret(self.spec.update_impl),
                                 effective_trainable_filter(self.spec))

    def n_selected(self, n_clients: int) -> int:
        if self.clients_per_round:
            return max(1, min(self.clients_per_round, n_clients))
        return super().n_selected(n_clients)

    def _param_shardings(self, task: Task) -> Pytree:
        p_specs = jax.eval_shape(task.init, jax.random.PRNGKey(0))
        return rules.param_shardings(p_specs, self.mesh, self.layout)

    def _axis1_sharding(self, arr):
        # batch-like axis 1 over (pod, data); replicate when it does not
        # divide — same degradation policy as the rules
        mesh = self.mesh
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        n_shards = 1
        for a in ("pod", "data"):
            n_shards *= sizes.get(a, 1)
        if arr.ndim >= 2 and n_shards > 1 and \
                arr.shape[1] % n_shards == 0 and arr.shape[1] >= n_shards:
            return jax.sharding.NamedSharding(
                mesh, rules.fl_batch_pspec(mesh, arr.ndim, batch_axis=1))
        return rules.replicated(mesh)

    def prepare_data(self, data: FederatedDataset):
        # sample pool (n_clients, n_per_client, ...): pool axis over the
        # mesh batch axes
        return data.device_arrays((self._axis1_sharding(data.x),
                                   self._axis1_sharding(data.y),
                                   rules.replicated(self.mesh)))

    def prepare_eval_data(self, batched):
        # eval stream (n_batches, B, ...): per-batch sample axis over the
        # mesh batch axes, exactly like the training pool
        return tuple(jax.device_put(a, self._axis1_sharding(a))
                     for a in batched)

    def _put_unaliased(self, tree: Pytree, shardings) -> Pytree:
        # device_put is a NO-OP (returns the caller's array) when the
        # placement already matches — e.g. phase 2 of a pod schedule
        # receiving phase 1's already-sharded result — and the engine
        # donates its carries, which would delete the caller's buffer.
        # Copy any aliased leaf so donation never eats external state.
        placed = jax.device_put(tree, shardings)
        return jax.tree_util.tree_map(
            lambda orig, out: jnp.copy(out) if out is orig else out,
            tree, placed)

    def place_params(self, params: Pytree) -> Pytree:
        return self._put_unaliased(
            params, rules.param_shardings(params, self.mesh, self.layout))

    def place_server_state(self, state: Pytree, task: Task) -> Pytree:
        if not jax.tree_util.tree_leaves(state):
            return state
        return self._put_unaliased(state, self.server_state_shardings(task))

    def state_shardings(self, task: Task, p_specs: Pytree,
                        n_clients: int) -> Dict:
        return {}

    def server_state_shardings(self, task: Task) -> Any:
        """Placement for the server-optimizer ``OptState``.

        Tree path: the moment trees mirror the param tree
        leaf-for-leaf, so the param path-pattern rules apply verbatim
        (the OptState/AdamWState wrappers only prefix the paths).
        Fused path: the moments are flat buffer dicts keyed by bucket
        name, so each moment buffer takes its bucket's
        ``flat_buffer_pspec``.  The scalar step count replicates either
        way."""
        server = self.make_server_update(task)
        if server is None:
            return ()
        p_specs = jax.eval_shape(task.init, jax.random.PRNGKey(0))
        fops = self.flat_ops(task)
        if fops is None:
            state = jax.eval_shape(server[0], p_specs)
            return rules.param_shardings(state, self.mesh, self.layout)
        buf_specs = jax.eval_shape(fops.flatten, p_specs)
        state = jax.eval_shape(server[0], buf_specs)
        buf_sh = fops.shardings()
        rep = rules.replicated(self.mesh)

        def leaf_sh(path, leaf):
            key = next((p.key for p in reversed(path)
                        if hasattr(p, "key")), None)
            return buf_sh.get(key, rep)

        return jax.tree_util.tree_map_with_path(leaf_sh, state)

    def jit_chunk(self, chunk: Callable, task: Task,
                  n_clients: int) -> Callable:
        p_specs = jax.eval_shape(task.init, jax.random.PRNGKey(0))
        fops = self.flat_ops(task)
        # flat-first: the params carry is the sharded buffer dict, so
        # its in/out shardings are the per-bucket flat shardings
        p_sh = fops.shardings() if fops is not None else \
            rules.param_shardings(p_specs, self.mesh, self.layout)
        rep = rules.replicated(self.mesh)
        st_sh = self.state_shardings(task, p_specs, n_clients)
        srv_sh = self.server_state_shardings(task)
        # chunk args: (key, params, algo_state, server_state, x_all,
        #              y_all, n_real, ids, lr_scales, eval_mask, ev_x,
        #              ev_y, ev_w); x/y and the eval stream keep the
        #              committed placement from prepare_data /
        #              prepare_eval_data (None = inherit), ids is None
        #              under on-device sampling, eval args are None in
        #              no-eval programs (a sharding entry broadcasts
        #              over the empty pytree); the trailing frozen
        #              bucket dict gets its replicated-or-FSDP layout
        #              ({} when nothing is frozen — any entry broadcasts)
        fz_sh = fops.frozen_shardings() if fops is not None else rep
        in_sh = (rep, p_sh, st_sh, srv_sh, None, None, rep, None, rep,
                 rep, None, None, None, fz_sh)
        out_sh = (rep, p_sh, st_sh, srv_sh, rep, rep)
        return jax.jit(chunk, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=(0, 1, 2, 3))


@dataclasses.dataclass(frozen=True)
class PodRelayStrategy(PodBackendMixin, RelayStrategy):
    """P1 relay on the mesh: the host relay body (sequential client scan,
    no aggregation) with params pinned to the FSDP×TP layout on round
    entry/exit."""
    mesh: Any = None
    layout: str = "fsdp_tp"
    clients_per_round: Optional[int] = None

    def __post_init__(self):
        super().__post_init__()         # relay rejects dp/secure_agg
        if self.mesh is None:
            raise ValueError("PodRelayStrategy requires a mesh")

    def build_round(self, task: Task) -> Callable:
        inner = RelayStrategy.build_round(self, task)
        fops = self.flat_ops(task)
        # fused: the carry is the sharded buffer dict — pin the buckets
        p_sh = fops.shardings() if fops is not None else \
            self._param_shardings(task)

        def body(key, params, x_all, y_all, ids, weights, lr_scale,
                 algo_state, frozen=None):
            params = jax.lax.with_sharding_constraint(params, p_sh)
            new_params, algo_state, loss = inner(
                key, params, x_all, y_all, ids, weights, lr_scale,
                algo_state, frozen)
            new_params = jax.lax.with_sharding_constraint(new_params, p_sh)
            return new_params, algo_state, loss

        return body


POD_AGGREGATIONS = ("sequential", "hierarchical")


@dataclasses.dataclass(frozen=True)
class PodAggregateStrategy(PodBackendMixin, AggregateStrategy):
    """P2 on the mesh: client scan + weighted f32 delta accumulation,
    algorithm state behind a data-axis-sharded ClientStateStore,
    server-side optimizers (``server_opt="momentum"|"adam"``) with
    param-sharded moments.  Numerically matches the host vmap backend
    round-for-round.

    Two aggregation topologies:

      sequential   : one ``lax.scan`` over all K clients accumulating
                     the delta — peak memory ~2×params independent of
                     K, aggregation critical path O(K).
      hierarchical : TWO-LEVEL — clients are grouped into ``n_pods``
                     (default: the mesh ``data``-axis size) pods; an
                     outer scan of K/G steps runs G clients at a time
                     (one vmap lane per pod), each lane accumulating
                     its own shard-local partial ``fused_delta_accum``,
                     and ONE cross-pod combine (a per-bucket sum over
                     the G lane partials, which lowers to a psum when
                     the lane axis is device-sharded) produces the
                     global weighted delta.  Critical path O(K/G) local
                     runs + one combine, at the cost of G× the f32
                     delta buffers and G× the lane activations — the
                     lanes are deliberately left unsharded so they
                     never conflict with the bucket axes.  Summation
                     order differs from sequential (per-pod partials,
                     then one sum), so results match up to float
                     reassociation.
    """
    mesh: Any = None
    layout: str = "fsdp_tp"
    clients_per_round: Optional[int] = None
    aggregation: str = "sequential"     # sequential | hierarchical
    n_pods: Optional[int] = None        # None: mesh data-axis size

    def __post_init__(self):
        if self.mesh is None:
            raise ValueError("PodAggregateStrategy requires a mesh")
        if self.algorithm not in POD_ALGORITHMS:
            raise ValueError(f"unknown pod algorithm {self.algorithm!r}")
        if self.aggregation not in POD_AGGREGATIONS:
            raise ValueError(f"unknown aggregation {self.aggregation!r} "
                             f"(choose from {POD_AGGREGATIONS})")
        if compression.compression_on(self.spec.compression) and \
                self.spec.update_impl == "tree":
            raise ValueError(
                "pod lossy compression needs the fused flat path "
                "(update_impl='fused'|'fused_interpret') — the tree "
                "backend has no shard-local compress kernel")
        if self.state_store is DENSE_STORE:
            object.__setattr__(self, "state_store",
                               ShardedClientStateStore(self.mesh))

    def _n_pods(self) -> int:
        if self.n_pods:
            return int(self.n_pods)
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return max(1, sizes.get(rules.DATA, 1))

    def state_shardings(self, task: Task, p_specs: Pytree,
                        n_clients: int) -> Dict:
        store = self.state_store
        if not hasattr(store, "shardings"):
            return {}
        fops = self.flat_ops(task)
        # the store rows mirror the engine's carried representation:
        # flat bucket dicts on the fused path, param trees otherwise
        template = jax.eval_shape(fops.zeros) if fops is not None else p_specs
        stacked = store.shardings(template, n_clients, self.mesh)
        out: Dict = {}
        if stacked is not None:
            if self.algorithm == "scaffold":
                c_sh = fops.shardings() if fops is not None else \
                    rules.param_shardings(p_specs, self.mesh, self.layout)
                out = {"c_global": c_sh, "c_clients": stacked}
            elif self.algorithm == "moon":
                out = {"w_prev": stacked}
        comp = self.spec.compression
        if compression.compression_on(comp) and comp.error_feedback:
            # error-feedback residual rows: f32 buffers in the carried
            # flat layout, client axis sharded like every other stack
            # (lossy compression on the pod implies the fused path)
            ef_tmpl = jax.eval_shape(functools.partial(fops.zeros,
                                                       jnp.float32))
            ef_sh = self._ef_store.shardings(ef_tmpl, n_clients, self.mesh)
            if ef_sh is not None:
                out = dict(out, ef_residuals=ef_sh)
        return out

    def build_round(self, task: Task) -> Callable:
        spec = self.spec
        fops = self.flat_ops(task)
        local = make_local_fn(task, spec, fops)
        algo = self.algorithm
        store = self.state_store
        fused = fops is not None
        p_sh = fops.shardings() if fused else self._param_shardings(task)
        unpack = fops.unflatten if fused else (lambda t, fz=None: t)
        G = self._n_pods() if self.aggregation == "hierarchical" else 1
        dp = spec.dp
        dp_clips = dp is not None and dp.clips
        comp = spec.compression
        compressed = compression.compression_on(comp)   # implies fused
        ef = compressed and comp.error_feedback
        ef_store = self._ef_store if ef else None

        def pin(t):
            return jax.lax.with_sharding_constraint(t, p_sh)

        def body(key, params, x_all, y_all, ids, weights, lr_scale,
                 algo_state, frozen=None):
            params = pin(params)
            K = ids.shape[0]
            keys = jax.random.split(key, K)
            cx = x_all[ids]
            cy = y_all[ids]
            w32 = weights.astype(jnp.float32)
            wsum = jnp.sum(w32)
            ef_rows = (ef_store.gather(algo_state["ef_residuals"], ids)
                       if ef else ())

            if fused:
                # flat-first: params and the f32 delta accumulator are
                # sharded buffer dicts; each client's contribution and
                # the final apply run shard-locally, one blocked kernel
                # per bucket (ShardedFlatOps)
                def zeros_delta():
                    return fops.zeros(jnp.float32)

                def add_delta(delta, w_end, w_i):
                    return fops.delta_accum(delta, w_end, params,
                                            w_i / wsum)

                def apply_delta(params_, delta):
                    return fops.apply_delta(params_, delta)

                # compressed uploads ARE deltas: each client compresses
                # its own f32 (w_end − p [+ residual]) shard-locally —
                # one lax.top_k + one blocked kernel pass per bucket
                # under shard_map — and the accumulator sums coeff·c
                # with the accum-only kernel (no −(Σc)·p term to apply;
                # the upload already subtracted p)
                def compress_client(w_end, r_row):
                    d = {name: w_end[name].astype(jnp.float32) -
                               params[name].astype(jnp.float32)
                         for name in w_end}
                    if ef:
                        d = {name: d[name] + r_row[name] for name in d}
                    return fops.compress_delta(d, comp)
            else:
                def zeros_delta():
                    return jax.tree_util.tree_map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params)

                def add_delta(delta, w_end, w_i):
                    # the running weighted delta sum IS the FedAvg all-reduce
                    return jax.tree_util.tree_map(
                        lambda d, we, p: d + (w_i / wsum) * (
                            we.astype(jnp.float32) - p.astype(jnp.float32)),
                        delta, w_end, params)

                def apply_delta(params_, delta):
                    return jax.tree_util.tree_map(
                        lambda p, d: (p.astype(jnp.float32) + d).astype(p.dtype),
                        params_, delta)

            if dp_clips:
                # DP clipping folds into the accumulation COEFFICIENT:
                # coeff_i = (w_i/wsum)·min(1, C/‖w_end − p‖) — the
                # p-present accumulators self-normalize, so clipping
                # costs a squared-norm reduction, not an extra pass
                sqnorm = privacy.flat_delta_sqnorm if fused else \
                    privacy.tree_delta_sqnorm
                base_add = add_delta

                def add_delta(delta, w_end, w_i):
                    scale = privacy.clip_scale(dp, sqnorm(w_end, params))
                    return base_add(delta, w_end, w_i * scale)

            # -- per-algorithm client step -------------------------------
            # client(k, cxi, cyi, row) -> (w_end, out, loss): ``row`` is
            # this client's state-store row (() when stateless), ``out``
            # the row to scatter back (() when none).  The aggregation
            # topologies below are generic over it.
            if algo in ("fedavg", "fedprox"):
                anchor = unpack(params, frozen) if algo == "fedprox" else None
                rows = ()

                def client(k, cxi, cyi, row):
                    extras = {"w_global": anchor} if algo == "fedprox" else {}
                    w_end, aux = local(k, params, extras, cxi, cyi, lr_scale,
                                       frozen)
                    return w_end, (), aux["loss"]

            elif algo == "scaffold":
                c, c_all = algo_state["c_global"], algo_state["c_clients"]
                rows = store.gather(c_all, ids)
                denom = spec.n_steps * spec.lr * lr_scale
                if fused:
                    # FLAT per-client state: the correction and the
                    # option-II control-variate update run directly on
                    # the row buffers — no per-client unflatten at all
                    def client(k, cxi, cyi, c_i_row):
                        c_diff = jax.tree_util.tree_map(
                            lambda g, l: g - l, c, c_i_row)
                        w_end, aux = local(k, params, {"c_diff_flat": c_diff},
                                           cxi, cyi, lr_scale, frozen)
                        c_i_new = jax.tree_util.tree_map(
                            lambda ci, cg, p, we: ci - cg + (p - we) / denom,
                            c_i_row, c, params, w_end)
                        return w_end, c_i_new, aux["loss"]
                else:
                    def client(k, cxi, cyi, c_i_row):
                        extras = {"c_diff": tm.sub(c, c_i_row)}
                        w_end, aux = local(k, params, extras, cxi, cyi,
                                           lr_scale, frozen)
                        # option II: c_i⁺ = c_i − c + (w − w_i)/(S·lr)
                        c_i_new = jax.tree_util.tree_map(
                            lambda ci, cg, p, we: ci - cg + (p - we) / denom,
                            c_i_row, c, params, w_end)
                        return w_end, c_i_new, aux["loss"]

            elif algo == "moon":
                w_prev_all = algo_state["w_prev"]
                rows = store.gather(w_prev_all, ids)
                anchor = unpack(params, frozen)  # loop-invariant: hoist
                if fused:
                    # rows are flat buffers; the tree materializes once
                    # per client at the loss boundary, and the local
                    # output scatters back as raw buffers
                    def client(k, cxi, cyi, w_prev_row):
                        extras = {"w_global": anchor,
                                  "w_prev": fops.unflatten(w_prev_row,
                                                           frozen)}
                        w_end, aux = local(k, params, extras, cxi, cyi,
                                           lr_scale, frozen)
                        return w_end, w_end, aux["loss"]
                else:
                    def client(k, cxi, cyi, w_prev_row):
                        extras = {"w_global": anchor, "w_prev": w_prev_row}
                        w_end, aux = local(k, params, extras, cxi, cyi,
                                           lr_scale, frozen)
                        return w_end, w_end, aux["loss"]

            else:
                raise ValueError(f"unknown algorithm {algo!r}")

            # -- aggregation topology ------------------------------------
            if G > 1:
                if K % G:
                    raise ValueError(
                        f"hierarchical aggregation needs clients_per_round "
                        f"divisible by n_pods (K={K}, n_pods={G})")
                S = K // G

                def resh(t):
                    return jax.tree_util.tree_map(
                        lambda a: a.reshape((S, G) + a.shape[1:]), t)

                vclient = jax.vmap(client, in_axes=(0, 0, 0, 0))
                # the lane axis shards over the mesh `data` axis when the
                # pod count matches it AND the carries are flat — each
                # data shard then owns one pod's p-free partial
                # (accum-only kernel) and the cross-pod combine lowers to
                # ONE psum over `data` per bucket; otherwise (1-device
                # test meshes, tree impl, mismatched n_pods) lanes stay
                # unsharded and the combine is a local tree-sum
                lane_psum = fused and G == fops.lane_count()
                if compressed:
                    # per-lane compressed uploads: every lane compresses
                    # its own client's delta before accumulating, so the
                    # lane partials are sums of coeff·c (accum-only, no
                    # −p rewrite needed — uploads already subtracted p)
                    # and the cross-pod combine is untouched
                    vcompress = jax.vmap(compress_client)
                    if lane_psum:
                        def one_step(delta_g, inp):
                            k_g, cx_g, cy_g, w_g, row_g, r_g = inp
                            w_end_g, out_g, loss_g = vclient(k_g, cx_g,
                                                             cy_g, row_g)
                            c_g, r_new_g = vcompress(w_end_g, r_g)
                            return (fops.lane_accum(delta_g, c_g,
                                                    w_g / wsum),
                                    (out_g, loss_g, r_new_g))

                        delta_g, (outs, losses, r_outs) = jax.lax.scan(
                            one_step, fops.lane_zeros(G),
                            resh((keys, cx, cy, w32, rows, ef_rows)))
                        delta = fops.lane_combine(delta_g)
                        delta = jax.lax.with_sharding_constraint(delta,
                                                                 p_sh)
                    else:
                        vadd = jax.vmap(
                            lambda a, c, w: fops.delta_accum(a, c, None, w))
                        delta0 = jax.tree_util.tree_map(
                            lambda d: jnp.zeros((G,) + d.shape, d.dtype),
                            zeros_delta())

                        def one_step(delta_g, inp):
                            k_g, cx_g, cy_g, w_g, row_g, r_g = inp
                            w_end_g, out_g, loss_g = vclient(k_g, cx_g,
                                                             cy_g, row_g)
                            c_g, r_new_g = vcompress(w_end_g, r_g)
                            return (vadd(delta_g, c_g, w_g / wsum),
                                    (out_g, loss_g, r_new_g))

                        delta_g, (outs, losses, r_outs) = jax.lax.scan(
                            one_step, delta0,
                            resh((keys, cx, cy, w32, rows, ef_rows)))
                        delta = jax.tree_util.tree_map(
                            lambda d: jnp.sum(d, axis=0), delta_g)
                elif lane_psum and dp_clips:
                    # clipped coefficients no longer sum to 1, so the
                    # −(Σc)·p term cannot factor out as −p: carry the
                    # running coefficient sum next to the p-free lane
                    # partials and apply −csum·p once after the combine
                    dp_scales = jax.vmap(
                        lambda we: privacy.clip_scale(
                            dp, privacy.flat_delta_sqnorm(we, params)))

                    def one_step(carry, inp):
                        delta_g, csum = carry
                        k_g, cx_g, cy_g, w_g, row_g = inp
                        w_end_g, out_g, loss_g = vclient(k_g, cx_g, cy_g,
                                                         row_g)
                        coeffs = (w_g / wsum) * dp_scales(w_end_g)
                        return ((fops.lane_accum(delta_g, w_end_g, coeffs),
                                 csum + jnp.sum(coeffs)),
                                (out_g, loss_g))

                    (delta_g, csum), (outs, losses) = jax.lax.scan(
                        one_step, (fops.lane_zeros(G), jnp.float32(0.0)),
                        resh((keys, cx, cy, w32, rows)))
                    acc = fops.lane_combine(delta_g)
                    acc = jax.lax.with_sharding_constraint(acc, p_sh)
                    delta = {name: acc[name] -
                             csum * params[name].astype(jnp.float32)
                             for name in acc}
                elif lane_psum:
                    def one_step(delta_g, inp):
                        k_g, cx_g, cy_g, w_g, row_g = inp
                        w_end_g, out_g, loss_g = vclient(k_g, cx_g, cy_g,
                                                         row_g)
                        return (fops.lane_accum(delta_g, w_end_g,
                                                w_g / wsum),
                                (out_g, loss_g))

                    delta_g, (outs, losses) = jax.lax.scan(
                        one_step, fops.lane_zeros(G),
                        resh((keys, cx, cy, w32, rows)))
                    acc = fops.lane_combine(delta_g)
                    acc = jax.lax.with_sharding_constraint(acc, p_sh)
                    # A = Σᵢ cᵢ·wᵢ came back combined; the −(Σc)·p term
                    # factors out exactly (Σᵢ wᵢ/wsum = 1), applied once
                    delta = {name: acc[name] -
                             params[name].astype(jnp.float32)
                             for name in acc}
                else:
                    vadd = jax.vmap(add_delta, in_axes=(0, 0, 0))
                    delta0 = jax.tree_util.tree_map(
                        lambda d: jnp.zeros((G,) + d.shape, d.dtype),
                        zeros_delta())

                    def one_step(delta_g, inp):
                        k_g, cx_g, cy_g, w_g, row_g = inp
                        w_end_g, out_g, loss_g = vclient(k_g, cx_g, cy_g,
                                                         row_g)
                        return vadd(delta_g, w_end_g, w_g), (out_g, loss_g)

                    delta_g, (outs, losses) = jax.lax.scan(
                        one_step, delta0, resh((keys, cx, cy, w32, rows)))
                    # the single cross-pod combine: one reduction per
                    # bucket over the G pod partials
                    delta = jax.tree_util.tree_map(
                        lambda d: jnp.sum(d, axis=0), delta_g)
                # (S, G, ...) lane outputs fold back to client order —
                # client j ran as step j//G, lane j%G
                outs = jax.tree_util.tree_map(
                    lambda a: a.reshape((K,) + a.shape[2:]), outs)
                losses = losses.reshape(K)
                if ef:
                    r_outs = jax.tree_util.tree_map(
                        lambda a: a.reshape((K,) + a.shape[2:]), r_outs)
            elif compressed:
                def one_client(delta, inp):
                    k, cxi, cyi, w_i, row, r_row = inp
                    w_end, out, loss = client(k, cxi, cyi, row)
                    c, r_new = compress_client(w_end, r_row)
                    return (fops.delta_accum(delta, c, None, w_i / wsum),
                            (out, loss, r_new))

                delta, (outs, losses, r_outs) = jax.lax.scan(
                    one_client, zeros_delta(),
                    (keys, cx, cy, w32, rows, ef_rows))
            else:
                def one_client(delta, inp):
                    k, cxi, cyi, w_i, row = inp
                    w_end, out, loss = client(k, cxi, cyi, row)
                    return add_delta(delta, w_end, w_i), (out, loss)

                delta, (outs, losses) = jax.lax.scan(
                    one_client, zeros_delta(), (keys, cx, cy, w32, rows))

            # aggregated DP noise + secure-agg masks: independent of the
            # client outputs, so computed once per round and added to the
            # f32 delta in every topology (None statically when off)
            extra = privacy.round_extra(
                dp, spec.secure_agg, key, ids, w32 / wsum,
                zeros_fn=zeros_delta,
                normal_fn=fops.normal if fused else
                (lambda k: privacy.tree_normal(k, params)))
            if extra is not None:
                delta = jax.tree_util.tree_map(jnp.add, delta, extra)

            new_params = pin(apply_delta(params, delta))

            if algo == "scaffold":
                # c ← c + (K/N)·mean_i(c_i⁺ − c_i); N is the population
                frac = K / store.population(c_all)
                c_new = jax.tree_util.tree_map(
                    lambda cg, new, old: cg + frac * jnp.mean(new - old,
                                                              axis=0),
                    c, outs, rows)
                state = dict(algo_state, c_global=c_new,
                             c_clients=store.scatter(c_all, ids, outs))
            elif algo == "moon":
                state = dict(algo_state,
                             w_prev=store.scatter(w_prev_all, ids, outs))
            else:
                state = algo_state
            if ef:
                state = dict(state, ef_residuals=ef_store.scatter(
                    algo_state["ef_residuals"], ids, r_outs))
            return new_params, state, jnp.mean(losses)

        return body


# ---------------------------------------------------------------------------
# declarative phase configs (core.pipeline entries)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PodCyclicConfig:
    """P1 relay phase on the pod backend."""
    mesh: Any
    rounds: int = 4
    clients_per_round: int = 4
    spec: PodFLSpec = PodFLSpec()
    layout: str = "fsdp_tp"
    lr_decay: float = 1.0           # the pod driver historically had no decay
    eval_every: int = 0
    eval_batch: int = 64
    seed: int = 0
    chunk_size: int = 4
    sampling: str = "device"        # device | host (seed-compatible)

    def strategy(self) -> PodRelayStrategy:
        return PodRelayStrategy(
            spec=self.spec.local_spec("plain"), mesh=self.mesh,
            layout=self.layout, clients_per_round=self.clients_per_round)

    def schedule(self) -> RoundSchedule:
        return RoundSchedule(
            rounds=self.rounds, lr_decay=self.lr_decay,
            eval_every=self.eval_every, eval_batch=self.eval_batch,
            seed=self.seed, chunk_size=self.chunk_size,
            sampling=self.sampling, host_rng_offset=HOST_RNG_OFFSET_P1)


@dataclasses.dataclass(frozen=True)
class PodFLConfig:
    """P2 aggregation phase on the pod backend (algorithm from spec)."""
    mesh: Any
    rounds: int = 4
    clients_per_round: int = 4
    spec: PodFLSpec = PodFLSpec()
    layout: str = "fsdp_tp"
    lr_decay: float = 1.0
    eval_every: int = 0
    eval_batch: int = 64
    seed: int = 0
    chunk_size: int = 4
    sampling: str = "device"
    aggregation: str = "sequential"     # sequential | hierarchical
    n_pods: Optional[int] = None
    store: str = "dense"                # dense | sparse
    store_capacity: int = 1024          # sparse active-set rows
    overlap: bool = True                # pipeline residency behind compute

    def strategy(self) -> PodAggregateStrategy:
        kwargs = {}
        if self.store == "sparse":
            kwargs["state_store"] = ShardedSparseClientStateStore(
                capacity=self.store_capacity, mesh=self.mesh)
        elif self.store != "dense":
            raise ValueError(f"unknown store {self.store!r} "
                             f"(choose from ('dense', 'sparse'))")
        return PodAggregateStrategy(
            spec=self.spec.local_spec(), algorithm=self.spec.algorithm,
            server_opt=self.spec.server_opt, server_lr=self.spec.server_lr,
            server_momentum=self.spec.server_momentum,
            mesh=self.mesh, layout=self.layout,
            clients_per_round=self.clients_per_round,
            aggregation=self.aggregation, n_pods=self.n_pods, **kwargs)

    def schedule(self) -> RoundSchedule:
        return RoundSchedule(
            rounds=self.rounds, lr_decay=self.lr_decay,
            eval_every=self.eval_every, eval_batch=self.eval_batch,
            seed=self.seed, chunk_size=self.chunk_size,
            sampling=self.sampling, host_rng_offset=HOST_RNG_OFFSET_P2,
            overlap=self.overlap)


def run_pod_rounds(task: Task, data: FederatedDataset, cfg,
                   init_params: Optional[Pytree] = None,
                   ledger=None, verbose: bool = False,
                   eval_fn: Optional[Callable] = None,
                   switch_policy=None, phase: str = "P2"):
    """Phase runner for the pod configs — the engine loop does the work."""
    strategy = cfg.strategy()
    return run_rounds(task, data, strategy, cfg.schedule(),
                      init_params=init_params, ledger=ledger, verbose=verbose,
                      eval_fn=eval_fn, switch_policy=switch_policy,
                      phase=phase, label=f"pod-{strategy.name}")


# register with the declarative schedule so Phase(cfg=Pod*Config) works
from repro.core.pipeline import register_phase_runner  # noqa: E402

register_phase_runner(PodCyclicConfig, "relay", run_pod_rounds)
register_phase_runner(PodFLConfig, "aggregate", run_pod_rounds)
