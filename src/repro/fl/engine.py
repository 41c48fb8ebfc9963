"""The federated round engine — ONE driver for both CyclicFL phases.

The paper's P1 (cyclic relay) and P2 (FedAvg-style rounds) are two
phases of one training process; this module is the single loop that runs
either, parameterized by a ``RoundStrategy``:

  RelayStrategy     : P1 — sequential ``lax.scan`` over the selected
                      clients carrying the model, NO aggregation
                      (Algorithm 1's server-relayed download/upload).
  AggregateStrategy : P2 — ``vmap`` over the selected clients + weighted
                      mean, with pluggable algorithm state for
                      fedavg / fedprox / scaffold / moon and an optional
                      server-side optimizer (FedAvgM / FedAdam) — on
                      BOTH backends: the pod shards the optimizer
                      moments exactly like the params they mirror.

Both the per-step client update and the per-round aggregation/server
step run either as per-leaf tree algebra (``update_impl="tree"``, the
parity oracle) or FLAT-FIRST (``update_impl="fused"``): the chunk
carries params and server-optimizer moments as contiguous FlatParamOps
buffers from phase start to phase end, the vmapped local outputs arrive
as already-stacked ``(K, N)`` buffers (no re-concatenate), and every
update stage is a blocked kernel per bucket
(repro.kernels.fused_update).  Trees materialize in exactly three
places: inside the loss closure (the model's forward/backward
boundary), at the in-program eval metric, and in the final
:class:`EngineResult` — the spec-level knob threads from LocalSpec
through every strategy, and the strategy's :meth:`flat_ops` picks the
buffer flavor (host FlatView; pod ShardedFlatView, see repro.fl.pod).

The engine owns everything the three seed drivers each re-implemented:

  * client selection — ON DEVICE by default: a
    ``jax.random.permutation``-based without-replacement draw folded
    into the jitted round program (``sampling="host"`` reproduces the
    seed drivers' ``np.random.default_rng`` streams bit-for-bit for
    parity testing);
  * round chunking — ``lax.scan`` over a chunk of R rounds per XLA
    dispatch with donated carries, so the host dispatches once per
    chunk and losses come back as one stacked array;
  * evaluation — IN PROGRAM: the chunk takes a per-round eval mask as a
    scan input and a pre-batched test stream as arguments, computes the
    eval metric under ``lax.cond`` on rounds where the mask is set
    (NaN-masked otherwise) and emits an (R,) metric stream next to the
    losses.  ``eval_every`` and ``chunk_size`` are therefore fully
    decoupled: evaluating runs cost zero extra dispatches, and
    histories stay chunk-size invariant because the mask is computed
    from global round indices on the host;
  * the lr-decay schedule, ``CommLedger`` recording and history rows;
  * switch policies (core.switch) at any phase boundary — when a policy
    is installed the engine pins chunk=1 so per-round early exit keeps
    the seed drivers' semantics.

``core.cyclic.cyclic_pretrain`` and ``fl.simulation.run_federated`` are
thin shims over :func:`run_rounds`; ``core.pipeline`` sequences phases
declaratively.

Backend contract
----------------
The loop machinery above is generic over WHERE a round runs.  A strategy
is also a *backend*: three hooks (defaulted by :class:`HostBackend` to
the single-process jit path) decide how data, params and the compiled
chunk program are placed:

  prepare_data(data)            -> (x_all, y_all, n_real) device arrays;
                                   a sharded backend device_puts the
                                   stacked client arrays with mesh
                                   placements (see repro.fl.pod).
  prepare_eval_data(batched)    -> (ev_x, ev_y, ev_w) device arrays for
                                   the in-program eval stream — the
                                   (n_batches, B, ...) batched test set
                                   plus the (n_batches, B) pad-validity
                                   weights (pod: batch axis sharded
                                   over (pod, data)).
  place_params(params)          -> the engine's working copy of the
                                   model (host: plain copy so donation
                                   cannot invalidate the caller's tree;
                                   pod: device_put with
                                   rules.param_shardings).
  place_server_state(state, t)  -> placement for the server-optimizer
                                   moments (host: identity; pod:
                                   device_put with param shardings so
                                   FedAvgM/FedAdam state shards like
                                   the params it mirrors).
  jit_chunk(chunk, task, n)     -> the compiled R-round program.  The
                                   host backend jits with donated
                                   carries only; the pod backend adds
                                   in_shardings/out_shardings for every
                                   carry so chunked dispatch runs as one
                                   SPMD program on the mesh.

ClientStateStore contract
-------------------------
Per-client algorithm state (SCAFFOLD control variates, Moon previous
local models) lives behind a ``ClientStateStore`` so its residency is a
backend decision, not an algorithm decision.  The state is an opaque
pytree owned by the store; the round body only ever sees the K selected
rows, which makes the stores representation-agnostic — the same store
holds tree rows on the tree path and flat ``(N,)`` buffer-dict rows on
the fused path:

  init(template, n_clients)     -> the store's state pytree (eager,
                                   once per engine run)
  gather(state, ids)            -> the selected K rows (inside jit)
  scatter(state, ids, rows)     -> state with rows written back
  population(state)             -> n_clients (the K/N scaffold fraction
                                   must count the population, not the
                                   store's physical rows)
  shardings(template, n, mesh)  -> placement pytree for jit
                                   in_shardings (None on the host)
  needs_host_ids                -> class attr; True if the store must
                                   see the NEXT dispatch's client ids
                                   before the chunk runs
  prepare_chunk(state, ids)     -> host-side residency step run between
                                   dispatches when ``needs_host_ids``
                                   (no-op for dense stores)

``DenseClientStateStore`` keeps the dense host stacks (seed semantics);
``SparseClientStateStore`` is the participation-indexed store — a
bounded ``(capacity, ...)`` active-set table plus an id→slot index,
with LRU eviction and host-spilled cold rows, so state memory scales
with *participation* (capacity) instead of population and million-client
populations fit where the dense stacks OOM.
``repro.fl.pod.ShardedClientStateStore`` /
``ShardedSparseClientStateStore`` shard the leading row axis over the
mesh ``data`` axis so scaffold/moon run at pod scale without a
replicated (n_clients, model) blow-up.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.federated import FederatedDataset
from repro.fl import compression, privacy
from repro.fl.local import (
    FlatParamOps,
    LocalSpec,
    effective_trainable_filter,
    host_flat_ops,
    make_local_fn,
)
from repro.fl.task import Task
from repro.kernels import ops
from repro.utils import tree_math as tm
from repro.utils.spans import scoped, span

Pytree = Any

ALGORITHMS = ("fedavg", "fedprox", "scaffold", "moon")

# FedAdam (server_opt="adam") moment decays — shared by the tree
# optimizer construction, the fused kernel call AND its bias-correction
# scalars, so the two implementations cannot drift apart
SERVER_ADAM_B1 = 0.9
SERVER_ADAM_B2 = 0.99


# ---------------------------------------------------------------------------
# pytree helpers shared by the aggregation algorithms
# ---------------------------------------------------------------------------

def stack_copies(tree: Pytree, n: int) -> Pytree:
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape).copy(), tree)


def tree_rows(tree: Pytree, ids: jnp.ndarray) -> Pytree:
    return jax.tree_util.tree_map(lambda x: x[ids], tree)


def tree_set_rows(tree: Pytree, ids: jnp.ndarray, rows: Pytree) -> Pytree:
    return jax.tree_util.tree_map(lambda x, r: x.at[ids].set(r.astype(x.dtype)),
                                  tree, rows)


def fused_aggregate(fops: FlatParamOps, p_bufs: Dict, stacked_bufs: Dict,
                    weights: jnp.ndarray) -> Dict:
    """FedAvg aggregation on the flat path: the vmapped flat local
    outputs are ALREADY the stacked ``(K, N)`` buffers (one per bucket),
    so aggregation is one blocked kernel per bucket
    (``ops.fused_weighted_delta``) with zero packing — the
    ``flatten_stacked`` re-concatenate of the PR-4 flow is gone."""
    wbar = (weights / jnp.sum(weights)).astype(jnp.float32)
    return fops.weighted_delta(p_bufs, stacked_bufs, wbar)


@functools.lru_cache(maxsize=64)
def _logical_model_bytes(task: Task) -> int:
    """X for the comm ledger: the LOGICAL model capacity from the task's
    param shapes — never the engine's carried representation, whose
    grid-padded flat buffers would over-count, and whose padding differs
    between P1/P2 and host/pod while the wire cost does not."""
    p_specs = jax.eval_shape(task.init, jax.random.PRNGKey(0))
    return tm.size_bytes(p_specs)


@functools.lru_cache(maxsize=64)
def _upload_payload_bytes(task: Task, comp,
                          filter_spec: Optional[str] = None) -> int:
    """Closed-form wire bytes of ONE client upload over the task's
    logical TRAINABLE flat bucket sizes (the accounting wire model on
    both backends — the pod's per-shard split carries the same logical
    elements).  With a trainable filter the sizes are the trainable
    slice only — frozen leaves never hit the wire — so the PEFT ratio
    composes multiplicatively with the compression ratio.  Uncompressed
    uploads count dtype-aware logical bytes (the bucket name IS the
    dtype), matching :func:`_logical_model_bytes` for ``filter=None``.
    """
    view = host_flat_ops(task, True, filter_spec).view
    if compression.compression_on(comp):
        return compression.payload_bytes(
            comp, tuple(view.buffer_sizes.values()))
    return int(sum(np.dtype(name).itemsize * size
                   for name, size in view.buffer_sizes.items()))


def unpack_server_state(fops: FlatParamOps, state: Any) -> Any:
    """Materialize a flat server OptState's moment buffers back into
    param-shaped trees (the EngineResult boundary)."""
    from repro.optim.optimizers import AdamWState, OptState
    if not isinstance(state, OptState):
        return state
    inner = state.inner
    if isinstance(inner, AdamWState):
        inner = AdamWState(mu=fops.unflatten(inner.mu),
                           nu=fops.unflatten(inner.nu))
    elif isinstance(inner, dict) and inner:
        inner = fops.unflatten(inner)
    return OptState(step=state.step, inner=inner)


# ---------------------------------------------------------------------------
# backends + per-client state stores
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DenseClientStateStore:
    """Per-client state as dense host stacks — the seed representation.

    gather/scatter are jit-traceable; ``init`` runs eagerly once per
    engine run.  See the module docstring for the full contract.  This
    store is the parity oracle for :class:`SparseClientStateStore`.
    """

    needs_host_ids = False

    def init(self, template: Pytree, n_clients: int) -> Pytree:
        return stack_copies(template, n_clients)

    def gather(self, state: Pytree, ids: jnp.ndarray) -> Pytree:
        return tree_rows(state, ids)

    def scatter(self, state: Pytree, ids: jnp.ndarray, rows: Pytree) -> Pytree:
        return tree_set_rows(state, ids, rows)

    def population(self, state: Pytree) -> int:
        return jax.tree_util.tree_leaves(state)[0].shape[0]

    def prepare_chunk(self, state: Pytree, ids_block) -> Pytree:
        return state                    # dense rows are always resident

    def shardings(self, template: Pytree, n_clients: int, mesh) -> Any:
        return None                     # host: no placement constraint


DENSE_STORE = DenseClientStateStore()


_SPILL_POOL: Optional[concurrent.futures.ThreadPoolExecutor] = None


def _spill_pool() -> concurrent.futures.ThreadPoolExecutor:
    """One background worker shared by every sparse store: spill blocks
    convert their device rows to numpy OFF the engine thread.  A single
    worker serializes the conversions, so at most one competes with the
    engine's dispatch enqueue for host cycles."""
    global _SPILL_POOL
    if _SPILL_POOL is None:
        _SPILL_POOL = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="spill-materialize")
    return _SPILL_POOL


class _SpillBlock:
    """One dispatch's stacked evicted rows, parked on the CPU device by a
    single (async) batched transfer at commit time.  ``commit_chunk``
    submits the numpy materialization to a background worker
    (:meth:`materialize_async`) — the conversion blocks until the
    dispatch that produced the source table drains, so running it on the
    worker hides that wait AND the copy itself off the critical path; by
    the time a refault burst needs the rows in ``stage_chunk``,
    ``leaves()`` just joins the (usually finished) worker.  Blocks that
    were never submitted (direct construction in tests) keep the old
    lazy first-refault conversion."""

    __slots__ = ("rows", "_np", "_future")

    def __init__(self, rows):
        self.rows = rows                # list of (n_evicted, ...) leaves
        self._np = None
        self._future = None

    def materialize_async(self, meta: Optional[dict] = None) -> None:
        """Convert to numpy on the shared background worker; ``meta``
        (the owning store's ``_meta``) accumulates the off-thread ms
        under ``"spill_ms"`` — single-writer, the one pool worker."""
        if self._future is None and self._np is None:
            self._future = _spill_pool().submit(self._materialize, meta)

    def _materialize(self, meta: Optional[dict]):
        with span("store.spill", meta, "spill_ms"):
            out = [np.asarray(leaf) for leaf in self.rows]
        self._np = out
        self.rows = None                # drop the device handles
        return out

    def leaves(self):
        f = self._future
        if f is not None:
            f.result()                  # join the background conversion
            self._future = None
        if self._np is None:
            self._np = [np.asarray(leaf) for leaf in self.rows]
            self.rows = None
        return self._np


@dataclasses.dataclass(frozen=True, eq=False)
class SparseClientStateStore:
    """Participation-indexed per-client state: a bounded active-set
    table instead of a dense population stack.

    The state pytree is ``{"table", "slot_of", "owner", "stamp"}``:
    ``table`` stacks ``capacity`` rows of the per-client template,
    ``slot_of`` is the ``(n_clients,)`` id→slot index (−1 = cold),
    ``owner``/``stamp`` the ``(capacity,)`` slot→id back-map and LRU
    clock.  gather/scatter run inside jit over *slots* — O(capacity)
    device memory however large the population — while residency is
    managed eagerly between dispatches in two halves:

      stage_chunk(ids_block) -> staged   (host planning + async H2D)
      commit_chunk(state, staged) -> state  (device-side splice, enqueued)

    :meth:`stage_chunk` plans against HOST MIRRORS of the residency
    index (kept in ``_meta``), so it never reads — and never blocks
    on — the device carries of an in-flight dispatch: the engine's
    overlapped loop stages dispatch N+1 while dispatch N is still
    executing.  Cold participants fault in from the spill dict (evicting
    the least-recently-used non-participating slots); the refill rows
    are stacked into a reused pinned staging buffer and shipped as ONE
    ``jax.device_put`` per template leaf, without ``block_until_ready``.
    :meth:`commit_chunk` then enqueues one batched spill gather of the
    evicted live rows (reading the LATEST table, so rows written by the
    previous dispatch spill with their updates, async-copied to the CPU
    device) and splices the staged rows plus the index updates in —
    pure functional device ops, nothing blocks.  ``prepare_chunk``
    composes the two for the synchronous path, so the classic contract
    is unchanged; ``spill=False`` drops evicted rows instead — a
    documented *forgetful* mode that trades parity for zero host
    traffic.

    ``capacity`` must cover the distinct participants of one dispatch
    (chunk_size × K in the worst case); stage_chunk raises otherwise.
    Eager members (the spill dict, the mirrors, the staging buffers)
    make this store identity-hashed (``eq=False``), which is exactly
    what the chunk cache wants — two stores are two cache entries.
    """

    capacity: int
    spill: bool = True
    _cold: dict = dataclasses.field(default_factory=dict, repr=False)
    _meta: dict = dataclasses.field(default_factory=dict, repr=False)

    needs_host_ids = True

    def __post_init__(self):
        if self.capacity < 1:
            raise ValueError("SparseClientStateStore capacity must be >= 1")

    def init(self, template: Pytree, n_clients: int) -> Pytree:
        leaves, treedef = jax.tree_util.tree_flatten(template)
        self._cold.clear()
        cap = max(1, min(self.capacity, n_clients))
        self._meta["treedef"] = treedef
        self._meta["template"] = [np.asarray(leaf) for leaf in leaves]
        # host mirrors of the residency index: stage_chunk plans against
        # these, so planning never synchronizes with the device
        self._meta["slot_of"] = np.full((n_clients,), -1, np.int32)
        self._meta["owner"] = np.full((cap,), -1, np.int32)
        self._meta["stamp"] = np.zeros((cap,), np.int32)
        self._meta["stage_bufs"] = None
        self._meta["transfer_ms"] = 0.0
        self._meta["spill_ms"] = 0.0
        return {
            "table": stack_copies(template, cap),
            "slot_of": jnp.full((n_clients,), -1, jnp.int32),
            "owner": jnp.full((cap,), -1, jnp.int32),
            "stamp": jnp.zeros((cap,), jnp.int32),
        }

    def gather(self, state: Pytree, ids: jnp.ndarray) -> Pytree:
        # residency is a precondition: prepare_chunk ran for these ids
        return tree_rows(state["table"], state["slot_of"][ids])

    def scatter(self, state: Pytree, ids: jnp.ndarray, rows: Pytree) -> Pytree:
        slots = state["slot_of"][ids]
        return dict(state, table=tree_set_rows(state["table"], slots, rows))

    def population(self, state: Pytree) -> int:
        return state["slot_of"].shape[0]

    def shardings(self, template: Pytree, n_clients: int, mesh) -> Any:
        return None                     # host flavor: no constraint

    @property
    def staged_transfer_ms(self) -> float:
        """Cumulative wall time spent enqueueing refill transfers."""
        return float(self._meta.get("transfer_ms", 0.0))

    @property
    def spill_materialize_ms(self) -> float:
        """Cumulative background time converting spilled rows to numpy —
        host ms moved OFF the stage/commit critical path (satellite of
        the overlapped pipeline: a refault burst no longer pays the
        device→numpy conversion inside ``stage_chunk``)."""
        return float(self._meta.get("spill_ms", 0.0))

    # -- host-side residency (eager, between dispatches) --------------------

    def _pop_cold(self, cid: int):
        row = self._cold.pop(cid, None)
        if isinstance(row, tuple):      # lazy ref into a spill block
            block, j = row
            return [leaf[j] for leaf in block.leaves()]
        return row

    def _cold_row(self, cid: int):
        row = self._cold.get(cid)
        if isinstance(row, tuple):
            block, j = row
            return [leaf[j] for leaf in block.leaves()]
        return row

    def _stage_rows(self, fill):
        """Stack the refill rows into a pinned staging buffer (grown
        geometrically, reused across dispatches — safe because at most
        one staged plan exists at a time and ``jax.device_put`` copies
        out of numpy before returning)."""
        tmpl = self._meta["template"]
        if not tmpl:
            return []
        n = len(fill)
        bufs = self._meta.get("stage_bufs")
        if bufs is None or bufs[0].shape[0] < n:
            rows_cap = max(n, 2 * (bufs[0].shape[0] if bufs else 4))
            bufs = [np.empty((rows_cap,) + t.shape, t.dtype) for t in tmpl]
            self._meta["stage_bufs"] = bufs
        for j, row in enumerate(fill):
            for i in range(len(tmpl)):
                bufs[i][j] = row[i]
        return [buf[:n] for buf in bufs]

    def _refill_placement(self, victims: np.ndarray):
        return None                     # host flavor: default device

    def stage_chunk(self, ids_block) -> Dict[str, Any]:
        """Plan residency for the NEXT dispatch and start its refill
        transfer — host work only, against the mirror index, so it can
        run while the previous dispatch is still executing on device."""
        ids = np.unique(np.asarray(ids_block))
        slot_of = self._meta["slot_of"]
        owner = self._meta["owner"]
        stamp = self._meta["stamp"]
        cap = owner.shape[0]
        slots_ids = slot_of[ids]
        miss = ids[slots_ids < 0]
        staged: Dict[str, Any] = {"victims": None}
        if miss.size:
            resident = slots_ids[slots_ids >= 0]
            cand = np.setdiff1d(np.arange(cap), resident)
            # free slots first, then coldest-first among the owned ones
            order = np.argsort(np.where(owner[cand] < 0, -1, stamp[cand]),
                               kind="stable")
            cand = cand[order]
            if miss.size > cand.size:
                raise ValueError(
                    f"store capacity {cap} cannot hold the {ids.size} "
                    f"distinct clients of the next dispatch "
                    f"({miss.size} cold, {cand.size} evictable slots) — "
                    f"raise --store-capacity above chunk_size × K")
            # sorted victims keep the staged rows in slot order, so a
            # sharded flavor can land each row on its owning shard
            victims = np.sort(cand[:miss.size])
            evicted = owner[victims].copy()
            # refill: spilled row if the client was seen before, else
            # the init template
            tmpl = self._meta["template"]
            fill = [self._pop_cold(int(cid)) or tmpl for cid in miss]
            rows_np = self._stage_rows(fill)
            with span("store.transfer", self._meta, "transfer_ms"):
                placement = self._refill_placement(victims)
                rows_dev = [jax.device_put(r) if placement is None
                            else jax.device_put(r, s)
                            for r, s in zip(rows_np,
                                            _broadcast(placement,
                                                       len(rows_np)))]
            gone = evicted[evicted >= 0]
            slot_of[gone] = -1
            slot_of[miss] = victims
            owner[victims] = miss
            staged.update(victims=victims, miss=miss, gone=gone,
                          evicted=evicted, rows=rows_dev)
        # touch every participant's slot so the LRU order tracks rounds
        touch = int(stamp.max()) + 1
        slots = slot_of[ids]
        stamp[slots] = touch
        staged.update(touch_slots=slots.copy(), touch_value=touch)
        return staged

    def commit_chunk(self, state: Pytree, staged: Dict[str, Any]) -> Pytree:
        """Apply a staged plan to the device-side state.  Everything here
        is an enqueued functional update on the carry handles — spilling
        gathers from the LATEST table (the output of the dispatch that
        last wrote it) in one stacked transfer, and the staged refill
        rows splice in with one scatter — so committing on top of an
        in-flight chunk's outputs just extends the device queue."""
        table, slot_of = state["table"], state["slot_of"]
        owner, stamp = state["owner"], state["stamp"]
        victims = staged["victims"]
        if victims is not None:
            evicted = staged["evicted"]
            live = evicted >= 0
            if self.spill and np.any(live):
                rows = tree_rows(table, jnp.asarray(victims[live]))
                # cold rows park on the CPU device; without one (JAX
                # started with a platform list that leaves CPU out)
                # jax.devices raises — spilled rows never stay in HBM
                rows = jax.device_put(rows, jax.devices("cpu")[0])
                block = _SpillBlock(jax.tree_util.tree_leaves(rows))
                # eager off-thread materialization: the conversion waits
                # for the in-flight dispatch on the WORKER, not here
                block.materialize_async(self._meta)
                for j, cid in enumerate(evicted[live]):
                    self._cold[int(cid)] = (block, j)
            rows_tree = jax.tree_util.tree_unflatten(
                self._meta["treedef"], [jnp.asarray(r)
                                        for r in staged["rows"]])
            table = tree_set_rows(table, jnp.asarray(victims), rows_tree)
            gone = staged["gone"]
            if gone.size:
                slot_of = slot_of.at[jnp.asarray(gone)].set(-1)
            slot_of = slot_of.at[jnp.asarray(staged["miss"])].set(
                jnp.asarray(victims, jnp.int32))
            owner = owner.at[jnp.asarray(victims)].set(
                jnp.asarray(staged["miss"], jnp.int32))
        stamp = stamp.at[jnp.asarray(staged["touch_slots"])].set(
            jnp.int32(staged["touch_value"]))
        return {"table": table, "slot_of": slot_of,
                "owner": owner, "stamp": stamp}

    def prepare_chunk(self, state: Pytree, ids_block) -> Pytree:
        return self.commit_chunk(state, self.stage_chunk(ids_block))

    # -- debugging / parity helper ------------------------------------------

    def to_dense(self, state: Pytree) -> Pytree:
        """Materialize the full ``(n_clients, ...)`` stack (hot rows from
        the table, cold rows from the spill dict, template otherwise) —
        test/debug only; defeats the point at scale."""
        slot_of = np.asarray(state["slot_of"])
        n = slot_of.shape[0]
        tmpl = self._meta["template"]
        table_leaves = [np.asarray(leaf) for leaf
                        in jax.tree_util.tree_leaves(state["table"])]
        out = [np.broadcast_to(leaf, (n,) + leaf.shape).copy()
               for leaf in tmpl]
        for cid in range(n):
            slot = slot_of[cid]
            row = table_leaves if slot >= 0 else self._cold_row(cid)
            if row is None:
                continue
            for i in range(len(out)):
                out[i][cid] = row[i][slot] if slot >= 0 else row[i]
        return jax.tree_util.tree_unflatten(
            self._meta["treedef"], [jnp.asarray(o) for o in out])


def _broadcast(placement, n: int):
    """Per-leaf placements for the staged refill transfer: a list is
    taken as-is, anything else repeats for every leaf."""
    if isinstance(placement, (list, tuple)):
        return list(placement)
    return [placement] * n


def _replay_device_sampling(key, n_clients: int, K: int, R: int):
    """Replay the chunk's in-program client draws on the host: the chunk
    derives round r's selection key by the fixed split recurrence below
    (see ``_cached_chunk_fn.one_round``), and threefry is deterministic,
    so the replay is bit-identical to what the next dispatch will draw.
    Sparse stores use this under ``sampling="device"`` to fault rows in
    *before* the chunk runs — residency only, the program itself still
    draws its ids in-program, unchanged.  Costs O(R · n_clients) host
    work per chunk; prefer ``sampling="host"`` at very large n_clients.

    Returns ``(ids, key_after)`` — the advanced key lets the overlapped
    loop replay chunk N+1's draws before chunk N's carried key exists as
    anything but an in-flight device handle.
    """
    out = []
    for _ in range(R):
        key, rk = jax.random.split(key)
        k_sel, _ = jax.random.split(rk)
        out.append(np.asarray(jax.random.permutation(k_sel, n_clients)[:K]))
    return np.stack(out), key


class HostBackend:
    """Default backend hooks: single-process jit, host-resident data."""

    def flat_ops(self, task: Task):
        """The strategy's flat-buffer representation, or None on the
        tree path.  When set, the engine's chunk carries params and
        server moments as this object's buffer dicts (flat-first); the
        pod backend overrides it with mesh-sharded buffers."""
        if self.spec.update_impl == "tree":
            return None
        return host_flat_ops(task, ops.fused_interpret(self.spec.update_impl),
                             effective_trainable_filter(self.spec))

    def prepare_data(self, data: FederatedDataset):
        return data.device_arrays()

    def prepare_eval_data(self, batched: Tuple) -> Tuple:
        return tuple(jnp.asarray(a) for a in batched)

    def place_params(self, params: Pytree) -> Pytree:
        # donated carries: copy so the caller's init_params buffer survives
        return jax.tree_util.tree_map(jnp.array, params)

    def place_server_state(self, state: Pytree, task: Task) -> Pytree:
        return state

    def prepare_chunk_state(self, algo_state: Dict, ids_block) -> Dict:
        """Hook run before every chunk dispatch when the strategy's
        store needs host-side residency management (see the
        ClientStateStore contract); the default is a no-op."""
        return algo_state

    def stage_chunk_state(self, ids_block) -> Any:
        """First half of :meth:`prepare_chunk_state`: host planning +
        async staging transfers only, no device-state reads — safe to
        run while the previous dispatch is still executing.  Returns an
        opaque token for :meth:`commit_chunk_state` (None = nothing to
        do)."""
        return None

    def commit_chunk_state(self, algo_state: Dict, staged: Any) -> Dict:
        """Second half: splice a staged plan into the (possibly still
        in-flight) algo-state carry.  Must be enqueue-only."""
        return algo_state

    def jit_chunk(self, chunk: Callable, task: Task,
                  n_clients: int) -> Callable:
        return jax.jit(chunk, donate_argnums=(0, 1, 2, 3))


# ---------------------------------------------------------------------------
# strategies
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RelayStrategy(HostBackend):
    """P1 — Algorithm 1's sequential relay.  The model hops client →
    client inside one scan; the carry IS the relay."""
    spec: LocalSpec
    participation: float = 0.25

    name = "relay"

    def __post_init__(self):
        # P1 has no aggregation step: there is nothing to clip, noise or
        # mask, so a privacy spec on the relay is a config error
        if self.spec.dp is not None or self.spec.secure_agg:
            raise ValueError("RelayStrategy (P1) has no aggregation; "
                             "dp/secure_agg apply to P2 only")
        # ... and the relayed model IS the next client's start state, so
        # a lossy upload would corrupt training, not just the aggregate
        if compression.compression_on(self.spec.compression):
            raise ValueError("RelayStrategy (P1) relays the model itself; "
                             "lossy compression applies to P2 round "
                             "deltas only")
        # ... and the relay hops the FULL model client → client — a
        # trainable-slice filter would freeze most of what P1 exists to
        # pre-train, so it is a config error here (the pod launcher
        # strips it for P1 like dp/compression)
        if self.spec.peft is not None or self.spec.trainable_filter is not None:
            raise ValueError("RelayStrategy (P1) relays the full model; "
                             "peft/trainable_filter applies to P2 rounds "
                             "only")

    def n_selected(self, n_clients: int) -> int:
        return max(1, int(round(self.participation * n_clients)))

    def init_state(self, task: Task, params: Pytree, n_clients: int) -> Dict:
        return {}

    def make_server_update(self, task: Optional[Task] = None):
        return None

    def build_round(self, task: Task) -> Callable:
        # the relay body is representation-agnostic: the scan carry is
        # whatever `local` consumes — param trees on the tree path, flat
        # buffer dicts on the fused path
        local = make_local_fn(task, self.spec, self.flat_ops(task))

        def body(key, params, x_all, y_all, ids, weights, lr_scale, algo_state,
                 frozen=None):
            del weights  # relay has no aggregation, hence no weighting
            cx = x_all[ids]                       # (K, n, ...)
            cy = y_all[ids]
            keys = jax.random.split(key, ids.shape[0])

            def relay(w, inp):
                k, cxi, cyi = inp
                w_next, aux = local(k, w, {}, cxi, cyi, lr_scale, frozen)
                return w_next, aux["loss"]

            params, losses = jax.lax.scan(relay, params, (keys, cx, cy))
            return params, algo_state, jnp.mean(losses)

        return body

    def record(self, ledger, k: int, params: Pytree, task=None) -> None:
        x = _logical_model_bytes(task) if task is not None else None
        ledger.record_cyclic_round(k, params, x_bytes=x)


@dataclasses.dataclass(frozen=True)
class AggregateStrategy(HostBackend):
    """P2 — one federated round: vmapped local runs over the stacked
    client axis + weighted-mean aggregation, with per-algorithm state
    (scaffold control variates, moon previous-local models) carried
    through the engine's scan behind ``state_store``."""
    spec: LocalSpec
    algorithm: str = "fedavg"
    participation: float = 0.1
    server_opt: str = "none"        # none | momentum | adam
    server_lr: float = 1.0
    server_momentum: float = 0.9
    state_store: Any = DENSE_STORE

    @property
    def name(self) -> str:
        return self.algorithm

    def n_selected(self, n_clients: int) -> int:
        return max(1, int(round(self.participation * n_clients)))

    # the store key each algorithm keeps its per-client rows under
    _STORE_KEYS = {"scaffold": "c_clients", "moon": "w_prev"}

    @functools.cached_property
    def _ef_store(self):
        """A FRESH store instance for the error-feedback residual rows —
        sparse stores keep eager per-pytree residency state (host
        mirrors, the spill dict), so the algorithm rows and the residual
        rows cannot share one instance.  Dense stores are stateless and
        reused as-is."""
        store = self.state_store
        if isinstance(store, SparseClientStateStore):
            return dataclasses.replace(store, _cold={}, _meta={})
        return store

    def _residency_entries(self):
        """``(algo_state key, store)`` pairs carrying per-client rows —
        the algorithm's own state plus, under compressed communication
        with error feedback, the residual rows.  Order is stable; the
        staged-token lists below index into it."""
        out = []
        key = self._STORE_KEYS.get(self.algorithm)
        if key is not None:
            out.append((key, self.state_store))
        comp = self.spec.compression
        if compression.compression_on(comp) and comp.error_feedback:
            out.append(("ef_residuals", self._ef_store))
        return out

    @property
    def residency_stores(self):
        """Every store instance holding per-client rows (engine timing
        aggregates their transfer/materialization counters)."""
        return [s for _, s in self._residency_entries()]

    def init_state(self, task: Task, params: Pytree, n_clients: int) -> Dict:
        # flat-first: ``params`` arrive as the engine's placed flat
        # buffers, so the per-client state is flat too — the store is
        # representation-agnostic and the round bodies below run the
        # scaffold/moon state algebra directly on the (K, N) row buffers
        fops = self.flat_ops(task)
        state: Dict[str, Pytree] = {}
        if self.algorithm == "scaffold":
            zeros = fops.zeros() if fops is not None else tm.zeros_like(params)
            state = {"c_global": zeros,
                     "c_clients": self.state_store.init(zeros, n_clients)}
        elif self.algorithm == "moon":
            state = {"w_prev": self.state_store.init(params, n_clients)}
        comp = self.spec.compression
        if compression.compression_on(comp) and comp.error_feedback:
            # error-feedback residuals are per-client f32 rows in the
            # engine's flat bucket layout on BOTH paths (compression is
            # defined on the flat buckets): padded carry buffers on the
            # fused path, the host FlatView's logical buckets on tree
            tmpl = (fops.zeros(jnp.float32) if fops is not None
                    else host_flat_ops(task, True).view.zeros(jnp.float32))
            state["ef_residuals"] = self._ef_store.init(tmpl, n_clients)
        return state

    def prepare_chunk_state(self, algo_state: Dict, ids_block) -> Dict:
        out = algo_state
        for key, store in self._residency_entries():
            if not getattr(store, "needs_host_ids", False):
                continue
            out = dict(out, **{key: store.prepare_chunk(out[key], ids_block)})
        return out

    def stage_chunk_state(self, ids_block) -> Any:
        toks = []
        for key, store in self._residency_entries():
            if not getattr(store, "needs_host_ids", False):
                toks.append(None)
            elif hasattr(store, "stage_chunk"):
                toks.append(("staged", key, store.stage_chunk(ids_block)))
            else:
                # stores without a staged contract degrade gracefully:
                # remember the ids and run the classic synchronous
                # prepare at commit time
                toks.append(("ids", key, np.asarray(ids_block)))
        return toks if any(t is not None for t in toks) else None

    def commit_chunk_state(self, algo_state: Dict, staged: Any) -> Dict:
        if staged is None:
            return algo_state
        out = dict(algo_state)
        stores = dict(self._residency_entries())
        for tok in staged:
            if tok is None:
                continue
            tag, key, val = tok
            if tag == "ids":
                out[key] = stores[key].prepare_chunk(out[key], val)
            else:
                out[key] = stores[key].commit_chunk(out[key], val)
        return out

    def make_server_update(self, task: Optional[Task] = None
                           ) -> Optional[Tuple[Callable, Callable]]:
        """Server-side optimizer (Reddi et al., adaptive federated
        optimization): pseudo-gradient g = w − w_avg.  Returns
        (init_fn, update_fn) or None for "none" (w ← w_avg exactly).

        On the tree path both functions speak param trees (the optax
        style ``repro.optim.optimizers`` pair).  With
        ``update_impl="fused"`` the WHOLE OptState is flat: init takes
        the flat param buffers and builds moment buffers mirroring
        them, update runs one blocked kernel per bucket
        (``ops.fused_server_update``) — the moments materialize back
        into trees only in :func:`unpack_server_state` at the
        EngineResult boundary.  ``task`` is required on the fused path
        (it keys the strategy's :meth:`flat_ops`).
        """
        if self.server_opt == "none":
            return None
        if self.server_opt not in ("momentum", "adam"):
            raise ValueError(f"unknown server_opt {self.server_opt!r}")
        from repro.optim.optimizers import AdamWState, OptState, adamw, sgd

        if self.spec.update_impl == "tree":
            if self.server_opt == "momentum":
                opt = sgd(self.server_lr, momentum=self.server_momentum)
            else:
                opt = adamw(self.server_lr, b1=SERVER_ADAM_B1,
                            b2=SERVER_ADAM_B2)

            def update(params, avg_params, state):
                pseudo_grad = tm.sub(params, avg_params)
                return opt.apply(pseudo_grad, state, params)

            return opt.init, update

        if task is None:
            raise ValueError("the fused server update is built per task — "
                             "pass the engine's Task")
        fops = self.flat_ops(task)
        server_opt, lr, beta = self.server_opt, self.server_lr, \
            self.server_momentum
        with_moments = server_opt == "adam" or beta != 0.0

        def init(p_bufs):
            zeros = lambda: {k: jnp.zeros_like(b)      # noqa: E731
                             for k, b in p_bufs.items()}
            if not with_moments:
                inner = ()          # momentum=0 keeps no moment buffers
            elif server_opt == "momentum":
                inner = zeros()
            else:
                inner = AdamWState(mu=zeros(), nu=zeros())
            return OptState(step=jnp.zeros((), jnp.int32), inner=inner)

        def update(p_bufs, avg_bufs, state):
            delta = {k: avg_bufs[k].astype(jnp.float32) -
                     p_bufs[k].astype(jnp.float32) for k in p_bufs}
            step = state.step + 1
            if not with_moments:
                new_p = fops.apply_delta(
                    p_bufs, {k: lr * d for k, d in delta.items()})
                return new_p, OptState(step=step, inner=())
            if server_opt == "momentum":
                new_p, (m,) = fops.server_update(
                    p_bufs, delta, (state.inner,), (lr,), opt="momentum",
                    beta=beta)
                return new_p, OptState(step=step, inner=m)
            t = step.astype(jnp.float32)
            scalars = (lr, 1.0 - SERVER_ADAM_B1 ** t,
                       1.0 - SERVER_ADAM_B2 ** t)
            new_p, (mu, nu) = fops.server_update(
                p_bufs, delta, (state.inner.mu, state.inner.nu), scalars,
                opt="adam", b1=SERVER_ADAM_B1, b2=SERVER_ADAM_B2)
            return new_p, OptState(step=step, inner=AdamWState(mu=mu, nu=nu))

        return init, update

    def build_round(self, task: Task) -> Callable:
        spec = self.spec
        fops = self.flat_ops(task)
        local = make_local_fn(task, spec, fops)
        algo = self.algorithm
        store = self.state_store
        # aggregation takes (round_key, ids, params, w_locals, weights,
        # algo_state) and returns (new_params, algo_state): the key/ids
        # thread the DP noise and secure-agg mask derivation
        # (repro.fl.privacy) into the round program, and the state rides
        # through so compressed communication (repro.fl.compression) can
        # gather/scatter its error-feedback residual rows; with privacy
        # and compression off the closures ignore all three and reduce
        # to the exact baseline math
        private = privacy.privacy_on(spec.dp, spec.secure_agg)
        comp = spec.compression
        compressed = compression.compression_on(comp)
        ef = compressed and comp.error_feedback
        ef_store = self._ef_store if ef else None

        def with_ef(agg_fn):
            def run(rk, ids, p, wl, w, st):
                res = (ef_store.gather(st["ef_residuals"], ids)
                       if ef else None)
                new_p, new_r = agg_fn(p, wl, w, res)
                if ef:
                    st = dict(st, ef_residuals=ef_store.scatter(
                        st["ef_residuals"], ids, new_r))
                return new_p, st
            return run

        def stateless(agg_fn):
            return lambda rk, ids, p, wl, w, st: (agg_fn(rk, ids, p, wl, w),
                                                  st)

        if fops is None:
            if compressed:
                view = host_flat_ops(task, True).view
                aggregate = with_ef(functools.partial(
                    compression.tree_compressed_aggregate, comp, view))
            elif private:
                aggregate = stateless(functools.partial(
                    privacy.tree_dp_aggregate, spec.dp, spec.secure_agg))
            else:
                aggregate = stateless(
                    lambda rk, ids, p, wl, w: tm.stacked_weighted_mean(wl, w))
            unpack = stacked_unpack = lambda t, fz=None: t                # noqa: E731
        else:
            # the vmapped flat local outputs ARE the stacked (K, N)
            # buffers — aggregation consumes them with zero packing
            if compressed:
                aggregate = with_ef(functools.partial(
                    compression.fused_compressed_aggregate, comp, fops))
            elif private:
                aggregate = stateless(functools.partial(
                    privacy.fused_dp_aggregate, spec.dp, spec.secure_agg,
                    fops))
            else:
                aggregate = stateless(
                    lambda rk, ids, p, wl, w: fused_aggregate(fops, p, wl, w))
            unpack = fops.unflatten
            stacked_unpack = fops.stacked_unflatten
        aggregate = scoped("fl_aggregate", aggregate)

        def body(key, params, x_all, y_all, ids, weights, lr_scale, algo_state,
                 frozen=None):
            K = ids.shape[0]
            keys = jax.random.split(key, K)
            cx = x_all[ids]
            cy = y_all[ids]

            if algo in ("fedavg", "fedprox"):
                # extras are TREES (they feed the loss at the forward
                # boundary) — materialized from the flat carry if needed
                extras = {"w_global": unpack(params, frozen)} \
                    if algo == "fedprox" else {}
                in_ext = jax.tree_util.tree_map(lambda _: None, extras)
                w_locals, aux = jax.vmap(
                    local, in_axes=(0, None, in_ext, 0, 0, None, None))(
                    keys, params, extras, cx, cy, lr_scale, frozen)
                new_params, algo_state = aggregate(key, ids, params,
                                                   w_locals, weights,
                                                   algo_state)
                return new_params, algo_state, jnp.mean(aux["loss"])

            if algo == "scaffold":
                c, c_all = algo_state["c_global"], algo_state["c_clients"]
                c_i = store.gather(c_all, ids)
                # control-variate update (option II):
                # c_i⁺ = c_i − c + (w−w_i)/(S·lr)
                denom = spec.n_steps * spec.lr * lr_scale
                if fops is not None:
                    # FLAT per-client state: c and the gathered (K, N)
                    # rows are buffer dicts, the whole control-variate
                    # algebra runs on the stacked buffers — no
                    # per-client unflatten anywhere in the round
                    c_diff = jax.tree_util.tree_map(
                        lambda g, l: g[None] - l, c, c_i)
                    w_locals, aux = jax.vmap(
                        local, in_axes=(0, None, {"c_diff_flat": 0}, 0, 0,
                                        None, None))(
                        keys, params, {"c_diff_flat": c_diff}, cx, cy,
                        lr_scale, frozen)
                    c_i_new = jax.tree_util.tree_map(
                        lambda ci, cg, p, wl: ci - cg[None] +
                        (p[None] - wl) / denom,
                        c_i, c, params, w_locals)
                else:
                    # per-client extras carry (c − c_i) with a leading K axis
                    c_diff = jax.tree_util.tree_map(
                        lambda g, l: jnp.broadcast_to(g[None], l.shape) - l,
                        c, c_i)
                    extras = {"c_diff": c_diff}
                    w_locals, aux = jax.vmap(
                        local, in_axes=(0, None, {"c_diff": 0}, 0, 0, None,
                                        None))(
                        keys, params, extras, cx, cy, lr_scale, frozen)
                    c_i_new = jax.tree_util.tree_map(
                        lambda ci, cg, w, wl: ci - cg[None] +
                        (w[None] - wl) / denom,
                        c_i, c, params, w_locals)
                new_params, algo_state = aggregate(key, ids, params,
                                                   w_locals, weights,
                                                   algo_state)
                # c ← c + (K/N)·mean_i(c_i⁺ − c_i); N is the POPULATION
                # (the sparse store's physical table is only capacity rows)
                frac = K / store.population(c_all)
                c_new = jax.tree_util.tree_map(
                    lambda cg, new, old: cg + frac * jnp.mean(new - old, axis=0),
                    c, c_i_new, c_i)
                c_all_new = store.scatter(c_all, ids, c_i_new)
                state = dict(algo_state, c_global=c_new,
                             c_clients=c_all_new)
                return new_params, state, jnp.mean(aux["loss"])

            if algo == "moon":
                w_prev_all = algo_state["w_prev"]
                # flat path: rows gather/scatter as raw (K, N) buffers —
                # ONE stacked unflatten at the loss boundary (extras are
                # trees), zero per-client packing on the way back
                w_prev = stacked_unpack(store.gather(w_prev_all, ids), frozen)
                extras = {"w_global": unpack(params, frozen),
                          "w_prev": w_prev}
                w_locals, aux = jax.vmap(
                    local,
                    in_axes=(0, None, {"w_global": None, "w_prev": 0}, 0, 0,
                             None, None))(
                    keys, params, extras, cx, cy, lr_scale, frozen)
                new_params, algo_state = aggregate(key, ids, params,
                                                   w_locals, weights,
                                                   algo_state)
                state = dict(algo_state,
                             w_prev=store.scatter(w_prev_all, ids, w_locals))
                return new_params, state, jnp.mean(aux["loss"])

            raise ValueError(f"unknown algorithm {algo!r}")

        return body

    def record(self, ledger, k: int, params: Pytree, task=None) -> None:
        comp = self.spec.compression
        filt = effective_trainable_filter(self.spec)
        x = _logical_model_bytes(task) if task is not None else None
        # the upload payload departs from the full model X whenever the
        # wire carries less: compressed deltas, a trainable slice, or
        # both (the ratios compose multiplicatively in the closed form)
        payload = (_upload_payload_bytes(task, comp, filt)
                   if task is not None and
                   (compression.compression_on(comp) or filt is not None)
                   else None)
        ledger.record_round(self.algorithm, k, params,
                            secure_agg=self.spec.secure_agg,
                            x_bytes=x, payload_bytes=payload)


# ---------------------------------------------------------------------------
# evaluation — the in-program eval stream
# ---------------------------------------------------------------------------
#
# The engine evaluates INSIDE the compiled chunk program: the test set is
# batched once into (n_batches, B, ...) arrays (the tail batch padded by
# wrap-around, with a (n_batches, B) 0/1 weight marking real samples),
# handed to the backend for placement, and scanned under a per-round
# ``lax.cond`` so non-eval rounds pay nothing.  The metric contract is
# PER-SAMPLE: ``metric(params, bx, by) -> (B,)`` — the engine returns the
# weight-averaged mean over the whole stream, which for the default
# accuracy metric equals full-test-set accuracy exactly (every sample
# carries the same number of label elements).

def make_eval_fn(task: Task, batch: int) -> Callable:
    """Host-side reference evaluation (one jit dispatch per test batch).

    Kept as the parity oracle for the in-program stream and for
    evaluating a model outside an engine run; the training loop itself
    evaluates in-program (see ``make_accuracy_metric``)."""
    @jax.jit
    def eval_batch(params, bx, by):
        return task.accuracy(params, bx, by)

    def evaluate(params, test_x, test_y) -> float:
        n = len(test_y)
        accs, ws = [], []
        for s in range(0, n, batch):
            bx = jnp.asarray(test_x[s:s + batch])
            by = jnp.asarray(test_y[s:s + batch])
            accs.append(float(eval_batch(params, bx, by)))
            ws.append(len(by))
        return float(np.average(accs, weights=ws))

    return evaluate


@functools.lru_cache(maxsize=64)
def make_accuracy_metric(task: Task) -> Callable:
    """Default in-program eval metric: per-sample accuracy.

    ``metric(params, bx, by) -> (B,)`` mean correctness per sample (the
    trailing label dims — sequence positions for token tasks — are
    averaged within each sample, matching ``Task.accuracy``)."""

    def metric(params, bx, by):
        correct = (task.predict_fn(params, bx) == by).astype(jnp.float32)
        return correct.reshape(correct.shape[0], -1).mean(axis=1)

    return metric


def batch_test_set(test_x, test_y, batch: int) -> Tuple:
    """Batch the held-out test set for the in-program eval stream.

    Returns host arrays ``(ev_x, ev_y, ev_w)``: ``(n_batches, B, ...)``
    data (tail batch padded by wrapping around to the front of the test
    set) and ``(n_batches, B)`` float32 weights — 1 for real samples, 0
    for pad — so the weighted mean over the stream is exact."""
    test_x, test_y = np.asarray(test_x), np.asarray(test_y)
    n = len(test_y)
    B = max(1, min(batch, n))
    n_batches = -(-n // B)
    pad = n_batches * B - n
    idx = np.concatenate([np.arange(n), np.arange(pad) % n])
    w = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
    shape = (n_batches, B)
    return (test_x[idx].reshape(shape + test_x.shape[1:]),
            test_y[idx].reshape(shape + test_y.shape[1:]),
            w.reshape(shape))


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RoundSchedule:
    """Host-side schedule knobs shared by every strategy.

    sampling="device" draws the per-round client subset inside the jitted
    chunk program (``jax.random.permutation(k, n_clients)[:K]``);
    "host" reproduces the seed drivers' ``np.random.default_rng(seed +
    host_rng_offset)`` stream (the offset was 31 for P1, 17 for P2) and
    feeds the precomputed ids in as scan inputs.

    eval_every ≤ 0 disables evaluation entirely (benchmark mode);
    otherwise the engine evaluates every ``eval_every`` rounds and on
    the final round — the same cadence as the seed drivers, but computed
    in-program from a per-round mask, so any ``eval_every`` composes
    with any ``chunk_size`` without splitting a dispatch.

    ``overlap=True`` pipelines the chunk loop: while dispatch N runs on
    device, the engine plans residency for dispatch N+1 (sampling
    replay, LRU eviction plan) and stages its refill rows with
    non-blocking transfers, so host residency cost hides behind device
    compute.  Staging only re-orders HOST work (the device-side op
    stream is identical), so overlapped == synchronous is bitwise; the
    knob is a pure throughput trade and a no-op for dense stores.  It
    is ignored (forced off) when a switch policy pins per-round
    dispatch.
    """
    rounds: int
    lr_decay: float = 0.998
    eval_every: int = 10
    eval_batch: int = 256
    seed: int = 0
    chunk_size: int = 1
    sampling: str = "device"        # device | host
    host_rng_offset: int = 0
    overlap: bool = False

    def __post_init__(self):
        if self.sampling not in ("device", "host"):
            raise ValueError(f"unknown sampling mode {self.sampling!r}")


@dataclasses.dataclass
class EngineResult:
    params: Pytree
    history: List[Dict[str, float]]
    algo_state: Dict[str, Pytree]
    server_state: Any = None
    dispatches: int = 0             # chunk-program invocations this run
    # wall-time breakdown of the run (totals, ms), each summed from the
    # ``engine.*`` / ``store.*`` spans named beside it:
    # host_residency_ms = stage planning + staging-transfer enqueue
    #   (engine.stage),
    # staged_transfer_ms = the device_put slice of that (store.transfer),
    # dispatch_enqueue_ms = commit + chunk_fn call overhead
    #   (engine.dispatch),
    # device_wait_ms = blocking on the dispatched chunk's outputs
    #   (engine.drain),
    # spill_materialize_ms = background spill→numpy conversion time
    #   (store.spill: work moved OFF the critical path, not added to it),
    # pack_ms = flatten + place of the params, algorithm and server
    #   state init (engine.pack),
    # prepare_data_ms = the data and eval stream's upload
    #   (engine.prepare_data),
    # unpack_ms = the flat carries back to trees (engine.unpack)
    timing: Optional[Dict[str, float]] = None
    # per history row: host clock from its dispatch to drained losses,
    # split evenly over the chunk's rounds.  It includes the chunk
    # program's compile on the first call of a shape, and leaves out the
    # host time between dispatches (planning, history, packing and
    # unpacking): not a rate of the whole run
    round_wall_s: List[float] = dataclasses.field(default_factory=list)
    # JAX traces and backend compiles charged to the innermost engine
    # span open when they happened: {"traces.engine.unpack": 57, ...}
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)


def make_chunk_fn(task: Task, strategy, schedule: RoundSchedule,
                  n_clients: int, metric: Optional[Callable] = None
                  ) -> Callable:
    """Build the jitted R-round program.

    signature: chunk_fn(key, params, algo_state, server_state,
                        x_all, y_all, n_real, ids, lr_scales, eval_mask,
                        ev_x, ev_y, ev_w, frozen)
               -> (key, params, algo_state, server_state, losses, metrics)

    ``frozen`` is the read-only frozen-leaf constant bucket of a
    trainable-filtered run ({} for full-filter) — NOT donated, NOT in
    the scan carry: the same buffers serve every round of every chunk
    and merge with the trainable carry only at the loss / eval tree
    boundaries.
    The per-round keys are derived INSIDE the scan by the same
    ``key, rk = jax.random.split(key)`` recurrence the seed drivers ran
    on the host (threefry is deterministic, so the streams are
    bit-identical) — the host does zero per-round work.  lr_scales is
    the (R,)-stacked decay schedule, ids is (R, K) for host sampling or
    None for on-device sampling, and the four carries are donated so
    chunk i+1 reuses chunk i's buffers.

    ``metric`` is the in-program eval metric (per-sample contract, see
    ``make_accuracy_metric``) or None for no-eval programs.  With a
    metric, eval_mask is an (R,) bool scan input and ev_x/ev_y/ev_w the
    backend-placed test stream from :func:`batch_test_set`; the chunk
    evaluates under ``lax.cond`` on masked-in rounds and emits an (R,)
    metric stream (NaN on masked-out rounds).  Without one, those four
    args are None and the metrics output is None.

    Programs are cached on (task, strategy, sampling, n_clients,
    metric) — Task and the strategies are frozen dataclasses — so
    repeated engine runs (benchmark sweeps, schedule phases reusing a
    config) skip retracing; jax.jit then caches per chunk length R
    underneath.
    """
    return _cached_chunk_fn(task, strategy, schedule.sampling, n_clients,
                            metric)


@functools.lru_cache(maxsize=64)
def _cached_chunk_fn(task: Task, strategy, sampling: str,
                     n_clients: int, metric: Optional[Callable]) -> Callable:
    body = strategy.build_round(task)
    server = strategy.make_server_update(task)
    fops = strategy.flat_ops(task)
    on_device = sampling == "device"
    K = strategy.n_selected(n_clients)

    def chunk(key, params, algo_state, server_state, x_all, y_all, n_real,
              ids, lr_scales, eval_mask, ev_x, ev_y, ev_w, frozen):
        def evaluate(params):
            # the eval metric speaks param trees — the flat carry
            # materializes one here, at the model's forward boundary
            # (merging the frozen constant bucket on filtered views)
            if fops is not None:
                params = fops.unflatten(params, frozen)

            # weighted mean over the batched test stream; ev_w zeroes
            # the wrap-around pad in the tail batch
            def eval_batch(tot, inp):
                bx, by, w = inp
                return tot + jnp.sum(metric(params, bx, by) * w), None

            tot, _ = jax.lax.scan(eval_batch, jnp.float32(0.0),
                                  (ev_x, ev_y, ev_w))
            return tot / jnp.sum(ev_w)

        def one_round(carry, xs):
            key, params, algo_state, server_state = carry
            ids_r, lr_scale, do_eval = xs
            key, rk = jax.random.split(key)
            if on_device:
                k_sel, rk = jax.random.split(rk)
                ids_r = jax.random.permutation(k_sel, n_clients)[:K]
            weights = n_real[ids_r].astype(jnp.float32)
            new_params, algo_state, loss = body(
                rk, params, x_all, y_all, ids_r, weights, lr_scale, algo_state,
                frozen)
            if server is not None:
                with jax.named_scope("fl_server_update"):
                    new_params, server_state = server[1](params, new_params,
                                                         server_state)
            m = None
            if metric is not None:
                with jax.named_scope("fl_eval"):
                    m = jax.lax.cond(do_eval, evaluate,
                                     lambda _: jnp.float32(jnp.nan),
                                     new_params)
            return (key, new_params, algo_state, server_state), (loss, m)

        (key, params, algo_state, server_state), (losses, metrics) = \
            jax.lax.scan(one_round, (key, params, algo_state, server_state),
                         (ids, lr_scales, eval_mask))
        return key, params, algo_state, server_state, losses, metrics

    return strategy.jit_chunk(chunk, task, n_clients)


@dataclasses.dataclass
class _ChunkPlan:
    """One dispatch's host-derived inputs, computable ahead of time so
    the overlapped loop can plan chunk N+1 while chunk N executes."""
    rnd: int
    R: int
    ids: Optional[jnp.ndarray]
    ids_block: Optional[np.ndarray]
    lr_scales: jnp.ndarray
    eval_mask: Optional[jnp.ndarray]
    do_eval: List[bool]
    staged: Any = None


def run_rounds(task: Task, data: FederatedDataset, strategy,
               schedule: RoundSchedule, *,
               init_params: Optional[Pytree] = None,
               ledger=None, verbose: bool = False,
               eval_fn: Optional[Callable] = None,
               switch_policy=None,
               phase: str = "P2",
               label: Optional[str] = None) -> EngineResult:
    """Run ``schedule.rounds`` rounds of ``strategy`` and return the
    final params plus the per-round history.

    The per-round key stream (split once per round from
    ``PRNGKey(schedule.seed)``) and the lr-decay scalars are derived on
    the host independently of chunking, so histories are invariant to
    ``chunk_size`` and, with sampling="host" + the right offset,
    bit-compatible with the seed drivers.

    Evaluation runs IN PROGRAM (see ``make_chunk_fn``): rounds where
    ``(round + 1) % eval_every == 0`` — plus the final round — compute
    the eval metric inside the chunk scan, so evaluating never splits a
    chunk or adds a dispatch.  ``eval_fn`` overrides the default
    accuracy metric and must follow the traceable per-sample contract
    ``eval_fn(params, bx, by) -> (B,)``; the history rows record the
    stream's weighted mean under the ``"acc"`` key either way.
    """
    # every host stretch below is an ``engine.*`` span (repro.utils.spans):
    # on the profiler's clock, summed into ``timing``, and charged the
    # JAX traces and compiles that happen inside it (``counters``)
    timing = {"host_residency_ms": 0.0, "staged_transfer_ms": 0.0,
              "dispatch_enqueue_ms": 0.0, "device_wait_ms": 0.0,
              "spill_materialize_ms": 0.0, "pack_ms": 0.0,
              "prepare_data_ms": 0.0, "unpack_ms": 0.0}
    counters: Dict[str, int] = {}
    key = jax.random.PRNGKey(schedule.seed)
    n_clients = data.n_clients
    # flat-first: on the fused path the engine's working params are the
    # strategy's flat buffers from here to the EngineResult — the server
    # OptState inits flat too, and trees reappear only at the eval /
    # forward boundaries inside the chunk.  Packing replaces the
    # place_params hook outright: fops.place commits the packed buffers
    # to the flat shardings AND de-aliases any flatten passthrough (a
    # single-1-D-leaf bucket packs to the caller's own array), so the
    # donated carries never eat the caller's tree and the per-leaf
    # placement would be dead work.
    fops = strategy.flat_ops(task)
    frozen: Dict[str, jnp.ndarray] = {}
    with span("engine.pack", timing, "pack_ms", counters):
        params = init_params if init_params is not None else task.init(key)
        if fops is None:
            # backend hook: copy (host) or device_put with shardings (pod)
            # so the donated carries never invalidate the caller's
            # init_params
            params = strategy.place_params(params)
        else:
            # pack + place FIRST: init_state sees the engine's working
            # representation, so per-client state initializes flat too.
            # Frozen leaves pack ONCE per phase into the read-only
            # constant bucket ({} for an unfiltered view): non-donated,
            # outside the chunk carry, merged back only at tree
            # boundaries.
            frozen = fops.place_frozen(fops.flatten_frozen(params))
            params = fops.place(fops.flatten(params))
        algo_state = strategy.init_state(task, params, n_clients)
        server = strategy.make_server_update(task)
        server_state = server[0](params) if server is not None else ()
        server_state = strategy.place_server_state(server_state, task)
    K = strategy.n_selected(n_clients)

    with_eval = schedule.eval_every > 0 and len(np.asarray(data.test_y)) > 0
    metric = None
    if with_eval:
        metric = eval_fn if eval_fn is not None else make_accuracy_metric(task)
    chunk_fn = make_chunk_fn(task, strategy, schedule, n_clients, metric)
    ev_x = ev_y = ev_w = None
    with span("engine.prepare_data", timing, "prepare_data_ms", counters):
        x_all, y_all, n_real = strategy.prepare_data(data)
        if with_eval:
            ev_x, ev_y, ev_w = strategy.prepare_eval_data(
                batch_test_set(data.test_x, data.test_y,
                               schedule.eval_batch))

    host_rng = None
    if schedule.sampling == "host":
        host_rng = np.random.default_rng(schedule.seed + schedule.host_rng_offset)

    label = label or getattr(strategy, "name", phase)
    # per-round switch decisions need per-round dispatch
    chunk = 1 if switch_policy is not None else max(1, schedule.chunk_size)
    # the overlapped pipeline pre-plans the NEXT chunk while the current
    # one runs; a switch policy decides per round, so it forces sync
    overlap = bool(getattr(schedule, "overlap", False)) \
        and switch_policy is None

    # sparse stores manage residency on the host between dispatches: they
    # must see each chunk's client ids before the chunk runs.  A strategy
    # may carry several stores (algorithm rows + EF residual rows).
    store = getattr(strategy, "state_store", None)
    stores = getattr(strategy, "residency_stores", None)
    if stores is None:
        stores = [store] if store is not None else []
    sparse_residency = any(getattr(s, "needs_host_ids", False)
                           for s in stores) and bool(algo_state)
    # device sampling: the replay key advances on the host by the same
    # split recurrence the program runs, so chunk N+1's draws are known
    # before chunk N's carried key has materialized
    replay_key = key

    def stores_ms(attr: str) -> float:
        return sum(float(getattr(s, attr, 0.0) or 0.0) for s in stores)

    transfer_ms0 = stores_ms("staged_transfer_ms")
    spill_ms0 = stores_ms("spill_materialize_ms")

    def make_plan(rnd: int) -> _ChunkPlan:
        """Everything host-derived a dispatch needs: the round window,
        sampled ids, residency id block, lr scales and the eval mask —
        all pure functions of the (host) rng streams and the global
        round index, so planning order == execution order keeps the
        streams bit-identical whether or not the loop overlaps."""
        nonlocal replay_key
        with span("engine.plan", counts=counters, round=rnd):
            R = min(chunk, schedule.rounds - rnd)
            ids = None
            if host_rng is not None:
                ids = jnp.asarray(np.stack([
                    host_rng.choice(n_clients, size=K, replace=False)
                    for _ in range(R)]))
            ids_block = None
            if sparse_residency:
                # host sampling: the ids are already known; device
                # sampling: replay the chunk's in-program draw
                # (bit-identical threefry recurrence) — residency only,
                # the program still samples in-program unchanged
                if ids is not None:
                    ids_block = np.asarray(ids)
                else:
                    ids_block, replay_key = _replay_device_sampling(
                        replay_key, n_clients, K, R)
            lr_scales = jnp.asarray(
                [schedule.lr_decay ** (rnd + j) for j in range(R)],
                jnp.float32)
            # the eval cadence is a host-computed mask over GLOBAL round
            # indices, so it is independent of how rounds chunk into
            # dispatches
            eval_mask = None
            do_eval = [False] * R
            if with_eval:
                do_eval = [(rnd + j + 1) % schedule.eval_every == 0
                           or rnd + j + 1 == schedule.rounds
                           for j in range(R)]
                eval_mask = jnp.asarray(do_eval)
            return _ChunkPlan(rnd=rnd, R=R, ids=ids, ids_block=ids_block,
                              lr_scales=lr_scales, eval_mask=eval_mask,
                              do_eval=do_eval)

    def stage(plan: _ChunkPlan) -> None:
        if plan.ids_block is None:
            return
        with span("engine.stage", timing, "host_residency_ms", counters,
                  round=plan.rnd):
            plan.staged = strategy.stage_chunk_state(
                plan.ids_block.reshape(-1))

    history: List[Dict[str, float]] = []
    round_wall_s: List[float] = []
    dispatches = 0
    plan = make_plan(0) if schedule.rounds > 0 else None
    staged_plan = None
    while plan is not None:
        if staged_plan is not plan:     # sync path (or the first chunk)
            stage(plan)
        rnd, R = plan.rnd, plan.R
        with span("engine.dispatch", timing, "dispatch_enqueue_ms", counters,
                  round=rnd) as sent:
            algo_state = strategy.commit_chunk_state(algo_state, plan.staged)
            key, params, algo_state, server_state, losses, metrics = \
                chunk_fn(key, params, algo_state, server_state, x_all,
                         y_all, n_real, plan.ids, plan.lr_scales,
                         plan.eval_mask, ev_x, ev_y, ev_w, frozen)
        dispatches += 1

        nxt = None
        if overlap and plan.rnd + plan.R < schedule.rounds:
            # the pipeline: plan + stage chunk N+1 while chunk N runs
            nxt = make_plan(plan.rnd + plan.R)
            stage(nxt)
            staged_plan = nxt

        with span("engine.drain", timing, "device_wait_ms", counters,
                  round=rnd) as drained:
            losses = np.asarray(losses)  # blocks: the dispatch drains here

        with span("engine.history", counts=counters, round=rnd):
            metrics = np.asarray(metrics) if metrics is not None else None
            for j in range(R):
                if ledger is not None:
                    strategy.record(ledger, K, params, task)
                row = {"round": rnd + j, "local_loss": float(losses[j]),
                       "phase": phase}
                round_wall_s.append((drained.t1 - sent.t0) / R)
                if plan.do_eval[j]:
                    row["acc"] = float(metrics[j])
                    if verbose:
                        print(f"[{label}] round {rnd + j + 1}/"
                              f"{schedule.rounds} "
                              f"loss={row['local_loss']:.4f} "
                              f"acc={row['acc']:.4f}", flush=True)
                history.append(row)

        if switch_policy is not None and switch_policy.should_switch(
                rnd + R - 1, history):
            break
        if not overlap:
            nxt = (make_plan(rnd + R) if rnd + R < schedule.rounds else None)
        plan = nxt

    timing["staged_transfer_ms"] = stores_ms("staged_transfer_ms") \
        - transfer_ms0
    # background spill-materialization ms accrued this run (off the
    # critical path — host work the refault bursts no longer pay)
    timing["spill_materialize_ms"] = stores_ms("spill_materialize_ms") \
        - spill_ms0

    if fops is not None:                # EngineResult speaks trees
        with span("engine.unpack", timing, "unpack_ms", counters):
            params = fops.unflatten(params, frozen)
            server_state = unpack_server_state(fops, server_state)
        # algo_state stays in the carried representation (flat row
        # buffers / sparse store tables) — materializing an
        # (n_clients, model) tree here would defeat the sparse store
    return EngineResult(params=params, history=history,
                        algo_state=algo_state, server_state=server_state,
                        dispatches=dispatches, timing=timing,
                        round_wall_s=round_wall_s, counters=counters)
