"""Client local training — the inner loop shared by P1 (cyclic) and P2 (FL).

One jit-friendly function runs ``n_steps`` of SGD on one client's shard,
with the algorithm-specific loss/gradient shaping injected through
``variant``:

  plain    : vanilla local SGD (FedAvg, and CyclicFL's P1)
  fedprox  : + (mu/2)·||w − w_global||²          [Li et al., MLSys'20]
  scaffold : g ← g − c_i + c  gradient correction [Karimireddy, ICML'20]
  moon     : + mu·contrastive(z, z_glob, z_prev)  [Li et al., CVPR'21]

The whole local run is a ``lax.scan`` over steps so a round compiles to
a single XLA program; batches are sampled inside the scan from the
client's fixed-size shard (uniform with replacement — the stochastic
approximation of the paper's epoch shuffling that keeps shapes static).

The post-gradient *step tail* — global-norm clip, scaffold correction,
decoupled weight decay, heavy-ball momentum, SGD axpy — has two
implementations behind ``LocalSpec.update_impl``:

  tree            : per-leaf ``tree_math`` algebra (the parity oracle);
                    the local fn takes and returns parameter TREES.
  fused[_interpret]: FLAT-FIRST — the local fn takes and returns
                    FlatView buffers; params/momentum ride the scan as
                    contiguous buffers, ``value_and_grad`` differentiates
                    w.r.t. the buffers themselves (the tree materializes
                    only inside the loss closure, at the model's
                    forward/backward boundary), so the backward emits
                    PACKED gradients — there is no per-step pack copy —
                    and the whole tail is ONE blocked Pallas pass per
                    step (repro.kernels.fused_update).  "fused" lowers
                    to Mosaic on TPU and auto-interprets on CPU;
                    "fused_interpret" forces the interpreter.

The buffer flavor is a backend decision carried by a
:class:`FlatParamOps` (host: 1-D per-dtype FlatView buffers, kernels
called directly; pod: ``repro.fl.pod.ShardedFlatOps`` — per-mesh-axis
group ``(n_shards, per_shard)`` buffers, kernels run shard-locally
under ``shard_map``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.fl.compression import (CompressionSpec, topk_k, topk_threshold,
                                  validate_compression)
from repro.fl.privacy import DPSpec
from repro.fl.task import Task
from repro.kernels import ops
from repro.kernels.fused_update import LANES, padded_len
from repro.utils import tree_math as tm
from repro.utils.flatten import FlatView

Pytree = Any

UPDATE_IMPLS = ("tree", "fused", "fused_interpret")


def validate_update_impl(update_impl: str) -> str:
    """Reject an unknown ``update_impl`` with the allowed values spelled
    out — shared by every spec/config so a typo fails at construction
    time, not deep inside the engine."""
    if update_impl not in UPDATE_IMPLS:
        raise ValueError(f"unknown update_impl {update_impl!r} "
                         f"(choose from {UPDATE_IMPLS})")
    return update_impl


def parse_peft(peft: str) -> Tuple[str, int]:
    """``"lora:<r>"`` → ``("lora", r)``, rejecting malformed specs the
    way :func:`validate_update_impl` rejects impls."""
    kind, sep, rank_s = peft.partition(":")
    if not sep or kind != "lora":
        raise ValueError(f"unknown peft spec {peft!r} "
                         f"(expected 'lora:<rank>')")
    try:
        rank = int(rank_s)
    except ValueError:
        raise ValueError(f"lora rank must be a positive integer, "
                         f"got {rank_s!r}") from None
    if rank <= 0:
        raise ValueError(f"lora rank must be a positive integer, got {rank}")
    return kind, rank


def validate_peft(peft: Optional[str], *,
                  trainable_filter: Optional[str] = None,
                  update_impl: str = "tree") -> Optional[str]:
    """Construction-time checks for the trainable-slice knobs: the peft
    spec must parse, and either knob requires the fused flat path —
    the tree backend has no trainable/frozen partition."""
    if peft is not None:
        parse_peft(peft)
    if (peft is not None or trainable_filter is not None) \
            and update_impl == "tree":
        raise ValueError(
            "peft/trainable_filter needs the fused flat path "
            "(update_impl='fused'|'fused_interpret') — the tree backend "
            "has no trainable-slice partition")
    return peft


def effective_trainable_filter(spec: "LocalSpec") -> Optional[str]:
    """The filter spec the round program runs under: an explicit
    ``trainable_filter`` wins; otherwise ``peft`` implies the named
    ``"lora"`` filter; ``None`` = every leaf trains (the full-filter
    oracle path, bitwise identical to the pre-filter program)."""
    if spec.trainable_filter is not None:
        return spec.trainable_filter
    if spec.peft is not None:
        return "lora"
    return None


@dataclasses.dataclass(frozen=True)
class LocalSpec:
    """Static description of one client's local-training run."""
    n_steps: int
    batch_size: int
    lr: float
    momentum: float = 0.0
    weight_decay: float = 0.0
    variant: str = "plain"          # plain | fedprox | scaffold | moon
    mu: float = 0.0                 # prox / moon coefficient
    temperature: float = 0.5        # moon
    grad_clip: Optional[float] = None
    update_impl: str = "tree"       # tree | fused | fused_interpret
    # round-aggregate privacy (repro.fl.privacy): DP-FedAvg clip+noise
    # on each client's round delta, and/or pairwise secure-agg masks.
    # Both apply at AGGREGATION — the local run itself is unchanged.
    dp: Optional[DPSpec] = None
    secure_agg: bool = False
    # compressed client→server uploads (repro.fl.compression): blockwise
    # int8/int16 quantization + magnitude top-k on each round delta,
    # optionally with error-feedback residuals.  Like dp/secure_agg this
    # applies at AGGREGATION only; None and the identity spec keep the
    # exact baseline program.
    compression: Optional[CompressionSpec] = None
    # trainable-slice / PEFT (ISSUE 10): peft="lora:<r>" declares the
    # model carries LoRA adapters of rank r (the model config must be
    # built with the matching ``lora_rank`` — see parse_peft) and
    # implies the "lora" trainable filter; trainable_filter names a
    # filter from repro.sharding.rules.TRAINABLE_FILTERS (or is a raw
    # path regex) selecting WHICH leaves train.  Either knob makes the
    # entire fused round program — grads, clip, step tail, aggregation,
    # server moments, the chunk carry, upload bytes — operate on the
    # trainable buckets only; frozen leaves ride outside the carry as a
    # read-only constant.  None/None is the full-filter oracle.
    peft: Optional[str] = None
    trainable_filter: Optional[str] = None

    def __post_init__(self):
        validate_update_impl(self.update_impl)
        validate_compression(self.compression, dp=self.dp,
                             secure_agg=self.secure_agg)
        validate_peft(self.peft, trainable_filter=self.trainable_filter,
                      update_impl=self.update_impl)


def _moon_contrastive(z: jnp.ndarray, z_glob: jnp.ndarray, z_prev: jnp.ndarray,
                      temperature: float) -> jnp.ndarray:
    """Model-contrastive loss: pull local representation toward the global
    model's, push away from the previous local model's."""

    def cos(a, b):
        a = a / (jnp.linalg.norm(a, axis=-1, keepdims=True) + 1e-12)
        b = b / (jnp.linalg.norm(b, axis=-1, keepdims=True) + 1e-12)
        return jnp.sum(a * b, axis=-1)

    sim_g = cos(z, z_glob) / temperature
    sim_p = cos(z, z_prev) / temperature
    return jnp.mean(-sim_g + jax.nn.logsumexp(jnp.stack([sim_g, sim_p]), axis=0))


# ---------------------------------------------------------------------------
# FlatParamOps — the canonical flat-buffer representation of one task
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FlatParamOps:
    """Bundle a packing plan with how to run the fused kernels on its
    buffers.  This is the *representation object* of the flat-first
    path: the engine carries params / momentum / server moments as the
    buffer dicts this produces, and every update stage goes through the
    dict-level methods below (one blocked kernel per bucket).

    The host flavor wraps a 1-D :class:`repro.utils.flatten.FlatView`
    and calls the kernels directly; the pod flavor
    (``repro.fl.pod.ShardedFlatOps``) swaps the view for a
    ShardedFlatView and overrides :meth:`_run` to execute each kernel
    shard-locally under ``shard_map`` — same math, mesh-resident
    buffers.
    """
    view: Any                       # FlatView | ShardedFlatView
    interpret: bool

    # -- representation -----------------------------------------------------

    def flatten(self, tree: Pytree) -> Dict[str, jnp.ndarray]:
        return self.view.flatten(tree)

    def unflatten(self, bufs: Dict[str, jnp.ndarray],
                  frozen: Optional[Dict[str, jnp.ndarray]] = None) -> Pytree:
        """Rebuild the tree from trainable buffers, merging ``frozen``
        (the read-only constant bucket dict) for filtered views; absent
        frozen buckets zero-fill — the right semantics for trees whose
        frozen slots are definitionally zero (server moments, deltas)."""
        return self.view.unflatten(bufs, frozen)

    @property
    def padded_sizes(self) -> Dict[str, int]:
        """Per-bucket carried length: the logical size rounded up to a
        length both kernel grids (interpret and compiled) tile exactly,
        so no kernel call over a carried buffer copies it to pad."""
        return {name: padded_len(size)
                for name, size in self.view.buffer_sizes.items()}

    def pad(self, bufs: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        """Right-pad each buffer's last axis up to its carried length
        (no-op on already-padded buffers).  Pad lanes start — and, by
        the kernel invariant, stay — zero, and unflatten reads only the
        logical prefix, so padded buffers flow through every dict-level
        op unchanged."""
        def _p(b):
            target = padded_len(b.shape[-1])
            if target == b.shape[-1]:
                return b
            widths = [(0, 0)] * (b.ndim - 1) + [(0, target - b.shape[-1])]
            return jnp.pad(b, widths)
        return {name: _p(b) for name, b in bufs.items()}

    def zeros(self, dtype=None) -> Dict[str, jnp.ndarray]:
        return self.pad(self.view.zeros(dtype))

    def normal(self, key) -> Dict[str, jnp.ndarray]:
        """Per-leaf standard-normal f32 buffers in carry layout (padded
        to the kernel grid — pad lanes zero, like every carried buffer).
        The draws are leaf-keyed (``view.normal``), so the tree oracle
        and both buffer flavors see identical bits for one key."""
        return self.pad(self.view.normal(key))

    def place(self, bufs: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        """Commit freshly packed buffers to their home placement AND
        guarantee they do not alias the caller's arrays — flatten is a
        NO-OP for a bucket holding exactly one 1-D leaf (concatenate of
        one array returns the operand), and the engine donates its
        carries, which would delete the caller's leaf.  Placement also
        pads to the kernel grid: carries enter the chunk pre-padded and
        every later kernel call skips its pad copy.  Host: copy (same
        cost as the tree path's place_params); pod: device_put with the
        per-bucket shardings, copying any passthrough."""
        return jax.tree_util.tree_map(jnp.array, self.pad(bufs))

    def shardings(self):
        """Per-bucket placement for jit in/out shardings (host: None)."""
        return None

    def stacked_flatten(self, tree: Pytree) -> Dict[str, jnp.ndarray]:
        return self.view.flatten_stacked(tree)

    def stacked_unflatten(self, bufs: Dict[str, jnp.ndarray],
                          frozen: Optional[Dict[str, jnp.ndarray]] = None
                          ) -> Pytree:
        """Stacked twin of :meth:`unflatten` — ``frozen`` rows (no K
        axis) broadcast over the stack."""
        return self.view.unflatten_stacked(bufs, frozen)

    # -- frozen bucket (filtered views; all no-ops when filter=None) --------

    def flatten_frozen(self, tree: Pytree) -> Dict[str, jnp.ndarray]:
        """Pack the FROZEN leaves — once per phase, never re-packed
        inside the round program.  Empty dict for an unfiltered view."""
        return self.view.flatten_frozen(tree)

    def frozen_zeros(self) -> Dict[str, jnp.ndarray]:
        return self.view.frozen_zeros()

    def place_frozen(self, bufs: Dict[str, jnp.ndarray]
                     ) -> Dict[str, jnp.ndarray]:
        """Commit the frozen constant bucket to its home placement.
        NEVER donated: the same arrays are closed over by every chunk of
        a phase.  Host: plain copy of the unpadded buckets (frozen
        buffers never enter the kernels); pod: device_put of the tiled
        buckets (``ShardedFlatOps.flatten_frozen``) with the
        frozen-group shardings."""
        return jax.tree_util.tree_map(jnp.array, bufs)

    def frozen_shardings(self):
        """Placement of the frozen constant bucket (host: None)."""
        return None

    # -- kernel execution ---------------------------------------------------

    def _run(self, name: str, fn: Callable, bufs, scalars) -> Tuple:
        """Run ``fn(*1-D buffers, *traced scalars) -> tuple of 1-D
        buffers`` for bucket ``name``.  Subclasses reroute this through
        shard_map; ``n_out`` only matters there."""
        del name
        return fn(*bufs, *scalars)

    def _logical_size(self, name: str) -> int:
        """Logical element count of bucket ``name`` as ONE kernel
        invocation sees it — the top-k population (pad lanes are zero
        and zeros never change the k-th largest |d|, so a logical k over
        a padded buffer is exact).  Host: the FlatView bucket size; the
        pod override returns the PER-SHARD size (shard-local top-k)."""
        return self.view.buffer_sizes[name]

    def grad_sqsum(self, g_bufs: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        """Σ‖g‖² over every bucket — the global clip norm is one
        reduction per bucket (sharded buffers reduce over the mesh)."""
        return sum(jnp.vdot(g, g) for g in g_bufs.values())

    def local_step(self, p_bufs, g_bufs, m_bufs, c_bufs, clip_scale,
                   step_size, *, weight_decay: float, momentum: float):
        """The fused client step tail over every bucket.  Returns
        ``(p_bufs, m_bufs)`` (``m_bufs`` empty when momentum is off)."""
        has_m, has_c = bool(momentum), c_bufs is not None
        interpret = self.interpret

        def fn(*a):
            it = iter(a)
            p1, g1 = next(it), next(it)
            m1 = next(it) if has_m else None
            c1 = next(it) if has_c else None
            cs, ss = next(it), next(it)
            pn, mn = ops.fused_local_step(
                p1, g1, m1, c1, cs, ss, weight_decay=weight_decay,
                momentum=momentum, interpret=interpret)
            return (pn, mn) if has_m else (pn,)

        new_p, new_m = {}, {}
        for name, p in p_bufs.items():
            bufs = [p, g_bufs[name]]
            if has_m:
                bufs.append(m_bufs[name])
            if has_c:
                bufs.append(c_bufs[name])
            outs = self._run(name, fn, bufs, (clip_scale, step_size))
            new_p[name] = outs[0]
            if has_m:
                new_m[name] = outs[1]
        return new_p, new_m

    def weighted_delta(self, p_bufs, stacked_bufs, wbar, extra=None, *,
                       deltas: bool = False):
        """Host FedAvg aggregation: the vmapped local outputs arrive as
        already-stacked ``(K, N)`` buffers — no re-concatenate.
        ``extra`` (optional f32 buffer dict — the round's DP noise +
        secure-agg mask total) folds into the same kernel pass.
        ``deltas=True`` reads the stack as already-formed client deltas
        (the compressed-communication aggregate)."""
        return {name: ops.fused_weighted_delta(
            stacked_bufs[name], p, wbar,
            None if extra is None else extra[name],
            deltas=deltas, interpret=self.interpret)
            for name, p in p_bufs.items()}

    def compress_delta(self, d_bufs, spec: CompressionSpec):
        """Compressed-communication form of one client's f32 delta dict
        — ``(c_bufs, r_bufs)``, ``r_bufs=None`` unless error feedback.
        The top-k threshold is computed INSIDE the per-bucket fn (one
        ``lax.top_k`` + one blocked kernel pass), so the pod flavor
        thresholds shard-locally under shard_map with zero collectives
        — each shard keeps its own k over its own elements."""
        interpret = self.interpret
        with_r = spec.error_feedback

        def make_fn(k):
            def fn(d1):
                tau = (topk_threshold(d1, k) if spec.sparsifies
                       else jnp.float32(0.0))
                out = ops.fused_compress_delta(
                    d1, tau, bits=spec.bits, topk=spec.sparsifies,
                    with_residual=with_r, interpret=interpret)
                return out if with_r else (out,)
            return fn

        c_out, r_out = {}, {}
        for name, d in d_bufs.items():
            k = topk_k(spec, self._logical_size(name))
            outs = self._run(name, make_fn(k), [d], ())
            c_out[name] = outs[0]
            if with_r:
                r_out[name] = outs[1]
        return c_out, (r_out if with_r else None)

    def dp_clip_noise(self, d_bufs, z_bufs, clip_scale, noise_scale):
        """One client's DP upload per bucket in ONE blocked pass:
        ``clip_scale·d₃₂ (+ noise_scale·z)`` (``z_bufs=None`` statically
        drops the Gaussian term).  The production aggregates fold these
        terms into ``weighted_delta``/``delta_accum`` coefficients and
        extras instead; this is the standalone kernel form for callers
        that materialize per-client uploads."""
        interpret = self.interpret
        has_z = z_bufs is not None

        def fn(*a):
            it = iter(a)
            d1 = next(it)
            z1 = next(it) if has_z else None
            cs, ns = next(it), next(it)
            return (ops.fused_dp_clip_noise(d1, z1, cs, ns,
                                            interpret=interpret),)

        out = {}
        for name, d in d_bufs.items():
            bufs = [d] + ([z_bufs[name]] if has_z else [])
            out[name] = self._run(name, fn, bufs,
                                  (clip_scale, noise_scale))[0]
        return out

    def delta_accum(self, delta_bufs, w_bufs, p_bufs, coeff):
        """One client's contribution to the pod's running f32 delta.
        ``p_bufs=None`` selects the accum-only form ``acc += coeff·w``
        (compressed uploads ARE deltas — there is no −coeff·p term)."""
        interpret = self.interpret
        with_p = p_bufs is not None

        def fn(*a):
            if with_p:
                d1, w1, p1, c1 = a
            else:
                (d1, w1, c1), p1 = a, None
            return (ops.fused_delta_accum(d1, w1, p1, c1,
                                          interpret=interpret),)

        return {name: self._run(
                    name, fn,
                    [d, w_bufs[name]] + ([p_bufs[name]] if with_p else []),
                    (coeff,))[0]
                for name, d in delta_bufs.items()}

    def apply_delta(self, p_bufs, delta_bufs):
        """p ← cast(p₃₂ + delta) per bucket (server_opt="none")."""
        new_p, _ = self.server_update(p_bufs, delta_bufs, (), (1.0,),
                                      opt="none")
        return new_p

    def server_update(self, p_bufs, delta_bufs, moments, scalars, *,
                      opt: str, beta: float = 0.9, b1: float = 0.9,
                      b2: float = 0.99):
        """Server optimizer over every bucket.  ``moments`` is a tuple
        of buffer dicts mirroring ``p_bufs`` (() for "none", (m,) for
        momentum, (mu, nu) for adam); ``scalars`` the traced scalars the
        kernel expects.  Returns ``(p_bufs, new_moments)``."""
        interpret = self.interpret
        n_m = len(moments)

        def fn(*a):
            it = iter(a)
            p1, d1 = next(it), next(it)
            ms = tuple(next(it) for _ in range(n_m))
            sc = tuple(it)
            pn, new = ops.fused_server_update(
                p1, d1, ms, sc, opt=opt, beta=beta, b1=b1, b2=b2,
                interpret=interpret)
            return (pn,) + tuple(new)

        new_p = {}
        new_ms: Tuple[Dict, ...] = tuple({} for _ in range(n_m))
        for name, p in p_bufs.items():
            bufs = [p, delta_bufs[name]] + [m[name] for m in moments]
            outs = self._run(name, fn, bufs, tuple(scalars))
            new_p[name] = outs[0]
            for i in range(n_m):
                new_ms[i][name] = outs[1 + i]
        return new_p, new_ms


@functools.lru_cache(maxsize=64)
def host_flat_ops(task: Task, interpret: bool,
                  filter_spec: Optional[str] = None) -> FlatParamOps:
    """The host backend's FlatParamOps for one task (cached — Task is a
    frozen dataclass).  ``filter_spec`` (a TRAINABLE_FILTERS name or a
    path regex) partitions the view into trainable/frozen buckets;
    None keeps the historical all-trainable view bitwise."""
    p_specs = jax.eval_shape(task.init, jax.random.PRNGKey(0))
    filt = None
    if filter_spec is not None:
        from repro.sharding import rules  # local import: rules ← flatten only
        filt = rules.trainable_mask(p_specs, filter_spec)
    return FlatParamOps(view=FlatView.of(p_specs, filter=filt),
                        interpret=interpret)


# ---------------------------------------------------------------------------
# the step tail — tree oracle and fused flat-buffer twin
# ---------------------------------------------------------------------------

def tree_step_tail(spec: LocalSpec, params: Pytree, grads: Pytree,
                   mom: Pytree, c_diff: Optional[Pytree], lr_scale):
    """The per-leaf reference update (clip → correction → decay →
    momentum → axpy).  Returns ``(params, mom)``."""
    # clip the RAW stochastic gradient, then apply the scaffold
    # correction and decoupled weight decay — clipping after decay
    # would rescale the regularizer with the gradient noise
    if spec.grad_clip:
        grads = tm.global_clip(grads, spec.grad_clip)
    if c_diff is not None:
        grads = tm.add(grads, c_diff)
    if spec.weight_decay:
        grads = tm.add_scaled(grads, params, spec.weight_decay)
    if spec.momentum:
        mom = tm.add_scaled(grads, mom, spec.momentum)
        eff = mom
    else:
        eff = grads
    params = jax.tree_util.tree_map(
        lambda p, g: (p - spec.lr * lr_scale * g).astype(p.dtype),
        params, eff)
    return params, mom


def fused_step_tail(spec: LocalSpec, fops: FlatParamOps, p_bufs: Dict,
                    g_bufs: Dict, m_bufs: Dict, c_bufs: Optional[Dict],
                    lr_scale):
    """The same tail over flat buffers: the global clip norm is ONE
    reduction per bucket and the rest is one fused kernel per bucket —
    O(1) ops per step regardless of tree depth."""
    if spec.grad_clip:
        sq = fops.grad_sqsum(g_bufs)
        clip_scale = jnp.minimum(
            1.0, spec.grad_clip / (jnp.sqrt(sq) + 1e-12)).astype(jnp.float32)
    else:
        clip_scale = jnp.float32(1.0)
    step_size = spec.lr * lr_scale
    return fops.local_step(p_bufs, g_bufs, m_bufs, c_bufs, clip_scale,
                           step_size, weight_decay=spec.weight_decay,
                           momentum=spec.momentum)


def make_local_fn(task: Task, spec: LocalSpec,
                  flat_ops: Optional[FlatParamOps] = None) -> Callable:
    """Build the per-client local-training function.

    tree impl : ``local(key, w_start, extras, cx, cy, lr_scale,
                frozen=None) -> (w_end, aux)`` over parameter TREES
                (``frozen`` is ignored — the tree path has no
                trainable-slice partition).
    fused impl: the SAME signature over flat buffer dicts — ``w_start``
                and ``w_end`` are FlatParamOps buffers holding ONLY the
                trainable slice; ``frozen`` is the read-only constant
                bucket dict merged at the loss boundary (never
                differentiated, never in the scan carry).  The tree
                exists only inside the loss closure (forward/backward
                boundary).  ``flat_ops`` selects the buffer flavor and
                defaults to the host FlatView ops for this task.

    extras (algorithm context, zero-size pytrees when unused; always
    TREES — they feed the loss at the forward boundary):
      w_global : anchor for fedprox / moon
      c_diff   : (c − c_i) correction for scaffold
      w_prev   : previous local model for moon
    aux: {'loss': mean local loss}
    """

    def loss_for_variant(params, extras, bx, by, rng):
        base = task.loss_fn(params, bx, by, rng)
        if spec.variant == "fedprox":
            prox = 0.5 * spec.mu * tm.squared_norm(tm.sub(params, extras["w_global"]))
            return base + prox
        if spec.variant == "moon":
            z = task.repr_fn(params, bx)
            z_glob = jax.lax.stop_gradient(task.repr_fn(extras["w_global"], bx))
            z_prev = jax.lax.stop_gradient(task.repr_fn(extras["w_prev"], bx))
            return base + spec.mu * _moon_contrastive(z, z_glob, z_prev,
                                                      spec.temperature)
        return base

    fused = spec.update_impl != "tree"
    if fused and flat_ops is None:
        flat_ops = host_flat_ops(task, ops.fused_interpret(spec.update_impl),
                                 effective_trainable_filter(spec))

    def local_tree(key: jax.Array, w_start: Pytree, extras: Dict[str, Pytree],
                   cx: jnp.ndarray, cy: jnp.ndarray, lr_scale: jnp.ndarray,
                   frozen: Optional[Dict] = None):
        del frozen  # tree path has no trainable-slice partition
        grad_fn = jax.value_and_grad(loss_for_variant)
        n_data = cx.shape[0]
        mom0 = tm.zeros_like(w_start) if spec.momentum else ()
        c_diff = extras["c_diff"] if spec.variant == "scaffold" else None

        def step(carry, step_key):
            params, mom = carry
            bidx = jax.random.randint(step_key, (spec.batch_size,), 0, n_data)
            with jax.named_scope("fl_fwd_bwd"):
                loss, grads = grad_fn(params, extras, cx[bidx], cy[bidx],
                                      step_key)
            with jax.named_scope("fl_step_tail"):
                params, mom = tree_step_tail(spec, params, grads, mom, c_diff,
                                             lr_scale)
            return (params, mom), loss

        keys = jax.random.split(key, spec.n_steps)
        (w_end, _), losses = jax.lax.scan(step, (w_start, mom0), keys)
        return w_end, {"loss": jnp.mean(losses)}

    def local_fused(key: jax.Array, p_start: Dict, extras: Dict[str, Pytree],
                    cx: jnp.ndarray, cy: jnp.ndarray, lr_scale: jnp.ndarray,
                    frozen: Optional[Dict] = None):
        n_data = cx.shape[0]
        # momentum mirrors the incoming buffers exactly (padded or not),
        # so the scan carry is shape-consistent however p_start arrived
        m0 = ({name: jnp.zeros_like(b) for name, b in p_start.items()}
              if spec.momentum else {})
        if spec.variant != "scaffold":
            c_bufs = None
        elif "c_diff_flat" in extras:
            # flat-state store: the correction is already a buffer dict
            # in carry layout — no per-client flatten
            c_bufs = extras["c_diff_flat"]
        else:
            c_bufs = flat_ops.pad(flat_ops.flatten(extras["c_diff"]))

        # the scan carries padded 1-D (host) buffers as (rows, 128)
        # tiles, the kernels' own shape: vmapped over clients, a (K, n)
        # carry is laid out on TPU in (K, 128) tiles, and every
        # step-tail call would relayout the whole client stack in and
        # out.  Pod buffers arrive tiled.
        shapes = {name: b.shape for name, b in p_start.items()}

        def tile(bufs):
            return {k: b.reshape(-1, LANES)
                    if b.ndim == 1 and b.shape[0] % LANES == 0 else b
                    for k, b in bufs.items()}

        def untile(bufs):
            return {k: b.reshape(shapes[k]) for k, b in bufs.items()}

        # differentiate w.r.t. the FLAT buffers: the tree materializes
        # only here, inside the loss closure, so the backward's
        # cotangents land directly in packed buffer form — the per-step
        # pack copy of the PR-4 flow does not exist.  ``frozen`` enters
        # as a closed-over constant on the non-differentiated side, so
        # the backward never touches (or allocates cotangents for) the
        # frozen leaves.
        def flat_loss(p_tiles, bx, by, rng):
            with jax.named_scope("fl_unflatten"):
                params = flat_ops.unflatten(untile(p_tiles), frozen)
            return loss_for_variant(params, extras, bx, by, rng)

        grad_fn = jax.value_and_grad(flat_loss)

        def step(carry, step_key):
            p_bufs, m_bufs = carry
            bidx = jax.random.randint(step_key, (spec.batch_size,), 0, n_data)
            with jax.named_scope("fl_fwd_bwd"):
                loss, g_bufs = grad_fn(p_bufs, cx[bidx], cy[bidx], step_key)
            with jax.named_scope("fl_step_tail"):
                p_bufs, m_bufs = fused_step_tail(
                    spec, flat_ops, untile(p_bufs), untile(g_bufs),
                    untile(m_bufs), c_bufs, lr_scale)
                p_bufs, m_bufs = tile(p_bufs), tile(m_bufs)
            return (p_bufs, m_bufs), loss

        keys = jax.random.split(key, spec.n_steps)
        (p_end, _), losses = jax.lax.scan(step, (tile(p_start), tile(m0)),
                                          keys)
        return untile(p_end), {"loss": jnp.mean(losses)}

    return local_fused if fused else local_tree
