"""Pod-scale federated training driver — CyclicFL as a first-class
distributed feature.

This is the production mapping of the paper's two phases onto a TPU mesh
(DESIGN.md §3).  Clients are *simulated mesh tenants*: every client's
local batch is sharded over the ``data`` (and ``pod``) axis and the model
over ``model`` (FSDP × TP via repro.sharding.rules), so ONE XLA program
runs a whole federated round:

  P1 (cyclic relay)   : ``lax.scan`` over the K selected clients carrying
                        the model — the strict sequential schedule of
                        Algorithm 1.  No aggregation — the model hops
                        client→client exactly like the paper's
                        server-relayed download/upload, except the "hop"
                        is free on-chip.
  P2 (federated round): the same scan, but each client starts from the
                        round's global params and emits a weighted delta;
                        aggregation is the running weighted delta sum —
                        the computation that IS the FedAvg all-reduce.
                        fedavg / fedprox / scaffold / moon, with
                        per-client state sharded over the mesh ``data``
                        axis (repro.fl.pod.ShardedClientStateStore).

Since PR 2 the driver is a thin schedule over the shared round engine:
``run_pod_training`` builds ``PodCyclicConfig``/``PodFLConfig`` phases
and hands them to ``core.pipeline.run_phase_schedule``, so the sharded
path gets on-device client sampling, in-program key derivation, chunked
``chunk_size``-rounds-per-dispatch scans with donated sharded carries,
lr schedules and switch policies — identical to the host simulator.
The pre-sampled per-round bodies (``make_pod_cyclic_round`` /
``make_pod_fl_round``) are kept for AOT lowering (dry-run HLO analysis)
and as the per-round-dispatch baseline in benchmarks/perf_pod_round.py.

CLI (reduced configs by default; ``--full-config`` builds the published
one; the mesh spans every device JAX sees, on the ``data`` axis):
    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --rounds 4 --cyclic-rounds 2 --clients 8
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import sys
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pipeline import Phase, run_phase_schedule
from repro.fl.compression import CompressionSpec
from repro.fl.pod import (
    POD_ALGORITHMS,
    PodCyclicConfig,
    PodFLConfig,
    PodFLSpec,
)
from repro.fl.privacy import DPSpec
from repro.fl.task import lm_task
from repro.models.transformer import TransformerConfig, init_lm, lm_loss
from repro.sharding import rules
from repro.sharding.rules import fl_batch_pspec, fl_batch_shardings  # noqa: F401  (re-export)
from repro.utils import tree_math as tm

Pytree = Any


def _local_sgd(cfg: TransformerConfig, spec: PodFLSpec):
    """t_i SGD steps on one client's pre-sampled batches.

    (params, batches, lr_scale, w_anchor) -> (params, mean_loss)
    batches leaves: (t_i, B, S); w_anchor is the fedprox anchor (the
    round's global params) or None.  Kept for the AOT-lowered round
    bodies; the engine path runs the same math through
    ``repro.fl.local.make_local_fn`` with on-device batch sampling.
    """

    def loss_fn(params, mb, anchor):
        loss, _ = lm_loss(params, cfg, mb)
        if spec.algorithm == "fedprox" and anchor is not None:
            loss = loss + 0.5 * spec.mu * tm.squared_norm(
                jax.tree_util.tree_map(
                    lambda p, a: (p - a).astype(jnp.float32), params, anchor))
        return loss

    def run(params, batches, lr_scale, anchor):
        mom0 = tm.zeros_like(params) if spec.momentum else ()

        def step(carry, mb):
            w, mom = carry
            loss, grads = jax.value_and_grad(loss_fn)(w, mb, anchor)
            # clip the RAW gradient, then decay — same order as
            # repro.fl.local (parity-tested in tests/test_pod_engine.py)
            if spec.grad_clip:
                grads = tm.global_clip(grads, spec.grad_clip)
            if spec.weight_decay:
                grads = tm.add_scaled(grads, w, spec.weight_decay)
            if spec.momentum:
                mom = tm.add_scaled(grads, mom, spec.momentum)
                eff = mom
            else:
                eff = grads
            w = jax.tree_util.tree_map(
                lambda p, g: (p - spec.lr * lr_scale * g).astype(p.dtype),
                w, eff)
            return (w, mom), loss

        (params, _), losses = jax.lax.scan(step, (params, mom0), batches)
        return params, jnp.mean(losses)

    return run


def make_pod_cyclic_round(cfg: TransformerConfig, spec: PodFLSpec) -> Callable:
    """P1: sequential relay over K clients (Algorithm 1, one round).

    (params, batches, lr_scale) -> (params, metrics)
    batches leaves: (K, t_i, B, S) — client-major.  The scan carry is the
    relayed model; there is deliberately NO aggregation.
    """
    local = _local_sgd(cfg, spec)

    def round_fn(params, batches, lr_scale):
        def relay(w, client_batches):
            w, loss = local(w, client_batches, lr_scale, None)
            return w, loss

        params, losses = jax.lax.scan(relay, params, batches)
        return params, {"local_loss": jnp.mean(losses)}

    return round_fn


def make_pod_fl_round(cfg: TransformerConfig, spec: PodFLSpec) -> Callable:
    """P2: one federated round = local runs + weighted-delta aggregation.

    (params, batches, weights, lr_scale) -> (params, metrics)
    batches leaves: (K, t_i, B, S); weights: (K,) client sample counts N_i.

    Clients run sequentially (scan) — at LLM scale a full per-client
    parameter copy per vmap lane is exactly what does NOT fit, so the
    production schedule trades wall-clock serialization for memory:
    peak = 2×params (+momentum), independent of K.  The weighted delta
    accumulator is the FedAvg aggregation; on the mesh its reduction is
    the all-reduce the paper's server performs.
    """
    local = _local_sgd(cfg, spec)

    def round_fn(params, batches, weights, lr_scale):
        wsum = jnp.sum(weights)

        def one_client(acc, inp):
            client_batches, w_i = inp
            anchor = params if spec.algorithm == "fedprox" else None
            w_end, loss = local(params, client_batches, lr_scale, anchor)
            acc = jax.tree_util.tree_map(
                lambda a, we, p: a + (w_i / wsum) * (we - p).astype(a.dtype),
                acc, w_end, params)
            return acc, loss

        delta0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        delta, losses = jax.lax.scan(one_client, delta0,
                                     (batches, weights.astype(jnp.float32)))
        new_params = jax.tree_util.tree_map(
            lambda p, d: (p + d.astype(jnp.float32)).astype(p.dtype),
            params, delta)
        return new_params, {"local_loss": jnp.mean(losses)}

    return round_fn


def lower_pod_round(cfg: TransformerConfig, mesh, *, kind: str = "fl",
                    spec: Optional[PodFLSpec] = None, K: int = 8,
                    batch: int = 32, seq: int = 512):
    """AOT-lower a pod federated/cyclic round on ``mesh`` (dry-run path)."""
    spec = spec or PodFLSpec()
    p_specs = jax.eval_shape(lambda k: init_lm(k, cfg), jax.random.PRNGKey(0))
    p_sh = rules.param_shardings(p_specs, mesh)
    b_specs = {
        "tokens": jax.ShapeDtypeStruct((K, spec.local_steps, batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((K, spec.local_steps, batch, seq), jnp.int32),
    }
    b_sh = fl_batch_shardings(b_specs, mesh)
    w_specs = jax.ShapeDtypeStruct((K,), jnp.float32)
    lr_specs = jax.ShapeDtypeStruct((), jnp.float32)

    with mesh:
        if kind == "cyclic":
            step = make_pod_cyclic_round(cfg, spec)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh, None),
                             out_shardings=(p_sh, None))
            return jitted.lower(p_specs, b_specs, lr_specs)
        step = make_pod_fl_round(cfg, spec)
        jitted = jax.jit(step, in_shardings=(p_sh, b_sh, None, None),
                         out_shardings=(p_sh, None))
        return jitted.lower(p_specs, b_specs, w_specs, lr_specs)


# ---------------------------------------------------------------------------
# end-to-end driver: the engine's phase schedule on the pod backend
# ---------------------------------------------------------------------------

def sample_round_batches(data, ids: np.ndarray, steps: int, batch: int,
                         rng: np.random.Generator) -> Dict[str, jnp.ndarray]:
    """Pre-sample (K, steps, batch, S) token/label batches for ``ids``
    (the per-round-dispatch baseline; the engine samples on device)."""
    toks, labs = [], []
    for cid in ids:
        bidx = rng.integers(0, data.n_per_client, size=(steps, batch))
        toks.append(data.x[cid][bidx])
        labs.append(data.y[cid][bidx])
    return {"tokens": jnp.asarray(np.stack(toks)),
            "labels": jnp.asarray(np.stack(labs))}


@dataclasses.dataclass
class PodTrainResult:
    params: Pytree
    history: list
    round_wall_s: list = dataclasses.field(default_factory=list)


def run_pod_training(cfg: TransformerConfig, data, *,
                     cyclic_rounds: int = 2, fl_rounds: int = 4,
                     clients_per_round: int = 4,
                     spec: Optional[PodFLSpec] = None,
                     mesh=None, seed: int = 0,
                     eval_fn: Optional[Callable] = None,
                     eval_every: Optional[int] = None,
                     eval_batch: int = 64,
                     verbose: bool = False,
                     chunk_size: int = 4,
                     sampling: str = "device",
                     layout: str = "fsdp_tp",
                     aggregation: str = "sequential",
                     n_pods: Optional[int] = None,
                     store: str = "dense",
                     store_capacity: int = 1024,
                     overlap: str = "on",
                     init_params: Optional[Pytree] = None) -> PodTrainResult:
    """CyclicFL end-to-end on the pod backend: a declarative P1→P2 phase
    schedule through the shared round engine — no hand-rolled loops.

    Evaluation streams IN PROGRAM (repro.fl.engine): rounds on the
    ``eval_every`` cadence score the held-out test set inside the
    compiled chunk, so evaluating keeps ONE mesh dispatch per
    ``chunk_size`` rounds — there is no per-round-dispatch eval mode
    anymore.  ``eval_fn`` optionally overrides the default test-accuracy
    metric and must be traceable with the engine's per-sample contract
    ``eval_fn(params, bx, by) -> (B,)``.  ``eval_every=None`` defaults
    to every round when a custom metric is given (the legacy cadence)
    and to no evaluation otherwise; evaluated rounds carry an ``eval``
    entry in their history row.  ``init_params`` continues from a
    previous run's params (the first phase then skips its seeded init),
    so running P1 and P2 as two calls equals running them as one.
    """
    from repro.launch.mesh import make_host_mesh
    spec = spec or PodFLSpec()
    mesh = mesh or make_host_mesh()
    task = lm_task(cfg)

    if eval_every is None:
        eval_every = 1 if eval_fn is not None else 0

    common = dict(mesh=mesh, clients_per_round=clients_per_round, spec=spec,
                  layout=layout, chunk_size=chunk_size, sampling=sampling,
                  eval_every=eval_every, eval_batch=eval_batch)
    # P2-only knobs: aggregation topology, the client-state store and
    # the overlapped residency pipeline (P1 relays the model and keeps
    # no per-client state, so overlap has nothing to hide there)
    if overlap not in ("on", "off"):
        raise ValueError(f"--overlap must be on|off, got {overlap!r}")
    fl_extra = dict(aggregation=aggregation, n_pods=n_pods, store=store,
                    store_capacity=store_capacity,
                    overlap=(overlap == "on"))
    phases = []
    if cyclic_rounds > 0:
        # privacy, compression and the trainable-slice filter apply at
        # the P2 aggregate only — P1 relays the model client-to-client
        # with no aggregation (clients need exact params to train on,
        # and the relay hop carries the full model), so the relay phase
        # runs with those knobs stripped (RelayStrategy rejects them)
        p1_common = dict(common, spec=dataclasses.replace(
            spec, dp=None, secure_agg=False, compression=None,
            peft=None, trainable_filter=None))
        phases.append(Phase("P1", PodCyclicConfig(rounds=cyclic_rounds,
                                                  seed=seed, **p1_common),
                            eval_fn=eval_fn))
    if fl_rounds > 0:
        # decorrelate the P2 key stream from P1's: each phase restarts
        # from PRNGKey(its seed), and with equal K the relay and
        # aggregate rounds split keys identically — the same seed would
        # replay P1's exact client selections and batch draws in P2.
        # When P2 is the first phase its seed also drives model init,
        # so only offset when a P1 phase (here or in the run that made
        # init_params) precedes it.
        from repro.fl.pod import HOST_RNG_OFFSET_P2
        p2_seed = seed + HOST_RNG_OFFSET_P2 \
            if phases or init_params is not None else seed
        phases.append(Phase("P2", PodFLConfig(rounds=fl_rounds, seed=p2_seed,
                                              **common, **fl_extra),
                            eval_fn=eval_fn))
    if not phases:
        params = init_params if init_params is not None \
            else init_lm(jax.random.PRNGKey(seed), cfg)
        return PodTrainResult(params=params, history=[])

    sched = run_phase_schedule(task, data, phases, verbose=verbose,
                               init_params=init_params)
    history = []
    for h in sched.history:
        row = {"phase": h["phase"], "round": h["round"],
               "loss": h["local_loss"]}
        if "acc" in h:
            row["eval"] = h["acc"]
        history.append(row)
    return PodTrainResult(
        params=sched.params, history=history,
        round_wall_s=[w for ph in sched.phases
                      for w in ph.result.round_wall_s])


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--full-config", action="store_true",
                    help="build the arch's published config "
                         "(repro.configs.get_config) in place of its "
                         "reduced smoke variant")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--cyclic-rounds", type=int, default=2)
    ap.add_argument("--clients", "--n-clients", dest="clients", type=int,
                    default=16, help="population size N (synthetic shards)")
    ap.add_argument("--clients-per-round", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8,
                    help="per-step local batch size B")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--algorithm", default="fedavg",
                    choices=POD_ALGORITHMS)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--server-opt", default="none",
                    choices=("none", "momentum", "adam"),
                    help="server-side optimizer on the aggregated "
                         "pseudo-gradient (FedAvgM / FedAdam)")
    ap.add_argument("--server-lr", type=float, default=1.0,
                    help="server step size; 1.0 suits momentum (FedAvgM), "
                         "adam wants ~0.01-0.1 (its update is sign-scale)")
    ap.add_argument("--server-momentum", type=float, default=0.9)
    ap.add_argument("--update-impl", default="fused",
                    choices=("tree", "fused", "fused_interpret"),
                    help="step-tail/aggregation implementation: the fused "
                         "flat-first path (default — ShardedFlatView "
                         "buffers preserve the FSDP×TP layout and the "
                         "kernels run shard-locally; Mosaic on TPU, the "
                         "Pallas interpreter on any other backend, as "
                         "printed at start) or the per-leaf tree algebra "
                         "(the parity oracle)")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="in-program test-accuracy cadence "
                         "(0 = no evaluation; never splits a chunk)")
    ap.add_argument("--chunk-size", type=int, default=4,
                    help="rounds fused into one XLA dispatch")
    ap.add_argument("--sampling", default="device",
                    choices=("device", "host"))
    ap.add_argument("--layout", default="fsdp_tp", choices=rules.LAYOUTS)
    ap.add_argument("--aggregation", default="sequential",
                    choices=("sequential", "hierarchical"),
                    help="P2 topology: one scan over all K clients, or "
                         "two-level — per-pod partial deltas + one "
                         "cross-pod combine (pods default to the mesh "
                         "data-axis size; see --n-pods)")
    ap.add_argument("--n-pods", type=int, default=None,
                    help="pod count for --aggregation hierarchical "
                         "(must divide clients-per-round)")
    ap.add_argument("--store", default="dense", choices=("dense", "sparse"),
                    help="per-client state store: dense (n_clients, ...) "
                         "stacks or the participation-indexed sparse "
                         "active-set table (O(capacity) memory)")
    ap.add_argument("--store-capacity", type=int, default=1024,
                    help="sparse store rows; must cover the distinct "
                         "participants of one dispatch "
                         "(chunk-size x clients-per-round)")
    ap.add_argument("--overlap", default="on", choices=("on", "off"),
                    help="pipeline sparse-store residency for dispatch "
                         "N+1 behind dispatch N's device compute "
                         "(bitwise-identical results; off = synchronous "
                         "prepare between dispatches)")
    ap.add_argument("--dp-clip", type=float, default=None,
                    help="DP-FedAvg per-client delta clip bound C "
                         "(None = no clipping)")
    ap.add_argument("--dp-sigma", type=float, default=0.0,
                    help="DP-FedAvg noise multiplier (per-client stddev "
                         "sigma*C, applied at aggregation; needs "
                         "--dp-clip)")
    ap.add_argument("--secure-agg", action="store_true",
                    help="simulate pairwise-masked secure aggregation "
                         "(masks cancel in the round sum)")
    ap.add_argument("--compress-bits", type=int, default=32,
                    choices=(8, 16, 32),
                    help="P2 upload quantization: blockwise symmetric "
                         "int8/int16 fake quantization of each client's "
                         "delta (32 = no quantization)")
    ap.add_argument("--compress-density", type=float, default=1.0,
                    help="P2 upload top-k sparsification: fraction of "
                         "delta elements kept per bucket, by magnitude "
                         "(1.0 = keep everything)")
    ap.add_argument("--error-feedback", action="store_true",
                    help="carry each client's compression residual and "
                         "add it to the next participating round's delta "
                         "(needs a lossy --compress-bits/-density combo)")
    ap.add_argument("--peft", default=None, metavar="lora:<r>",
                    help="parameter-efficient P2: build the model with "
                         "rank-r LoRA adapters and train ONLY them — "
                         "frozen leaves never enter the kernels, the "
                         "donated carry or the upload (P1 still relays "
                         "the full model)")
    ap.add_argument("--trainable-filter", default=None,
                    choices=sorted(rules.TRAINABLE_FILTERS),
                    help="named trainable-leaf filter (overrides the one "
                         "--peft implies); needs --update-impl fused")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="write a JAX profiler trace of the whole run "
                         "here: the engine's engine.* host spans and the "
                         "round program's fl_* phases on one clock "
                         "(docs/ARCHITECTURE.md, Observability)")
    return ap


def make_config(args) -> TransformerConfig:
    from repro.configs import get_config, get_reduced, with_peft
    build = get_config if args.full_config else get_reduced
    return with_peft(build(args.arch), args.peft)


def make_run(args, cfg: TransformerConfig):
    """``(data, kwargs)`` for ``run_pod_training(cfg, data, **kwargs)``
    from parsed CLI args: synthetic token data from ``--seed``, the
    PodFLSpec and the mesh."""
    from repro.data.synthetic import make_synthetic_tokenlm
    from repro.launch.mesh import make_device_mesh

    data = make_synthetic_tokenlm(
        n_clients=args.clients, seq_len=args.seq, n_seq_per_client=64,
        vocab=cfg.vocab_size, beta=0.5, seed=args.seed)
    dp = DPSpec(args.dp_clip, args.dp_sigma) \
        if args.dp_clip is not None else None
    comp = CompressionSpec(bits=args.compress_bits,
                           density=args.compress_density,
                           error_feedback=args.error_feedback)
    spec = PodFLSpec(local_steps=args.local_steps, batch_size=args.batch,
                     lr=args.lr, algorithm=args.algorithm,
                     server_opt=args.server_opt, server_lr=args.server_lr,
                     server_momentum=args.server_momentum,
                     update_impl=args.update_impl, dp=dp,
                     secure_agg=args.secure_agg,
                     compression=None if comp.identity else comp,
                     peft=args.peft, trainable_filter=args.trainable_filter)
    return data, dict(
        cyclic_rounds=args.cyclic_rounds, fl_rounds=args.rounds,
        clients_per_round=args.clients_per_round, spec=spec,
        mesh=make_device_mesh(),
        seed=args.seed, chunk_size=args.chunk_size,
        eval_every=args.eval_every,
        sampling=args.sampling, layout=args.layout,
        aggregation=args.aggregation, n_pods=args.n_pods,
        store=args.store, store_capacity=args.store_capacity,
        overlap=args.overlap)


def main(argv=None) -> int:
    from repro.kernels.ops import describe_fused
    from repro.launch.compile_cache import enable_compile_cache

    args = build_parser().parse_args(argv)
    cfg = make_config(args)
    if cfg.input_mode != "tokens":
        print(f"[train] {args.arch}: the pod trainer takes token-mode archs; "
              f"{cfg.input_mode}-mode archs train via the same round fns "
              "with embedding batches (see examples/)", file=sys.stderr)
        return 2
    enable_compile_cache()
    print(f"[train] {describe_fused(args.update_impl)}", flush=True)
    data, run_kwargs = make_run(args, cfg)
    t0 = time.time()
    trace = (jax.profiler.trace(args.trace_dir) if args.trace_dir
             else contextlib.nullcontext())
    with trace:
        res = run_pod_training(cfg, data, verbose=True, **run_kwargs)
    first = res.history[0]["loss"]
    last = res.history[-1]["loss"]
    print(f"[train] {args.arch}: loss {first:.4f} -> {last:.4f} "
          f"({time.time() - t0:.1f}s)")
    return 0 if last < first else 1


if __name__ == "__main__":
    sys.exit(main())
