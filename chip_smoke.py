"""Bring-up check: CyclicFL's main paths, once, on a TPU.

With no arguments (one chip) it runs, in one process:

  lm       the pod backend through the train CLI's own functions
           (``repro.launch.train``) at qwen1.5-0.5b's published config:
           one P1 relay round, two P2 FedAvg rounds (K=4 clients,
           2 local steps, batch 4, seq 512, fused update kernels), then
           one more P2 round under ``--server-opt adam``.  Every loss
           must be finite.
  paper    the host backend on the paper's own setting — LeNet-5 on
           cifar10-like non-IID data, K=10 clients vmapped — one P1 and
           two P2 rounds with the fused kernels and again with the tree
           oracle; the final params' updates must agree within
           PAPER_TOL.
  kernels  every fused update kernel once at qwen1.5-0.5b's carried
           flat length against its jnp oracle (repro.kernels.parity).

``--chips 4`` runs only the sharded pod path and what it is compared
with: phase lm's model (its widths and depth, in f32) on a 2x2
(data x model) mesh with hierarchical aggregation (two pods,
psum-lowered combine), and the same rounds on a one-device mesh
(sequential aggregation) in the same process; per-round losses and the
params' update from the shared init must agree within SHARDED_LOSS_RTOL
and SHARDED_UPDATE_RTOL.

    python3 chip_smoke.py [--chips 4] [--seed 0]

It exits non-zero, before any work, unless JAX's first device is a TPU.
No phase's failure is caught.  The last line of stdout is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

ARCH = "qwen1.5-0.5b"
# phase lm, as train-CLI arguments (server defaults: --server-opt none)
LM_ARGV = ["--arch", ARCH, "--full-config", "--cyclic-rounds", "1",
           "--rounds", "2", "--clients-per-round", "4", "--local-steps", "2",
           "--batch", "4", "--seq", "512", "--chunk-size", "1",
           "--update-impl", "fused"]
ADAM_LR = 0.01
# fused vs tree: relative L2 of the final params' update from the shared
# init.  Matmuls run at the default single-pass bf16 precision (3- and
# 6-pass precision compile the vmapped LeNet-5 round programs for over
# ten minutes on TPU), so the two runs round apart a little each step.
# Readings (PERF.md, finding 9): sound 2.0e-4 on one v5e, 1.4e-6 on
# CPU; planted faults on CPU — FedAvg weights made uniform 1.1e-2, the
# local step size 1% high 0.14.
PAPER_TOL = 2e-3
# 2x2 hierarchical vs 1-device sequential, the model in f32: per-round
# losses (relative) and the whole update from the shared init (relative
# L2).  Readings on a CPU proxy (reduced widths, 4 host devices; PERF.md,
# finding 8): sound 0 and 1.1e-5; one pod's clients counted twice
# (weights renormalized) 4.9e-4 and 0.11; one pod dropped (renormalized)
# 1.5e-3 and 0.33.  tests/test_chip_smoke_bounds.py plants both faults.
SHARDED_LOSS_RTOL = 2e-4
SHARDED_UPDATE_RTOL = 2e-2

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile_s[0] += duration


def _peak_bytes():
    stats = [d.memory_stats() for d in jax.devices()]
    # CPU devices keep no stats (the CPU proxies of the checks, in tests/)
    return max(s["peak_bytes_in_use"] for s in stats) if all(stats) else None


def _timed(label: str, fn, *args, **kwargs):
    """Run ``fn`` and print its compile seconds, wall seconds and the
    device's peak bytes in use so far."""
    c0, t0 = _compile_s[0], time.perf_counter()
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    print(f"[{label}] compile_s={_compile_s[0] - c0:.1f} "
          f"wall_s={time.perf_counter() - t0:.1f} "
          f"peak_bytes_in_use={_peak_bytes()}", flush=True)
    return out


def _print_rounds(label: str, history, wall_s=()) -> None:
    for i, h in enumerate(history):
        loss = h["loss"] if "loss" in h else h["local_loss"]
        if not math.isfinite(loss):
            raise RuntimeError(f"{label}: non-finite loss in {h}")
        extra = f" acc={h['acc']:.4f}" if "acc" in h else ""
        if i < len(wall_s):
            extra += f" wall_s={wall_s[i]:.2f}"
        print(f"[{label}] {h['phase']} round {h['round']} loss={loss:.5f}"
              f"{extra}", flush=True)


def _update_err(a, b, p0) -> float:
    """``||a − b|| / ||b − p0||`` over all leaves: how far two runs from
    the same init ``p0`` disagree, relative to how far they trained."""
    num = den = 0.0
    for x, y, z in zip(*(jax.tree_util.tree_leaves(t) for t in (a, b, p0))):
        x, y, z = (np.asarray(v, np.float32) for v in (x, y, z))
        num += float(np.sum((x - y) ** 2))
        den += float(np.sum((y - z) ** 2))
    return math.sqrt(num / den)


def _lm_setup(seed: int):
    from repro.launch import train
    args = train.build_parser().parse_args(LM_ARGV + ["--seed", str(seed)])
    cfg = train.make_config(args)
    data, kw = train.make_run(args, cfg)
    return cfg, data, kw


def phase_lm(seed: int) -> None:
    from repro.launch.train import run_pod_training
    cfg, data, kw = _lm_setup(seed)
    print(f"[lm] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"mesh {dict(kw['mesh'].shape)}", flush=True)
    # P1 then P2 as two calls of the CLI's run (init_params carries the
    # relayed model over, exactly as the one-call schedule does), so
    # each phase gets its own compile/wall/peak line
    p1 = _timed("lm P1", run_pod_training, cfg, data, **dict(kw, fl_rounds=0))
    _print_rounds("lm", p1.history, p1.round_wall_s)
    p2 = _timed("lm P2", run_pod_training, cfg, data, init_params=p1.params,
                **dict(kw, cyclic_rounds=0))
    del p1
    _print_rounds("lm", p2.history, p2.round_wall_s)
    adam = dataclasses.replace(kw["spec"], server_opt="adam",
                               server_lr=ADAM_LR)
    p3 = _timed("lm P2 adam", run_pod_training, cfg, data,
                init_params=p2.params,
                **dict(kw, cyclic_rounds=0, fl_rounds=1, spec=adam))
    del p2
    _print_rounds("lm adam", p3.history, p3.round_wall_s)


def paper_update_err(seed: int) -> float:
    """One P1 and two P2 rounds of LeNet-5 on cifar10-like non-IID data
    (host backend, K=10 vmapped), once with the fused kernels and once
    with the tree oracle; returns ``||update diff|| / ||update||`` of
    the final params."""
    from repro.core.cyclic import CyclicConfig
    from repro.core.pipeline import run_cyclic_then_federated
    from repro.data.synthetic import DATASETS
    from repro.fl.simulation import FLConfig
    from repro.fl.task import vision_task

    data = DATASETS.get("cifar10-like")(n_clients=10, beta=0.5, seed=seed,
                                        n_train=2000, n_test=500)
    task = vision_task("lenet5", n_classes=10, in_ch=3)
    common = dict(participation=1.0, local_steps=10, batch_size=32,
                  lr=0.01, eval_every=1, seed=seed, chunk_size=1)

    def run(impl: str):
        cyc = CyclicConfig(rounds=1, update_impl=impl, **common)
        fed = FLConfig(rounds=2, update_impl=impl, **common)
        return run_cyclic_then_federated(task, data, cyc, fed)

    results = {}
    for impl in ("fused", "tree"):
        res = _timed(f"paper {impl}", run, impl)
        _print_rounds(f"paper {impl}", res.history)
        results[impl] = res.federated.params
    return _update_err(results["fused"], results["tree"],
                       task.init(jax.random.PRNGKey(seed)))


def phase_paper(seed: int) -> None:
    err = paper_update_err(seed)
    print(f"[paper] fused vs tree: ||update diff|| / ||update|| = {err:.3e} "
          f"(tol {PAPER_TOL})", flush=True)
    if not err <= PAPER_TOL:
        raise RuntimeError("fused kernels disagree with the tree oracle")


def carried_flat_len(arch: str) -> int:
    """The length the engine carries ``arch``'s one bf16 bucket at."""
    from repro.configs import get_config
    from repro.fl.local import FlatParamOps
    from repro.fl.task import lm_task
    from repro.utils.flatten import FlatView

    task = lm_task(get_config(arch))
    p_specs = jax.eval_shape(task.init, jax.random.PRNGKey(0))
    (n,) = FlatParamOps(view=FlatView.of(p_specs),
                        interpret=False).padded_sizes.values()
    return n


def phase_kernels(seed: int) -> None:
    from repro.kernels import parity
    from repro.kernels.ops import fused_interpret

    n = carried_flat_len(ARCH)
    print(f"[kernels] {ARCH} carried flat length {n}", flush=True)
    key = jax.random.PRNGKey(seed)
    interpret = fused_interpret("fused")
    for name, case in parity.CASES.items():
        key, sub = jax.random.split(key)
        t0 = time.perf_counter()
        worst = case(n, sub, interpret)
        print(f"[kernels] {name}: worst error / tolerance = {worst:.3e} "
              f"wall_s={time.perf_counter() - t0:.1f}", flush=True)
        if not worst <= 1.0:
            raise RuntimeError(f"kernel {name} disagrees with its oracle: "
                               f"{worst:.3e} x tolerance")


def sharded_run(label: str, cfg, data, kw, mesh, **agg):
    """``run_pod_training`` on ``mesh``; returns the per-round losses and
    the final params, both moved to the host."""
    from repro.launch.train import run_pod_training
    print(f"[sharded] {label}: mesh {dict(mesh.shape)}", flush=True)
    res = _timed(f"sharded {label}", run_pod_training, cfg, data,
                 **dict(kw, mesh=mesh, **agg))
    _print_rounds(f"sharded {label}", res.history, res.round_wall_s)
    return (np.array([h["loss"] for h in res.history]),
            jax.tree_util.tree_map(np.asarray, res.params))


def sharded_errors(run, ref, p0) -> tuple:
    """``(loss_err, update_err)`` of a :func:`sharded_run` against a
    reference run from the same init ``p0``: the largest per-round
    relative loss difference, and ``||update diff|| / ||update||``."""
    (loss_a, params_a), (loss_b, params_b) = run, ref
    loss_err = float(np.max(np.abs(loss_a - loss_b) / np.abs(loss_b)))
    return loss_err, _update_err(params_a, params_b, p0)


def phase_sharded(seed: int) -> None:
    """The 2x2 hierarchical pod path (one pod per ``data`` row) vs the
    same rounds run sequentially on one device, both from the same
    seeded init.  The model runs in f32 here: bf16 params round the
    update to bf16 steps and bf16 activations round two layouts'
    partial sums apart, which together hide a dropped or doubled pod
    (PERF.md, finding 8)."""
    from repro.launch.mesh import make_device_mesh, make_host_mesh
    from repro.models.transformer import init_lm
    cfg, data, kw = _lm_setup(seed)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32,
                              param_dtype=jnp.float32)
    # the one-device run first: it needs the most memory on device 0
    ref = sharded_run("1-device sequential", cfg, data, kw,
                      make_host_mesh(), aggregation="sequential")
    mesh = make_device_mesh(model=2)
    run = sharded_run("2x2 hierarchical", cfg, data, kw, mesh,
                      aggregation="hierarchical",
                      n_pods=mesh.shape["data"])
    loss_err, update_err = sharded_errors(
        run, ref, init_lm(jax.random.PRNGKey(seed), cfg))
    print(f"[sharded] per-round loss rel. diff {loss_err:.3e} "
          f"(tol {SHARDED_LOSS_RTOL}); ||update diff|| / ||update|| "
          f"{update_err:.3e} (tol {SHARDED_UPDATE_RTOL})", flush=True)
    if not (loss_err <= SHARDED_LOSS_RTOL
            and update_err <= SHARDED_UPDATE_RTOL):
        raise RuntimeError("2x2 hierarchical run disagrees with 1 device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind} count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; nothing ran", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    from repro.kernels.ops import describe_fused, fused_interpret
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(describe_fused("fused"), flush=True)
    if fused_interpret("fused"):
        raise RuntimeError("fused kernels resolved to interpret mode on TPU")
    # one process owns the chip: nothing here may fork a JAX child, and
    # the modules that force fake host devices must stay out
    for mod in ("repro.launch.dryrun", "benchmarks.perf_hillclimb"):
        if mod in sys.modules:
            raise RuntimeError(f"{mod} is imported on a chip path")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    phases = ([phase_sharded] if args.chips == 4
              else [phase_lm, phase_kernels, phase_paper])
    for phase in phases:
        t0 = time.perf_counter()
        phase(args.seed)
        print(f"[{phase.__name__}] done in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
