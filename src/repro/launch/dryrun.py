import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture × input shape) pair, lower + compile the
production step program against ``ShapeDtypeStruct`` stand-ins (zero
allocation) on the 16×16 single-pod mesh and the 2×16×16 multi-pod mesh,
then extract the three roofline terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all                 # single-pod, all pairs
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod     # pod-axis pass

Results are appended as JSON to ``experiments/dryrun/<tag>.json`` so the
roofline table in EXPERIMENTS.md §Roofline is reproducible.

NOTE the XLA_FLAGS line above MUST run before any jax import — jax locks
the device count on first init.  Do not import this module from tests.
"""
import argparse
import json
import pathlib
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import (
    ASSIGNED, SHAPES, batch_specs, get_config, list_archs, param_count,
    active_param_count, params_specs, shape_applicable,
)
from repro.launch import mesh as meshlib
from repro.launch.hlo_analysis import analyze_compiled
from repro.launch.steps import (
    TrainSpec, make_prefill_step, make_serve_step, make_train_step,
    momentum_specs,
)
from repro.sharding import rules

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def model_flops_estimate(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE); decode D = B·1."""
    n = active_param_count(cfg) if cfg.is_moe else param_count(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def build_lowered(cfg, shape, mesh, *, n_micro: int = 1,
                  layout: str = "fsdp_tp"):
    """Lower the step program for (cfg, shape) on ``mesh``.  Returns
    (lowered, meta) — no compilation yet."""
    p_specs = params_specs(cfg)
    p_sh = rules.param_shardings(p_specs, mesh, layout)
    b_specs = batch_specs(cfg, shape)

    if shape.kind == "train":
        m_specs = momentum_specs(p_specs, dtype=jnp.float32)
        m_sh = rules.param_shardings(m_specs, mesh, layout)
        b_sh = rules.batch_shardings(b_specs, mesh, layout)
        step = make_train_step(cfg, TrainSpec(n_micro=n_micro))
        jitted = jax.jit(step, in_shardings=(p_sh, m_sh, b_sh),
                         out_shardings=(p_sh, m_sh, None))
        lowered = jitted.lower(p_specs, m_specs, b_specs)
    elif shape.kind == "prefill":
        b_sh = rules.batch_shardings(b_specs, mesh)
        step = make_prefill_step(cfg, max_len=shape.seq_len)
        # cache output layout: same generic rule the decode inputs use
        from repro.models.transformer import init_decode_cache
        cache_spec = jax.eval_shape(
            lambda: init_decode_cache(cfg, shape.global_batch, shape.seq_len))
        c_sh = rules.cache_shardings(cache_spec, mesh, shape.global_batch)
        jitted = jax.jit(step, in_shardings=(p_sh, b_sh),
                         out_shardings=(None, c_sh))
        lowered = jitted.lower(p_specs, b_specs)
    else:  # decode
        tok, cache, clen = b_specs["token"], b_specs["cache"], b_specs["cache_len"]
        t_sh = rules.batch_shardings(tok, mesh)
        c_sh = rules.cache_shardings(cache, mesh, shape.global_batch)
        step = make_serve_step(cfg)
        jitted = jax.jit(step, in_shardings=(p_sh, t_sh, c_sh, None),
                         out_shardings=(None, c_sh))
        lowered = jitted.lower(p_specs, tok, cache, clen)
    return lowered


def _layer_variant(cfg, n_scan_layers: int):
    """Same config with ``n_scan_layers`` scanned layers, fully unrolled —
    XLA cost_analysis counts a while body ONCE regardless of trip count
    (verified empirically), so scanned stacks undercount FLOPs/bytes/
    collectives by ~L.  Costs are linear in the scanned-layer count, so
    two tiny unrolled compiles (1 and 2 layers) give exact per-layer cost
    by differencing; run_pair extrapolates to the real depth."""
    import dataclasses as _dc
    return _dc.replace(cfg, n_layers=cfg.n_dense_layers + n_scan_layers,
                       scan_unroll=True)


def _scan_cost_correction(cfg, shape, mesh, n_chips, *, n_micro=1,
                          layout="fsdp_tp"):
    """Return (flops, bytes, collective_bytes) corrected for the layer-scan
    undercount via 1-layer/2-layer unrolled extrapolation."""
    costs = []
    for n in (1, 2):
        lowered = build_lowered(_layer_variant(cfg, n), shape, mesh,
                                n_micro=n_micro, layout=layout)
        compiled = lowered.compile()
        t = analyze_compiled(compiled, arch=cfg.name, shape=shape.name,
                             mesh_name="corr", n_chips=n_chips,
                             device_kind=meshlib.V5E)
        costs.append((t.hlo_flops, t.hlo_bytes, t.collective_bytes))
    (f1, b1, c1), (f2, b2, c2) = costs
    L = cfg.n_layers - cfg.n_dense_layers
    return (f1 + (L - 1) * (f2 - f1),
            b1 + (L - 1) * (b2 - b1),
            int(c1 + (L - 1) * (c2 - c1)))


def run_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
             n_micro: int = 1, verbose: bool = True, save: bool = True,
             cfg_override=None, correct_scan: bool = True,
             layout: str = "fsdp_tp", tag: str = "") -> dict:
    shape = SHAPES[shape_name]
    cfg = cfg_override or get_config(arch)
    mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    n_chips = 512 if multi_pod else 256
    t0 = time.time()
    row = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "ok": False,
           "layout": layout}
    try:
        with mesh:
            lowered = build_lowered(cfg, shape, mesh, n_micro=n_micro,
                                    layout=layout)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        terms = analyze_compiled(
            compiled, arch=arch, shape=shape_name, mesh_name=mesh_name,
            n_chips=n_chips, device_kind=meshlib.V5E,
            model_flops=model_flops_estimate(cfg, shape))
        if correct_scan and cfg.n_layers > cfg.n_dense_layers + 1:
            raw = (terms.hlo_flops, terms.hlo_bytes, terms.collective_bytes)
            with mesh:
                fc, bc, cc = _scan_cost_correction(cfg, shape, mesh, n_chips,
                                                   n_micro=n_micro,
                                                   layout=layout)
            # keep whichever is LARGER per term: the full compile already
            # counts non-layer cost exactly and the extrapolation can only
            # add layer-body repetitions it missed
            terms.hlo_flops = max(terms.hlo_flops, fc)
            terms.hlo_bytes = max(terms.hlo_bytes, bc)
            terms.collective_bytes = max(terms.collective_bytes, cc)
            row["raw_uncorrected"] = {
                "hlo_flops": raw[0], "hlo_bytes": raw[1],
                "collective_bytes": raw[2]}
        row.update(terms.to_dict())
        row.update(ok=True, t_lower_s=round(t_lower, 1),
                   t_compile_s=round(t_compile, 1))
        if verbose:
            print(f"[dryrun] {arch:22s} {shape_name:12s} {mesh_name:8s} OK  "
                  f"flops={terms.hlo_flops:.3e} coll={terms.collective_bytes:.3e}B "
                  f"bottleneck={terms.bottleneck} "
                  f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)", flush=True)
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        row["error"] = f"{type(e).__name__}: {e}"
        if verbose:
            print(f"[dryrun] {arch:22s} {shape_name:12s} {mesh_name:8s} FAIL "
                  f"{row['error'][:200]}", flush=True)
            traceback.print_exc()
    if save:
        OUT_DIR.mkdir(parents=True, exist_ok=True)
        stem = f"{arch}_{shape_name}_{mesh_name}".replace("/", "-")
        if tag:
            stem += f"_{tag}"
        (OUT_DIR / f"{stem}.json").write_text(json.dumps(row, indent=1))
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="architecture id (see --list)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true",
                    help="sweep every applicable (arch × shape) pair")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2×16×16 pod-axis mesh (512 chips)")
    ap.add_argument("--n-micro", type=int, default=1,
                    help="gradient-accumulation microbatches for train shapes")
    ap.add_argument("--layout", default="fsdp_tp",
                    choices=("fsdp_tp", "fsdp_only"),
                    help="parameter/batch layout (EXPERIMENTS.md §Perf)")
    ap.add_argument("--no-correct-scan", action="store_true",
                    help="skip the 1/2-layer unrolled cost extrapolation")
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)

    if args.list:
        for a in list_archs():
            print(a)
        return 0

    pairs = []
    if args.all:
        for arch in ASSIGNED:
            for s in SHAPES:
                if shape_applicable(arch, s):
                    pairs.append((arch, s))
    else:
        if not args.arch or not args.shape:
            ap.error("need --arch and --shape (or --all)")
        pairs = [(args.arch, args.shape)]

    failures = 0
    for arch, s in pairs:
        row = run_pair(arch, s, multi_pod=args.multi_pod,
                       n_micro=args.n_micro, layout=args.layout,
                       correct_scan=not args.no_correct_scan, tag=args.tag)
        failures += 0 if row["ok"] else 1
    print(f"[dryrun] {len(pairs) - failures}/{len(pairs)} pairs OK")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
