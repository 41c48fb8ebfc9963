"""§Roofline report — aggregate the dry-run artifacts into the
per-(arch × shape × mesh) three-term roofline table.

Reads experiments/dryrun/*.json (written by repro.launch.dryrun) and
emits a markdown table with:
  compute / memory / collective terms (seconds), dominant bottleneck,
  MODEL_FLOPS = 6·N(_active)·D, useful-FLOPs ratio.

A second table covers the fused FL-update kernels
(repro.kernels.fused_update): analytic TPU roofline terms per model
size (they are pure-elementwise, so t_memory dominates by construction)
plus a measured interpret-mode wall time on this host (timed with the
shared ``benchmarks/common.py:time_best_of`` policy).

    PYTHONPATH=src python -m benchmarks.roofline_report [--mesh 16x16]
    PYTHONPATH=src python -m benchmarks.roofline_report --no-update-kernels
"""
from __future__ import annotations

import argparse
import json
import pathlib

DRYRUN_DIR = pathlib.Path(__file__).resolve().parents[1] / "experiments" / "dryrun"

SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]

# fused-update kernel traffic models: (name, reads, writes) in units of
# one n_params f32 buffer — e.g. local_step reads p,g,m and writes p,m
UPDATE_KERNELS = [
    ("local_step",      3, 2),     # p,g,m -> p,m  (momentum variant)
    ("delta_accum",     3, 1),     # d,w,p -> d
    ("server_momentum", 3, 2),     # p,delta,m -> p,m
    ("server_adam",     4, 3),     # p,delta,mu,nu -> p,mu,nu
]


def load_rows(mesh: str):
    rows = []
    for f in sorted(DRYRUN_DIR.glob(f"*_{mesh}.json")):
        r = json.loads(f.read_text())
        if r.get("mesh") == mesh:
            rows.append(r)
    rows.sort(key=lambda r: (r["arch"], SHAPE_ORDER.index(r["shape"])
                             if r["shape"] in SHAPE_ORDER else 99))
    return rows


def _fmt_s(x) -> str:
    if x is None:
        return "-"
    if x >= 1.0:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x * 1e3:.1f}ms"
    return f"{x * 1e6:.0f}us"


def to_markdown(rows) -> str:
    head = ("| arch | shape | t_compute | t_memory | t_collective | "
            "bottleneck | useful_flops | status |")
    sep = "|" + "---|" * 8
    lines = [head, sep]
    for r in rows:
        if not r.get("ok"):
            lines.append(f"| {r['arch']} | {r['shape']} | - | - | - | - | - | "
                         f"FAIL: {r.get('error', '?')[:60]} |")
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {_fmt_s(r['t_compute_s'])} | "
            f"{_fmt_s(r['t_memory_s'])} | {_fmt_s(r['t_collective_s'])} | "
            f"{r['bottleneck']} | {r['useful_flops_ratio']:.2f} | ok |")
    return "\n".join(lines)


def update_kernel_rows(n_params_list, repeats: int = 3):
    """Roofline rows for the fused FL-update kernels: analytic TPU terms
    (v5e peaks from repro.launch.mesh.CHIP_PEAKS — elementwise kernels,
    so memory-bound by construction) plus a measured interpret-mode
    wall time on this host for the local_step kernel."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import time_best_of
    from repro.kernels import ops
    from repro.launch.mesh import V5E, chip_peaks
    peaks = chip_peaks(V5E)

    rows = []
    for n in n_params_list:
        for name, reads, writes in UPDATE_KERNELS:
            bytes_moved = (reads + writes) * 4 * n
            flops = 8 * n          # ~a handful of FMA-class ops per elem
            row = {"kernel": name, "n_params": n,
                   "bytes": bytes_moved, "flops": flops,
                   "t_compute_s": flops / peaks.flops_bf16,
                   "t_memory_s": bytes_moved / peaks.hbm_bw,
                   "bottleneck": "memory"}
            if name == "local_step":
                p = jnp.zeros((n,), jnp.float32)
                g = jnp.ones((n,), jnp.float32)
                m = jnp.zeros((n,), jnp.float32)
                fn = lambda: jax.block_until_ready(ops.fused_local_step(  # noqa: E731
                    p, g, m, None, 1.0, 0.01, momentum=0.9,
                    interpret=True)[0])
                fn()
                row["t_host_interpret_s"] = time_best_of(fn, repeats)
            rows.append(row)
    return rows


def update_kernels_markdown(rows) -> str:
    head = ("| kernel | n_params | bytes | t_compute | t_memory | "
            "bottleneck | t_host_interpret |")
    lines = [head, "|" + "---|" * 7]
    for r in rows:
        host = r.get("t_host_interpret_s")
        lines.append(
            f"| {r['kernel']} | {r['n_params']:.0e} | {r['bytes']:.2e} | "
            f"{_fmt_s(r['t_compute_s'])} | {_fmt_s(r['t_memory_s'])} | "
            f"{r['bottleneck']} | {_fmt_s(host) if host else '-'} |")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--no-update-kernels", action="store_true",
                    help="skip the fused-update kernel roofline section")
    args = ap.parse_args(argv)
    rows = load_rows(args.mesh)
    rc = 0
    if not rows:
        print(f"[roofline] no dry-run artifacts for mesh {args.mesh} — run "
              "PYTHONPATH=src python -m repro.launch.dryrun --all first")
        rc = 1
    else:
        print(to_markdown(rows))
        n_ok = sum(1 for r in rows if r.get("ok"))
        by_bneck = {}
        for r in rows:
            if r.get("ok"):
                by_bneck[r["bottleneck"]] = by_bneck.get(r["bottleneck"], 0) + 1
        print(f"\n[roofline] {n_ok}/{len(rows)} pairs ok on {args.mesh}; "
              f"bottlenecks: {by_bneck}")
    if not args.no_update_kernels:
        print("\n### fused FL-update kernels (repro.kernels.fused_update)\n")
        print(update_kernels_markdown(
            update_kernel_rows([10 ** 5, 10 ** 6, 10 ** 7])))
        print("\n[roofline] update kernels are elementwise — memory-bound "
              "at every size; the host column is CPU interpret mode "
              "(correctness vehicle), not TPU time")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
