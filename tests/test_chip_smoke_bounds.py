"""The bounds of ``chip_smoke.py``'s two comparisons, checked against
planted faults on the CPU.

A chip run reports only whether a comparison passed, so each bound
must sit between what a sound program reads and what a faulty one
does.  Each test runs the comparison as ``chip_smoke.py`` does (at
reduced widths where it needs a mesh) in a fresh process — once sound,
once per planted fault — and asserts the sound reading passes and every
fault fails.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


def _readings(code: str, n_devices: int = 1) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{n_devices}")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


# phase sharded's comparison at reduced qwen widths on a 2x2 host mesh;
# the faults scale each pod lane's FedAvg coefficients (keeping their
# sum) — one pod's clients counted twice, or one pod dropped
SHARDED = r"""
import dataclasses, json, sys
sys.path.insert(0, ".")
import jax, jax.numpy as jnp
import chip_smoke as cs
from repro.fl import engine, pod
from repro.launch import train
from repro.launch.mesh import make_device_mesh, make_host_mesh
from repro.models.transformer import init_lm

args = train.build_parser().parse_args(
    [a for a in cs.LM_ARGV if a != "--full-config"])
args.seq = 64
cfg = dataclasses.replace(train.make_config(args), dtype=jnp.float32,
                          param_dtype=jnp.float32)
data, kw = train.make_run(args, cfg)
p0 = init_lm(jax.random.PRNGKey(args.seed), cfg)
ref = cs.sharded_run("ref", cfg, data, kw, make_host_mesh(),
                     aggregation="sequential")
mesh = make_device_mesh(model=2)
lane_accum = pod.ShardedFlatOps.lane_accum
out = {}
for name, scale in (("sound", None), ("doubled_pod", (4 / 3, 2 / 3)),
                    ("dropped_pod", (2.0, 0.0))):
    if scale is not None:
        f = jnp.asarray(scale, jnp.float32)
        pod.ShardedFlatOps.lane_accum = (
            lambda self, acc, w, c, f=f: lane_accum(self, acc, w, c * f))
    engine._cached_chunk_fn.cache_clear()
    run = cs.sharded_run(name, cfg, data, kw, mesh,
                         aggregation="hierarchical", n_pods=2)
    out[name] = cs.sharded_errors(run, ref, p0)
print(json.dumps(out))
"""


def test_sharded_bounds_catch_a_doubled_or_dropped_pod():
    out = _readings(SHARDED, n_devices=4)
    loss, update = out.pop("sound")
    assert loss <= chip_smoke.SHARDED_LOSS_RTOL, out
    assert update <= chip_smoke.SHARDED_UPDATE_RTOL, out
    for name, (loss, update) in out.items():
        assert update > chip_smoke.SHARDED_UPDATE_RTOL, (name, update)
        assert loss > chip_smoke.SHARDED_LOSS_RTOL, (name, loss)


# phase paper's comparison as the chip runs it; the fault aggregates
# with uniform FedAvg weights in place of the clients' data shares
PAPER = r"""
import json, sys
sys.path.insert(0, ".")
import jax.numpy as jnp
import chip_smoke as cs
from repro.fl import engine
from repro.kernels import ops

out = {"sound": cs.paper_update_err(0)}
weighted_delta = ops.fused_weighted_delta
ops.fused_weighted_delta = lambda st, p, w, *a, **k: weighted_delta(
    st, p, jnp.full_like(w, 1.0 / w.shape[0]), *a, **k)
engine._cached_chunk_fn.cache_clear()
out["uniform_weights"] = cs.paper_update_err(0)
print(json.dumps(out))
"""


def test_paper_bound_catches_unweighted_aggregation():
    out = _readings(PAPER)
    assert out["sound"] <= chip_smoke.PAPER_TOL, out
    assert out["uniform_weights"] > chip_smoke.PAPER_TOL, out
