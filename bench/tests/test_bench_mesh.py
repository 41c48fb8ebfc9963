"""The harness builds a cell's round loop on the cell's own chips: the
``qwen05b-f32.p2-hier-2x2`` cell's traffic on four chips is two pods on
a 2x2 ``data x model`` mesh whose first dispatch agrees with the plain
FedAvg reference in float32, and a one-chip traffic keeps the library's
one-device mesh.  By the cell's own limits, its sound run is correct,
and the control and each fault the cell can have are not (``faults.py``
and ``controls.py``): the reference one precision down, with half of
every batch, or with a stale key in the program's place, and a run
whose cross-pod combine leaves out the exchange between the pods.  A
step that returns its state unchanged or doubled reads 1 on
``update1`` by the measure itself.  JAX fixes its device count when it
starts, so the four-chip part runs in a child process on four virtual
CPU devices, at a smoke-width decoder LM."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import pytest

from bench import compare, run

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAME = "qwen05b-f32.p2-hier-2x2"
# planted in the program: a round that returns its params unchanged, half
# of every batch left out, and the cross-pod combine without its psum
FAULTS = ("unchanged", "half_batch", "drop_exchange")

_SCRIPT = textwrap.dedent("""
    import dataclasses, json, os, sys, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, pytest
    from bench import run
    from bench.controls import readings_of
    from bench.tests.faults import TINY_LM, plant
    from repro.fl import engine

    cell, FAULTS = run.load_cell(sys.argv[1]), sys.argv[2:]
    t = cell.traffic
    t = dict(t, data=dict(t["data"], clients=8, sequences_per_client=8,
                          seq_len=16),
             fl=dict(t["fl"], local_steps=2))
    cell = dataclasses.replace(cell, traffic=t,
                               config=dict(cell.config, **TINY_LM))
    devs = jax.devices()[:4]
    strategy, _ = run.build_round_loop(t, devs)
    read = readings_of(run.Harness(cell, devs), 2147483923, controls=True)
    # faults through the rest of a run, round programs traced anew
    run.MAX_WARMUP = 1
    read["planted"] = {}
    for fault in FAULTS:
        engine._cached_chunk_fn.cache_clear()
        with pytest.MonkeyPatch.context() as mp:
            plant(fault, mp)
            out = run.run_cell(cell, seed=2147483923, seconds=0.2,
                               trace=False, t_start=time.perf_counter(),
                               require_chip=False)
        read["planted"][fault] = {k: out[k] for k in ("correct", "checks")}
    print("MESH " + json.dumps(dict(
        read, mesh=dict(strategy.mesh.shape), pods=strategy._n_pods())))
""")


@pytest.fixture(scope="module")
def four_chips():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    p = subprocess.run([sys.executable, "-c", _SCRIPT, NAME, *FAULTS],
                       cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = next(x for x in p.stdout.splitlines() if x.startswith("MESH "))
    return json.loads(line[5:])


def test_four_chips_are_two_pods_of_two(four_chips):
    assert four_chips["mesh"] == {"data": 2, "model": 2}
    assert four_chips["pods"] == 2


def test_first_dispatch_matches_the_reference_in_float32(four_chips):
    # float32 on the CPU: the program and the reference differ only in
    # the order of their sums (the tensor-parallel contractions, the
    # lanes' sum of w_i before p is taken off); a pod left out or
    # counted twice moves update1 by a third or more
    sound = four_chips["program"]
    assert sound["loss1"] <= 1e-5, sound
    assert sound["update1"] <= 1e-4, sound


def test_sound_run_is_correct_by_the_cells_limits(four_chips):
    ok, checks = compare.judge(four_chips["program"],
                               run.load_cell(NAME).limits)
    assert ok, checks


@pytest.mark.parametrize("fault", ["control", "half_batch", "stale_key"])
def test_control_and_reference_faults_are_not_correct(four_chips, fault):
    ok, checks = compare.judge(four_chips[fault], run.load_cell(NAME).limits)
    assert not ok, checks


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(four_chips, fault):
    out = four_chips["planted"][fault]
    assert not out["correct"], out["checks"]


def test_one_chip_keeps_the_one_device_mesh():
    from repro.launch.mesh import make_host_mesh
    t = run.load_cell("qwen05b.p2-fedavg").traffic
    strategy, _ = run.build_round_loop(t, jax.devices()[:1])
    host = make_host_mesh()
    assert strategy.mesh == host
    assert dict(host.shape) == {"data": 1, "model": 1}
    assert strategy.aggregation == "sequential"


@pytest.mark.parametrize("traffic,chips,error", [
    ("p2-fedavg-paper", 4, "host backend runs on one chip"),
    ("p2-fedavg-lm", 4, "does not hold"),
    ("p2-hier-lm", 1, "does not hold"),
])
def test_a_mesh_the_cell_cannot_hold_is_refused(traffic, chips, error):
    t = json.loads((ROOT / f"bench/traffic/{traffic}.json").read_text())
    devs = [jax.devices()[0]] * chips
    with pytest.raises(ValueError, match=error):
        run.build_round_loop(t, devs)
