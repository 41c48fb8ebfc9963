"""The round engine's observability: host spans on the profiler's clock,
compile counters charged to the span they happen in, and the named
phases of the compiled round program.

  - a traced ``run_rounds`` writes every ``engine.*`` span into the
    profiler's host plane, inside the call, with the round index as a
    stat; each ``EngineResult.timing`` key is its spans' summed time;
  - a retrace planted in the unpack lands in
    ``counters["traces.engine.unpack"]``;
  - the round program's compiled HLO carries every ``fl_*`` phase in
    its ``op_name`` metadata: host backend here, pod backend on four
    virtual devices in a subprocess.
"""
import dataclasses
import glob
import os
import re
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.data.federated import FederatedDataset
from repro.fl import engine
from repro.fl.engine import (AggregateStrategy, RoundSchedule,
                             SparseClientStateStore, run_rounds)
from repro.fl.local import LocalSpec
from repro.fl.task import vision_task
from repro.utils.spans import span

PHASES = ("fl_fwd_bwd", "fl_unflatten", "fl_step_tail", "fl_aggregate",
          "fl_server_update", "fl_eval")
# span -> the EngineResult.timing key it feeds
TIMED = {"engine.pack": "pack_ms", "engine.prepare_data": "prepare_data_ms",
         "engine.stage": "host_residency_ms",
         "engine.dispatch": "dispatch_enqueue_ms",
         "engine.drain": "device_wait_ms", "engine.unpack": "unpack_ms",
         "store.transfer": "staged_transfer_ms"}


@pytest.fixture(scope="module")
def setup():
    task = vision_task("mlp", in_ch=1, seed_kwargs={"img": 8, "d_hidden": 16})
    rng = np.random.default_rng(0)
    n, per = 8, 16
    x = rng.normal(size=(n, per, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(n, per)).astype(np.int32)
    data = FederatedDataset(x=x, y=y, n_real=np.full((n,), per, np.int32),
                            test_x=x[0], test_y=y[0], n_classes=10,
                            name="spans-test")
    return task, data


def _strategy(store=None):
    spec = LocalSpec(n_steps=2, batch_size=4, lr=0.05, variant="scaffold",
                     update_impl="fused_interpret")
    return AggregateStrategy(spec=spec, algorithm="scaffold",
                             participation=0.25, server_opt="momentum",
                             server_lr=0.5,
                             state_store=store or SparseClientStateStore(
                                 capacity=4))


def _sched(**kw):
    return RoundSchedule(rounds=kw.pop("rounds", 6), lr_decay=1.0,
                         eval_every=2, eval_batch=8, seed=0, chunk_size=2,
                         sampling="host", host_rng_offset=17, **kw)


def _host_events(trace_dir):
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.duration_ns,
                         dict(e.stats) if "." in e.name else {})
                        for e in line.events]
    return out


def _traced(task, data, strat, tmp_path):
    run_rounds(task, data, strat, _sched(overlap=True))     # compile
    jax.profiler.start_trace(str(tmp_path))
    with span("test.call"):
        res = run_rounds(task, data, strat, _sched(overlap=True))
    engine._spill_pool().submit(lambda: None).result()     # spills landed
    jax.profiler.stop_trace()
    return res, _host_events(str(tmp_path))


def test_traced_run_writes_every_engine_span(setup, tmp_path):
    task, data = setup
    # capacity for every client: residency faults rows in and evicts
    # none, so no spill worker competes with the engine's thread
    res, events = _traced(task, data,
                          _strategy(SparseClientStateStore(capacity=8)),
                          tmp_path)
    call = [e for e in events if e[0] == "test.call"]
    assert len(call) == 1
    c0, c1 = call[0][1], call[0][1] + call[0][2]
    spans = [e for e in events if e[0].startswith("engine.")]
    assert {e[0] for e in spans} == {
        "engine.pack", "engine.prepare_data", "engine.plan", "engine.stage",
        "engine.dispatch", "engine.drain", "engine.history", "engine.unpack"}
    assert all(c0 <= s and s + d <= c1 for _, s, d, _ in spans)
    # one dispatch's spans share its first round as a stat
    rounds = sorted(st["round"] for n, _, _, st in spans
                    if n == "engine.dispatch")
    assert rounds == [0, 2, 4] and res.dispatches == 3
    for name, key in TIMED.items():
        mine = [d for n, _, d, _ in events if n == name]
        assert mine, name
        # each span encloses the two clock reads that feed the key; it
        # is longer only by the profiler's own cost, microseconds a span
        got = sum(mine) * 1e-6
        assert got >= res.timing[key] - 1e-3 * len(mine), key
        assert res.timing[key] == pytest.approx(got, rel=0.05,
                                                abs=0.02 * len(mine)), key
    assert set(res.timing) == {
        "host_residency_ms", "staged_transfer_ms", "dispatch_enqueue_ms",
        "device_wait_ms", "spill_materialize_ms", "pack_ms",
        "prepare_data_ms", "unpack_ms"}


def test_spill_spans_run_on_the_worker(setup, tmp_path):
    task, data = setup
    store = SparseClientStateStore(capacity=4)      # evicts: spills rows
    res, events = _traced(task, data, _strategy(store), tmp_path)
    spill = [d for n, _, d, _ in events if n == "store.spill"]
    # the worker's spans enclose the ms its store sums; the engine's
    # thread may hold the interpreter inside them, so only this bound
    assert spill and store.spill_materialize_ms > 0
    assert sum(spill) * 1e-6 >= store.spill_materialize_ms - 1e-3 * len(
        spill)
    assert 0 < res.timing["spill_materialize_ms"] <= \
        store.spill_materialize_ms


def test_untraced_timing_keys_come_from_the_spans(setup):
    task, data = setup
    res = run_rounds(task, data, _strategy(), _sched())
    for key in ("pack_ms", "prepare_data_ms", "unpack_ms",
                "dispatch_enqueue_ms", "device_wait_ms", "host_residency_ms"):
        assert res.timing[key] > 0.0, (key, res.timing)
    # round_wall_s: one row per round, each dispatch split evenly
    assert len(res.round_wall_s) == 6
    assert res.round_wall_s[0] == res.round_wall_s[1] > 0


def test_planted_retrace_is_charged_to_unpack(setup, monkeypatch):
    task, data = setup
    strat = _strategy()
    run_rounds(task, data, strat, _sched(rounds=2))          # compile
    again = run_rounds(task, data, strat, _sched(rounds=2))
    base = again.counters.get("traces.engine.unpack", 0)

    unpack = engine.unpack_server_state

    def retraced(fops, state):
        # a fresh jit on every call: one trace per unpack
        return jax.jit(lambda s: s)(unpack(fops, state))

    monkeypatch.setattr(engine, "unpack_server_state", retraced)
    res = run_rounds(task, data, strat, _sched(rounds=2))
    assert res.counters["traces.engine.unpack"] == base + 1
    assert res.counters.get("traces.engine.dispatch", 0) == \
        again.counters.get("traces.engine.dispatch", 0)


def test_spans_charge_the_innermost_span_with_counts():
    x = np.float32(1.0)
    jax.jit(lambda v: v * 2)(x)                 # one-time work, unspanned
    timing, counts = {}, {}
    with span("engine.outer", timing, "outer_ms", counts):
        with span("store.inner", timing, "inner_ms"):
            jax.jit(lambda v: v * 3)(x)         # charged to engine.outer
        with span("engine.inner", counts=counts):
            jax.jit(lambda v: v * 5)(x)
    assert counts["traces.engine.outer"] == counts["traces.engine.inner"] > 0
    assert not any("store.inner" in k for k in counts)
    assert timing["outer_ms"] >= timing["inner_ms"] > 0


def _capture(strategy):
    """``strategy`` whose round program keeps itself and the shapes of
    its first call, so that its compiled HLO can be read."""
    cls, got = type(strategy), {}

    def jit_chunk(self, chunk, task, n_clients):
        fn = cls.jit_chunk(self, chunk, task, n_clients)

        def watched(*args):
            if "args" not in got:
                got["fn"], got["args"] = fn, jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=a.sharding),
                    args)
            return fn(*args)
        return watched

    sub = type(f"Captured{cls.__name__}", (cls,), {"jit_chunk": jit_chunk})
    return sub(**{f.name: getattr(strategy, f.name)
                  for f in dataclasses.fields(strategy)}), got


def _op_names(got):
    hlo = got["fn"].lower(*got["args"]).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', hlo)


def test_host_round_program_names_every_phase(setup):
    task, data = setup
    engine._cached_chunk_fn.cache_clear()
    strat, got = _capture(_strategy())
    run_rounds(task, data, strat, _sched(rounds=2))
    names = _op_names(got)
    for phase in PHASES:
        assert any(phase in n for n in names), phase
    # the backward's ops keep the phase they transpose
    assert any("transpose(jvp(fl_unflatten))" in n for n in names)
    assert any(re.search(r"fl_fwd_bwd/transpose\(", n) for n in names)


_POD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import re, sys
    import numpy as np
    sys.path.insert(0, os.path.dirname(os.environ["SPANS_TEST"]))
    from test_spans import PHASES, _capture, _op_names
    from repro.data.federated import FederatedDataset
    from repro.fl.engine import RoundSchedule, run_rounds
    from repro.fl.local import LocalSpec
    from repro.fl.pod import PodAggregateStrategy
    from repro.fl.task import vision_task
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2), ("data", "model"))
    task = vision_task("mlp", in_ch=1,
                       seed_kwargs={"img": 8, "d_hidden": 16})
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 16, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(8, 16)).astype(np.int32)
    data = FederatedDataset(x=x, y=y, n_real=np.full((8,), 16, np.int32),
                            test_x=x[0], test_y=y[0], n_classes=10,
                            name="pod-spans")
    spec = LocalSpec(n_steps=2, batch_size=4, lr=0.05,
                     update_impl="fused_interpret")
    for aggregation in ("sequential", "hierarchical"):
        strat, got = _capture(PodAggregateStrategy(
            spec=spec, mesh=mesh, clients_per_round=4,
            aggregation=aggregation, n_pods=2, server_opt="momentum",
            server_lr=0.5))
        res = run_rounds(task, data, strat, RoundSchedule(
            rounds=2, eval_every=1, eval_batch=8, seed=0, chunk_size=2))
        names = _op_names(got)
        missing = [p for p in PHASES if not any(p in n for n in names)]
        assert not missing, (aggregation, missing)
        # the mesh unflatten's custom transpose reads as fl_unflatten
        assert any(re.search(r"transpose\\(fl_fwd_bwd\\)/.*fl_unflatten/",
                             n) for n in names), aggregation
        assert res.counters["traces.engine.unpack"] > 0
    print("POD_SPANS_OK")
""")


def test_pod_round_program_names_every_phase_on_4_devices():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src
    env["SPANS_TEST"] = os.path.abspath(__file__)
    out = subprocess.run([sys.executable, "-c", _POD_SCRIPT],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "POD_SPANS_OK" in out.stdout


def test_train_cli_trace_dir_writes_the_spans(monkeypatch, tmp_path):
    from repro.launch import train
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    args = ["--arch", "qwen1.5-0.5b", "--rounds", "1",
            "--cyclic-rounds", "1", "--clients", "4",
            "--clients-per-round", "2", "--local-steps", "1", "--batch", "2",
            "--seq", "16", "--chunk-size", "1",
            "--trace-dir", str(tmp_path / "trace")]
    assert train.main(args) in (0, 1)
    names = {e[0] for e in _host_events(str(tmp_path / "trace"))}
    # both phases (P1 relay, P2 aggregate) ran through the engine
    assert {"engine.pack", "engine.dispatch", "engine.drain",
            "engine.unpack"} <= names
