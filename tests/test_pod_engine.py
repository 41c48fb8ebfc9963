"""Pod-backend engine tests: one RoundStrategy stack from laptop CPU to
sharded mesh.

Covers the PR-2 contract:
  - fl_batch_pspec/fl_batch_shardings layout logic (rank<3 leaves,
    pod+data vs data-only meshes) without needing real multi-device
    meshes (the pspec helpers only read axis names/sizes);
  - host↔pod engine parity: same seed + sampling="host" produce
    identical loss histories on a 1-device mesh (relay bitwise, fedavg
    up to fp reduction order — scan-delta vs vmap-weighted-mean);
  - chunk-size invariance on the pod backend (chunk>1 = one XLA
    dispatch per chunk on the mesh);
  - scaffold/moon on the pod backend through the ShardedClientStateStore;
  - the _local_sgd ↔ fl.local clip-then-decay order parity;
  - run_pod_training driving both phases through run_phase_schedule,
    with the in-program eval stream (default accuracy + custom metric);
  - (slow) a 16-fake-device subprocess run asserting the client-state
    stack AND the server-optimizer moments actually shard over the
    mesh, with in-program eval keeping one dispatch per chunk.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_reduced
from repro.data.synthetic import make_synthetic_tokenlm
from repro.fl.engine import AggregateStrategy, RelayStrategy, RoundSchedule, run_rounds
from repro.fl.local import LocalSpec, make_local_fn
from repro.fl.pod import (
    HOST_RNG_OFFSET_P1,
    HOST_RNG_OFFSET_P2,
    PodAggregateStrategy,
    PodCyclicConfig,
    PodFLConfig,
    PodFLSpec,
    PodRelayStrategy,
    ShardedClientStateStore,
)
from repro.fl.task import lm_task
from repro.launch.mesh import make_host_mesh
from repro.sharding import rules

SEED = 0


def _mesh_stub(shape, axes):
    """Duck-typed mesh for the pure pspec helpers (axis names + sizes
    only) — lets the layout logic be tested at >1 axis sizes without
    real devices."""
    return SimpleNamespace(axis_names=axes, devices=np.empty(shape))


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("qwen1.5-0.5b")
    data = make_synthetic_tokenlm(n_clients=8, seq_len=16,
                                  n_seq_per_client=8,
                                  vocab=cfg.vocab_size, beta=0.5, seed=SEED)
    return cfg, lm_task(cfg), data


def _leaves32(tree):
    return [np.asarray(x, np.float32) for x in jax.tree_util.tree_leaves(tree)]


# ---------------------------------------------------------------------------
# fl_batch_pspec / fl_batch_shardings layout logic
# ---------------------------------------------------------------------------

def test_fl_batch_pspec_data_only_mesh():
    mesh = _mesh_stub((4, 4), ("data", "model"))
    assert rules.fl_batch_pspec(mesh, 4) == P(None, None, "data", None)
    assert rules.fl_batch_pspec(mesh, 3, batch_axis=1) == P(None, "data", None)


def test_fl_batch_pspec_pod_data_mesh():
    mesh = _mesh_stub((2, 4, 4), ("pod", "data", "model"))
    assert rules.fl_batch_pspec(mesh, 4) == P(None, None, ("pod", "data"), None)
    assert rules.fl_batch_pspec(mesh, 3, batch_axis=1) == \
        P(None, ("pod", "data"), None)


def test_fl_batch_pspec_small_rank_leaves():
    """rank <= batch_axis leaves have no batch dim to shard."""
    mesh = _mesh_stub((4, 4), ("data", "model"))
    assert rules.fl_batch_pspec(mesh, 2) == P(None, None)
    assert rules.fl_batch_pspec(mesh, 1) == P(None)
    assert rules.fl_batch_pspec(mesh, 1, batch_axis=1) == P(None)


def test_fl_batch_shardings_on_host_mesh():
    mesh = make_host_mesh()
    tree = {"tokens": jax.ShapeDtypeStruct((4, 2, 8, 16), jnp.int32),
            "weights": jax.ShapeDtypeStruct((4,), jnp.float32)}
    sh = rules.fl_batch_shardings(tree, mesh)
    assert sh["tokens"].spec == P(None, None, "data", None)
    assert sh["weights"].spec == P(None)


def test_client_axis_pspec_divisibility():
    mesh = _mesh_stub((4, 4), ("data", "model"))
    assert rules.client_axis_pspec(mesh, 3, 8) == P("data", None, None)
    assert rules.client_axis_pspec(mesh, 3, 6) == P(None, None, None)  # 6 % 4
    one = _mesh_stub((1, 1), ("data", "model"))
    assert rules.client_axis_pspec(one, 2, 8) == P(None, None)


# ---------------------------------------------------------------------------
# host ↔ pod engine parity (1-device mesh)
# ---------------------------------------------------------------------------

def _schedule(rounds, chunk, sampling, offset):
    return RoundSchedule(rounds=rounds, lr_decay=1.0, eval_every=0,
                         seed=SEED, chunk_size=chunk, sampling=sampling,
                         host_rng_offset=offset)


def test_host_pod_relay_parity(setup):
    """Same seed + sampling="host": pod relay == host relay, bit-for-bit
    (identical round bodies, the pod adds only layout pins)."""
    cfg, task, data = setup
    spec = LocalSpec(n_steps=2, batch_size=4, lr=0.05)
    host = run_rounds(task, data, RelayStrategy(spec=spec, participation=0.25),
                      _schedule(3, 2, "host", HOST_RNG_OFFSET_P1))
    pod = run_rounds(task, data,
                     PodRelayStrategy(spec=spec, mesh=make_host_mesh(),
                                      clients_per_round=2),
                     _schedule(3, 2, "host", HOST_RNG_OFFSET_P1))
    np.testing.assert_allclose([h["local_loss"] for h in host.history],
                               [h["local_loss"] for h in pod.history],
                               atol=1e-6, rtol=1e-6)
    for a, b in zip(_leaves32(host.params), _leaves32(pod.params)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("algorithm", ["fedavg", "scaffold"])
def test_host_pod_aggregate_parity(setup, algorithm):
    """Pod P2 (sequential scan + delta accumulation) matches the host
    vmap backend round-for-round: same keys, same batches, the FedAvg
    identity w_avg = w + Σ wᵢ/W·(wᵢ − w)."""
    cfg, task, data = setup
    spec = LocalSpec(n_steps=2, batch_size=4, lr=0.05, variant={
        "fedavg": "plain", "scaffold": "scaffold"}[algorithm])
    host = run_rounds(task, data,
                      AggregateStrategy(spec=spec, algorithm=algorithm,
                                        participation=0.25),
                      _schedule(3, 2, "host", HOST_RNG_OFFSET_P2))
    pod = run_rounds(task, data,
                     PodAggregateStrategy(spec=spec, algorithm=algorithm,
                                          mesh=make_host_mesh(),
                                          clients_per_round=2),
                     _schedule(3, 2, "host", HOST_RNG_OFFSET_P2))
    np.testing.assert_allclose([h["local_loss"] for h in host.history],
                               [h["local_loss"] for h in pod.history],
                               atol=1e-5, rtol=1e-5)
    for a, b in zip(_leaves32(host.params), _leaves32(pod.params)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_pod_chunked_matches_per_round(setup):
    """chunk=4 (one mesh dispatch) == chunk=1 on the pod backend."""
    cfg, task, data = setup
    mesh = make_host_mesh()
    spec = LocalSpec(n_steps=2, batch_size=4, lr=0.05)

    def run(chunk):
        return run_rounds(task, data,
                          PodRelayStrategy(spec=spec, mesh=mesh,
                                           clients_per_round=2),
                          _schedule(4, chunk, "device", 0))

    r1, r4 = run(1), run(4)
    np.testing.assert_allclose([h["local_loss"] for h in r1.history],
                               [h["local_loss"] for h in r4.history],
                               atol=1e-6, rtol=1e-6)
    for a, b in zip(_leaves32(r1.params), _leaves32(r4.params)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# sharded client state (scaffold / moon on the pod backend)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm", ["scaffold", "moon"])
def test_stateful_algorithms_run_on_pod_backend(setup, algorithm):
    cfg, task, data = setup
    spec = LocalSpec(n_steps=2, batch_size=4, lr=0.05, variant=algorithm,
                     mu=0.1)
    strat = PodAggregateStrategy(spec=spec, algorithm=algorithm,
                                 mesh=make_host_mesh(), clients_per_round=3)
    assert isinstance(strat.state_store, ShardedClientStateStore)
    res = run_rounds(task, data, strat, _schedule(2, 2, "device", 0))
    assert len(res.history) == 2
    assert all(np.isfinite(h["local_loss"]) for h in res.history)
    state_key = "c_clients" if algorithm == "scaffold" else "w_prev"
    lead = jax.tree_util.tree_leaves(res.algo_state[state_key])[0]
    assert lead.shape[0] == data.n_clients


def test_sharded_store_gather_scatter_roundtrip():
    store = ShardedClientStateStore(make_host_mesh())
    template = {"w": jnp.arange(6.0).reshape(2, 3)}
    state = store.init(template, 4)
    assert jax.tree_util.tree_leaves(state)[0].shape == (4, 2, 3)
    ids = jnp.asarray([1, 3])
    rows = store.gather(state, ids)
    rows = jax.tree_util.tree_map(lambda r: r + 1.0, rows)
    out = store.scatter(state, ids, rows)
    np.testing.assert_allclose(np.asarray(out["w"][1]),
                               np.asarray(template["w"]) + 1.0)
    np.testing.assert_allclose(np.asarray(out["w"][0]),
                               np.asarray(template["w"]))


# ---------------------------------------------------------------------------
# clip-then-decay parity (satellite: _local_sgd vs fl.local order)
# ---------------------------------------------------------------------------

def test_local_sgd_clip_decay_order_matches_fl_local(setup):
    """Feed _local_sgd the exact batches make_local_fn samples; with
    grad_clip AND weight_decay active the end params must match — only
    true if both apply clip(raw grad) THEN decay."""
    from repro.launch.train import _local_sgd

    cfg, task, data = setup
    pod_spec = PodFLSpec(local_steps=3, batch_size=4, lr=0.1,
                         weight_decay=0.1, grad_clip=0.05)
    local_spec = pod_spec.local_spec("plain")
    params = task.init(jax.random.PRNGKey(SEED))
    x_all, y_all, _ = data.device_arrays()
    cx, cy = x_all[0], y_all[0]
    key = jax.random.PRNGKey(5)

    w_host, _ = make_local_fn(task, local_spec)(
        key, params, {}, cx, cy, jnp.float32(1.0))

    # replicate fl.local's per-step sampling stream
    keys = jax.random.split(key, pod_spec.local_steps)
    bidx = jnp.stack([
        jax.random.randint(k, (pod_spec.batch_size,), 0, cx.shape[0])
        for k in keys])
    batches = {"tokens": cx[bidx], "labels": cy[bidx]}
    w_pod, _ = _local_sgd(cfg, pod_spec)(params, batches, jnp.float32(1.0),
                                         None)

    for a, b in zip(_leaves32(w_host), _leaves32(w_pod)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# run_pod_training through the declarative schedule
# ---------------------------------------------------------------------------

def test_run_pod_training_eval_rows_and_phases(setup):
    """A custom traceable metric streams through the in-program eval:
    every round carries an ``eval`` row even with chunked dispatch
    (eval_fn no longer forces eval_every=1 → per-round dispatch)."""
    from repro.launch.train import run_pod_training

    cfg, task, data = setup

    def eval_fn(params, bx, by):            # per-sample contract: (B,)
        return jnp.full((bx.shape[0],), 7.0, jnp.float32)

    res = run_pod_training(cfg, data, cyclic_rounds=1, fl_rounds=2,
                           clients_per_round=2,
                           spec=PodFLSpec(local_steps=2, batch_size=4,
                                          lr=0.05),
                           seed=SEED, eval_fn=eval_fn, chunk_size=2)
    assert [h["phase"] for h in res.history] == ["P1", "P2", "P2"]
    assert [h["round"] for h in res.history] == [0, 1, 2]
    assert all("eval" in h for h in res.history)
    assert all(abs(h["eval"] - 7.0) < 1e-6 for h in res.history)


def test_run_pod_training_default_eval_cadence(setup):
    """eval_every without a custom metric scores test accuracy on the
    cadence (plus the final round), computed inside the chunk."""
    from repro.launch.train import run_pod_training

    cfg, task, data = setup
    res = run_pod_training(cfg, data, cyclic_rounds=0, fl_rounds=3,
                           clients_per_round=2,
                           spec=PodFLSpec(local_steps=2, batch_size=4,
                                          lr=0.05),
                           seed=SEED, eval_every=2, chunk_size=3)
    assert [("eval" in h) for h in res.history] == [False, True, True]
    assert all(0.0 <= h["eval"] <= 1.0 for h in res.history if "eval" in h)


def test_run_pod_training_zero_rounds_returns_init(setup):
    from repro.launch.train import run_pod_training
    from repro.models.transformer import init_lm

    cfg, task, data = setup
    res = run_pod_training(cfg, data, cyclic_rounds=0, fl_rounds=0,
                           seed=SEED)
    assert res.history == []
    want = init_lm(jax.random.PRNGKey(SEED), cfg)
    for a, b in zip(_leaves32(res.params), _leaves32(want)):
        np.testing.assert_array_equal(a, b)


def test_pod_phase_params_survive_next_phase_donation(setup):
    """device_put is a no-op on an already-matching placement, so phase
    2's place_params must COPY phase 1's result before the donated
    carries delete it — earlier phases' params stay readable."""
    from repro.core.pipeline import Phase, run_phase_schedule

    cfg, task, data = setup
    mesh = make_host_mesh()
    spec = PodFLSpec(local_steps=2, batch_size=4, lr=0.05)
    kw = dict(mesh=mesh, rounds=1, clients_per_round=2, spec=spec,
              seed=SEED, chunk_size=1)
    sched = run_phase_schedule(task, data, [
        Phase("P1", PodCyclicConfig(**kw)),
        Phase("P2", PodFLConfig(**kw)),
    ])
    for leaf in jax.tree_util.tree_leaves(sched.phases[0].result.params):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()


def test_pod_phase_schedule_alternation(setup):
    """Multi-cycle P1↔P2 on the POD backend — what run_phase_schedule
    unlocks for the sharded path."""
    from repro.core.pipeline import Phase, run_phase_schedule

    cfg, task, data = setup
    mesh = make_host_mesh()
    spec = PodFLSpec(local_steps=2, batch_size=4, lr=0.05)
    kw = dict(mesh=mesh, rounds=1, clients_per_round=2, spec=spec,
              seed=SEED, chunk_size=2)
    sched = run_phase_schedule(task, data, [
        Phase("P1", PodCyclicConfig(**kw)),
        Phase("P2", PodFLConfig(**kw)),
        Phase("P1'", PodCyclicConfig(**kw)),
        Phase("P2'", PodFLConfig(**kw)),
    ])
    hist = sched.history
    assert [h["phase"] for h in hist] == ["P1", "P2", "P1'", "P2'"]
    assert [h["round"] for h in hist] == [0, 1, 2, 3]
    led = sched.ledger.summary()
    assert led["p1_rounds"] == 2 and led["p2_rounds"] == 2


# ---------------------------------------------------------------------------
# multi-device: client state really shards over the data axis
# ---------------------------------------------------------------------------

_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax
    import numpy as np
    from repro.configs import get_reduced
    from repro.data.synthetic import make_synthetic_tokenlm
    from repro.fl.engine import RoundSchedule, run_rounds
    from repro.fl.local import LocalSpec
    from repro.fl.pod import PodAggregateStrategy
    from repro.fl.task import lm_task

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 4), ("data", "model"))
    cfg = get_reduced("qwen1.5-0.5b")
    task = lm_task(cfg)
    data = make_synthetic_tokenlm(n_clients=8, seq_len=16,
                                  n_seq_per_client=8,
                                  vocab=cfg.vocab_size, beta=0.5, seed=0)
    strat = PodAggregateStrategy(
        spec=LocalSpec(n_steps=2, batch_size=8, lr=0.05, variant="scaffold"),
        algorithm="scaffold", mesh=mesh, clients_per_round=2,
        server_opt="momentum", server_lr=0.5)
    res = run_rounds(task, data, strat,
                     RoundSchedule(rounds=2, eval_every=2, eval_batch=8,
                                   seed=0, chunk_size=2))
    assert np.isfinite(res.history[-1]["local_loss"])
    assert 0.0 <= res.history[-1]["acc"] <= 1.0   # in-program eval on mesh
    assert res.dispatches == 1                    # eval did not split chunks
    leaf = jax.tree_util.tree_leaves(res.algo_state["c_clients"])[0]
    spec = leaf.sharding.spec
    assert spec and spec[0] == "data", ("c_clients not data-sharded", spec)
    # server-optimizer moments shard like the params they mirror
    mom = jax.tree_util.tree_leaves(res.server_state.inner)
    assert mom and any(
        any(ax is not None for ax in m.sharding.spec) for m in mom
        if m.ndim >= 2), "server momentum not sharded"
    print("POD_SUBPROCESS_OK")
""")


@pytest.mark.slow
def test_pod_scaffold_shards_client_state_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "POD_SUBPROCESS_OK" in out.stdout


# ---------------------------------------------------------------------------
# multi-device: hierarchical two-level combine on a real 4×4 mesh
# ---------------------------------------------------------------------------

_HIER_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax
    import numpy as np
    from repro.configs import get_reduced
    from repro.data.synthetic import make_synthetic_tokenlm
    from repro.fl.engine import RoundSchedule, run_rounds
    from repro.fl.local import LocalSpec
    from repro.fl.pod import PodAggregateStrategy, ShardedSparseClientStateStore
    from repro.fl.task import lm_task

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 4), ("data", "model"))
    cfg = get_reduced("qwen1.5-0.5b")
    task = lm_task(cfg)
    data = make_synthetic_tokenlm(n_clients=8, seq_len=16,
                                  n_seq_per_client=8,
                                  vocab=cfg.vocab_size, beta=0.5, seed=0)
    sched = RoundSchedule(rounds=2, eval_every=0, seed=0, chunk_size=2,
                          sampling="host", host_rng_offset=17)

    def run(aggregation, store=None):
        kw = {"state_store": store} if store is not None else {}
        strat = PodAggregateStrategy(
            spec=LocalSpec(n_steps=2, batch_size=8, lr=0.05,
                           variant="scaffold"),
            algorithm="scaffold", mesh=mesh, clients_per_round=4,
            aggregation=aggregation, **kw)
        return run_rounds(task, data, strat, sched)

    seq = run("sequential")
    hier = run("hierarchical",                       # G=4 from the data axis
               ShardedSparseClientStateStore(capacity=8, mesh=mesh))
    # two-level combine only reassociates the weighted sum
    for a, b in zip(jax.tree_util.tree_leaves(seq.params),
                    jax.tree_util.tree_leaves(hier.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=0)
    np.testing.assert_allclose(
        [h["local_loss"] for h in seq.history],
        [h["local_loss"] for h in hier.history], atol=5e-5, rtol=0)
    # sparse table is data-sharded at its bounded capacity, not n_clients
    table = jax.tree_util.tree_leaves(hier.algo_state["c_clients"]["table"])[0]
    assert table.shape[0] == 8, table.shape
    spec = table.sharding.spec
    assert spec and spec[0] == "data", ("sparse table not data-sharded", spec)
    print("POD_HIER_SUBPROCESS_OK")
""")


@pytest.mark.slow
def test_pod_hierarchical_combine_16dev_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _HIER_SUBPROCESS_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "POD_HIER_SUBPROCESS_OK" in out.stdout


# ---------------------------------------------------------------------------
# multi-device: the sharded-lane hierarchical combine IS one psum over
# `data` — asserted on the lowered HLO, plus fused end-to-end parity
# ---------------------------------------------------------------------------

_PSUM_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import re
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data.federated import FederatedDataset
    from repro.fl.engine import RoundSchedule, run_rounds
    from repro.fl.local import LocalSpec
    from repro.fl.pod import (PodAggregateStrategy,
                              ShardedSparseClientStateStore,
                              _sharded_flat_ops)
    from repro.fl.task import vision_task
    from repro.sharding import rules

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 4), ("data", "model"))
    task = vision_task("mlp", in_ch=1, seed_kwargs={"img": 8, "d_hidden": 16})
    fops = _sharded_flat_ops(task, mesh, "fsdp_tp", True)
    G = fops.lane_count()
    assert G == 4, G

    # -- HLO: the cross-pod combine lowers to EXACTLY ONE all-reduce
    # whose replica groups are the mesh `data` columns — no host gather,
    # no all-gather/all-to-all
    rng = np.random.default_rng(0)
    acc = fops.lane_zeros(G)
    acc = fops.lane_accum(
        acc,
        {k: jnp.asarray(rng.normal(size=(G,) + v.shape[1:], scale=0.1)
                        .astype(np.float32)) for k, v in acc.items()},
        jnp.asarray(rng.random(G).astype(np.float32)))
    hlo = jax.jit(fops.lane_combine).lower(acc).compile().as_text()
    n_ar = len(re.findall(r"all-reduce(?:-start)?\\(", hlo))
    assert n_ar == 1, f"expected exactly one psum, found {n_ar}"
    assert "all-gather" not in hlo and "all-to-all" not in hlo, hlo[-2000:]
    # the data axis strides the (4, 4) device grid by 4: columns
    want = "{{0,4,8,12},{1,5,9,13},{2,6,10,14},{3,7,11,15}}"
    m = re.search(r"replica_groups=(\\{\\{[0-9,{}]*\\}\\})", hlo)
    assert m and m.group(1) == want, (m and m.group(1), want)

    # -- numerics: combine(accum(...)) == the plain weighted sum
    comb = fops.lane_combine(acc)
    for k, v in comb.items():
        assert v.shape == fops.lane_zeros(G)[k].shape[1:]

    # -- end-to-end: fused hierarchical (sharded lanes + psum) matches
    # the sequential scan, sparse store refills landing per shard
    N, per = 8, 16
    x = rng.normal(size=(N, per, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(N, per)).astype(np.int32)
    data = FederatedDataset(x=x, y=y, n_real=np.full((N,), per, np.int32),
                            test_x=x[0], test_y=y[0], n_classes=10,
                            name="psum-test")
    spec = LocalSpec(n_steps=2, batch_size=4, lr=0.05, variant="scaffold",
                     update_impl="fused_interpret")

    def run(aggregation, overlap):
        strat = PodAggregateStrategy(
            spec=spec, algorithm="scaffold", mesh=mesh, clients_per_round=4,
            aggregation=aggregation, n_pods=4,
            state_store=ShardedSparseClientStateStore(capacity=8, mesh=mesh))
        return run_rounds(task, data, strat,
                          RoundSchedule(rounds=4, lr_decay=1.0, eval_every=0,
                                        seed=0, chunk_size=2, sampling="host",
                                        host_rng_offset=17, overlap=overlap))

    seq = run("sequential", False)
    hier = run("hierarchical", False)
    hier_ovl = run("hierarchical", True)
    np.testing.assert_allclose(
        [h["local_loss"] for h in seq.history],
        [h["local_loss"] for h in hier.history], atol=5e-5, rtol=0)
    for a, b in zip(jax.tree_util.tree_leaves(seq.params),
                    jax.tree_util.tree_leaves(hier.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=0)
    # overlapped pipeline == synchronous, BITWISE, on the pod
    np.testing.assert_array_equal(
        [h["local_loss"] for h in hier.history],
        [h["local_loss"] for h in hier_ovl.history])
    for a, b in zip(jax.tree_util.tree_leaves(hier.params),
                    jax.tree_util.tree_leaves(hier_ovl.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert hier_ovl.dispatches == hier.dispatches == 2
    print("POD_PSUM_SUBPROCESS_OK")
""")


@pytest.mark.slow
def test_pod_hierarchical_psum_lowering_16dev_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _PSUM_SUBPROCESS_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "POD_PSUM_SUBPROCESS_OK" in out.stdout
