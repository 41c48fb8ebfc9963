"""Flat-first fused update path vs the tree parity oracle.

``update_impl="fused_interpret"`` routes the client step tail, the
FedAvg aggregation and the server optimizers through the FlatView +
Pallas kernels (repro.kernels.fused_update, interpret mode on this
CPU container); ``"tree"`` is the per-leaf tree_math oracle.  These
tests pin numerical parity at three levels:

  - the step tail alone (fused_step_tail vs tree_step_tail, all term
    combinations incl. clip / correction / decay / momentum);
  - the flat-grad local contract (value_and_grad w.r.t. the buffers
    emits packed gradients identical to packing the tree gradients);
  - full host-engine runs for all four variants and both server
    optimizers (flat chunk carries + flat OptState);
  - full pod-backend runs (sequential fused delta accumulation +
    fused server moments over ShardedFlatOps);
  - (slow) a 16-fake-device subprocess run pinning fused == tree under
    a REAL sharded FSDP×TP layout, with the carry buckets actually
    sharded over their mesh-axis groups.

Adam comparisons carry the looser tolerance documented in
tests/test_eval_stream.py: its sign-like normalization amplifies fp
reduction-order differences on near-zero pseudo-gradient elements.
"""
import dataclasses as dc
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic import DATASETS, make_synthetic_tokenlm
from repro.fl import privacy
from repro.fl.engine import RoundSchedule, run_rounds
from repro.fl.local import (
    FlatParamOps,
    LocalSpec,
    fused_step_tail,
    tree_step_tail,
)
from repro.fl.simulation import HOST_RNG_OFFSET_P2, FLConfig, run_federated
from repro.fl.task import lm_task, vision_task
from repro.utils.flatten import FlatView

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                         # pragma: no cover
    HAVE_HYPOTHESIS = False

SEED = 0


def _leaves32(tree):
    return [np.asarray(x, np.float32) for x in jax.tree_util.tree_leaves(tree)]


def _assert_tree_close(a, b, tol):
    for x, y in zip(_leaves32(a), _leaves32(b)):
        np.testing.assert_allclose(x, y, atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# step tail: fused kernel vs tree oracle
# ---------------------------------------------------------------------------

def _random_tree(key, scale=1.0):
    ks = jax.random.split(key, 3)
    return {"w": jax.random.normal(ks[0], (17, 33)) * scale,
            "b": jax.random.normal(ks[1], (33,)) * scale,
            "head": {"w": jax.random.normal(ks[2], (33, 5)) * scale}}


@pytest.mark.parametrize("grad_clip,momentum,weight_decay,with_c", [
    (None, 0.0, 0.0, False),            # bare axpy
    (0.5, 0.0, 0.0, False),             # clip only
    (None, 0.9, 0.0, False),            # momentum only
    (None, 0.0, 1e-2, False),           # decay only
    (0.5, 0.9, 1e-2, True),             # everything + scaffold correction
])
def test_step_tail_matches_tree(grad_clip, momentum, weight_decay, with_c):
    spec = LocalSpec(n_steps=1, batch_size=1, lr=0.05, momentum=momentum,
                     weight_decay=weight_decay, grad_clip=grad_clip,
                     update_impl="fused_interpret")
    params = _random_tree(jax.random.PRNGKey(0))
    grads = _random_tree(jax.random.PRNGKey(1), scale=3.0)
    mom = _random_tree(jax.random.PRNGKey(2)) if momentum else ()
    c = _random_tree(jax.random.PRNGKey(3), scale=0.1) if with_c else None
    lr_scale = jnp.float32(0.7)

    want_p, want_m = tree_step_tail(spec, params, grads, mom, c, lr_scale)

    view = FlatView.of(params)
    fops = FlatParamOps(view=view, interpret=True)
    m_bufs = view.flatten(mom) if momentum else {}
    got_p, got_m = fused_step_tail(
        spec, fops, view.flatten(params), view.flatten(grads), m_bufs,
        view.flatten(c) if c is not None else None, lr_scale)
    _assert_tree_close(view.unflatten(got_p), want_p, 1e-6)
    if momentum:
        _assert_tree_close(view.unflatten(got_m), want_m, 1e-6)


# ---------------------------------------------------------------------------
# host engine: all four variants + both server optimizers
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def vision_setup():
    data = DATASETS.get("fashion-like")(n_clients=8, beta=0.5, seed=SEED,
                                        n_train=256, n_test=64)
    task = vision_task("mlp", n_classes=10, in_ch=data.x.shape[-1])
    return task, data


def _fl(**kw):
    kw.setdefault("rounds", 2)
    kw.setdefault("chunk_size", 2)
    return FLConfig(participation=0.25, local_steps=2, batch_size=8,
                    eval_every=0, seed=SEED, **kw)


@pytest.mark.parametrize("algorithm", ["fedavg", "fedprox", "scaffold",
                                       "moon"])
def test_host_variant_parity(vision_setup, algorithm):
    task, data = vision_setup
    cfg = _fl(algorithm=algorithm, momentum=0.9, weight_decay=1e-4,
              grad_clip=1.0)
    tree = run_federated(task, data, cfg)
    fused = run_federated(task, data,
                          dc.replace(cfg, update_impl="fused_interpret"))
    np.testing.assert_allclose([h["local_loss"] for h in tree.history],
                               [h["local_loss"] for h in fused.history],
                               atol=1e-5, rtol=1e-5)
    _assert_tree_close(tree.params, fused.params, 2e-5)


@pytest.mark.parametrize("server_opt,server_lr,tol",
                         [("momentum", 0.5, 2e-5), ("adam", 0.02, 1e-2)])
def test_host_server_opt_parity(vision_setup, server_opt, server_lr, tol):
    task, data = vision_setup
    cfg = _fl(algorithm="fedavg", rounds=3, server_opt=server_opt,
              server_lr=server_lr)
    tree = run_federated(task, data, cfg)
    fused = run_federated(task, data,
                          dc.replace(cfg, update_impl="fused_interpret"))
    _assert_tree_close(tree.params, fused.params, tol)


def test_relay_parity(vision_setup):
    from repro.core.cyclic import CyclicConfig, cyclic_pretrain
    task, data = vision_setup
    cfg = CyclicConfig(rounds=2, participation=0.25, local_steps=2,
                       batch_size=8, momentum=0.9, grad_clip=1.0,
                       eval_every=0, seed=SEED, chunk_size=2)
    tree = cyclic_pretrain(task, data, cfg)
    fused = cyclic_pretrain(task, data,
                            dc.replace(cfg, update_impl="fused_interpret"))
    np.testing.assert_allclose([h["local_loss"] for h in tree.history],
                               [h["local_loss"] for h in fused.history],
                               atol=1e-5, rtol=1e-5)
    _assert_tree_close(tree.params, fused.params, 2e-5)


@pytest.mark.parametrize("case", sorted(
    __import__("repro.kernels.parity", fromlist=["CASES"]).CASES))
def test_kernel_matches_its_oracle(case):
    """Every fused kernel (interpret mode) against its jnp oracle in
    repro.kernels.ref, on an unaligned length that exercises the
    wrappers' pad/strip — the same cases chip_smoke.py runs with Mosaic
    at qwen1.5-0.5b width."""
    from repro.kernels import parity
    worst = parity.CASES[case](10_000, jax.random.PRNGKey(3), True)
    assert worst <= 1.0, f"{case}: {worst} x tolerance"


def test_kernel_oracle_check_catches_a_wrong_kernel():
    """The parity score is not vacuous: a kernel run with the wrong
    momentum scores far outside tolerance."""
    from repro.kernels import ops, parity, ref
    n = 4096
    p, g, m = parity._operands(jax.random.PRNGKey(0), n, jnp.bfloat16,
                               jnp.bfloat16, jnp.float32)
    got = ops.fused_local_step(p, g, m, None, 1.0, 0.1, momentum=0.8,
                               interpret=True)
    worst = parity._score(got, lambda *a: ref.local_step_ref(
        *a, momentum=0.9), p, g, m, None, 1.0, 0.1)
    assert worst > 10.0


@pytest.mark.parametrize("n", [1, 1000, 1024, 65_536, 65_537, 463_987_712])
def test_padded_len_tiles_both_grids(n):
    """A carried buffer of padded_len(n) needs no pad on the one-block
    interpret grid nor on the 512-row compiled grid, and the pad stays
    below one compiled block."""
    from repro.kernels.fused_update import (
        DEFAULT_BLOCK_ROWS, LANES, _grid_rows, padded_len)
    m = padded_len(n)
    assert n <= m < n + DEFAULT_BLOCK_ROWS * LANES
    for interpret in (True, False):
        rows_p, _ = _grid_rows(m, DEFAULT_BLOCK_ROWS, interpret)
        assert rows_p * LANES == m


def test_bad_update_impl_rejected():
    with pytest.raises(ValueError, match="update_impl"):
        LocalSpec(n_steps=1, batch_size=1, lr=0.1, update_impl="magic")


@pytest.mark.parametrize("make_config", [
    lambda: FLConfig(update_impl="fusde"),
    lambda: __import__("repro.core.cyclic", fromlist=["CyclicConfig"])
    .CyclicConfig(update_impl="magic"),
    lambda: __import__("repro.fl.pod", fromlist=["PodFLSpec"])
    .PodFLSpec(update_impl="Fused"),
])
def test_bad_update_impl_rejected_at_config_time(make_config):
    """A typo'd update_impl fails at CONFIG construction with the
    allowed values spelled out — not deep inside the engine."""
    with pytest.raises(ValueError, match=r"tree.*fused.*fused_interpret"):
        make_config()


def test_flat_place_never_aliases_the_callers_arrays():
    """flatten is a NO-OP for a bucket holding exactly one 1-D leaf
    (concatenate of one array returns the operand) — place() must copy
    such passthroughs, or the engine's donated carries would delete the
    caller's params (the P1→P2 handoff regression class)."""
    tree = {"v": jnp.arange(5, dtype=jnp.float32)}
    view = FlatView.of(tree)
    fops = FlatParamOps(view=view, interpret=True)
    bufs = view.flatten(tree)
    assert bufs["float32"] is tree["v"]          # the hazard is real
    placed = fops.place(bufs)
    assert placed["float32"] is not tree["v"]    # place de-aliases

    # pod flavor: (1, N)-shaped unsharded leaves pass straight through
    # the shard transform AND device_put on matching placement
    from jax.sharding import PartitionSpec as P
    from repro.fl.pod import ShardedFlatOps
    from repro.launch.mesh import make_host_mesh
    from repro.utils.flatten import ShardedFlatView

    mesh = make_host_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tree2 = {"row": jnp.arange(6, dtype=jnp.float32).reshape(1, 6)}
    sview = ShardedFlatView.of(tree2, {"row": P()}, sizes)
    sops = ShardedFlatOps(view=sview, interpret=True, mesh=mesh)
    placed2 = sops.place(sview.flatten(tree2))
    assert placed2["float32"] is not tree2["row"]


def test_flat_local_emits_packed_gradients(vision_setup):
    """The flat-grad contract: the fused local fn takes/returns flat
    buffers, and the params it trains match the tree local bit-for-bit
    tolerance — i.e. d(loss∘unflatten)/d(bufs) == flatten(dloss/dtree)."""
    from repro.fl.local import host_flat_ops, make_local_fn

    task, data = vision_setup
    spec = LocalSpec(n_steps=3, batch_size=8, lr=0.05, momentum=0.9,
                     weight_decay=1e-4, grad_clip=1.0)
    params = task.init(jax.random.PRNGKey(SEED))
    x_all, y_all, _ = data.device_arrays()
    key = jax.random.PRNGKey(7)

    w_tree, aux_tree = make_local_fn(task, spec)(
        key, params, {}, x_all[1], y_all[1], jnp.float32(1.0))

    fspec = dc.replace(spec, update_impl="fused_interpret")
    fops = host_flat_ops(task, True)
    p_end, aux_flat = make_local_fn(task, fspec)(
        key, fops.flatten(params), {}, x_all[1], y_all[1], jnp.float32(1.0))
    assert set(p_end) == set(fops.flatten(params))     # flat in, flat out
    np.testing.assert_allclose(float(aux_tree["loss"]),
                               float(aux_flat["loss"]), atol=1e-5, rtol=1e-5)
    _assert_tree_close(fops.unflatten(p_end), w_tree, 2e-5)


# ---------------------------------------------------------------------------
# pod backend: fused sequential delta accumulation + server moments
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm_setup():
    from repro.configs import get_reduced
    cfg = get_reduced("qwen1.5-0.5b")
    data = make_synthetic_tokenlm(n_clients=8, seq_len=16, n_seq_per_client=8,
                                  vocab=cfg.vocab_size, beta=0.5, seed=SEED)
    return lm_task(cfg), data


def _pod_sched(rounds=2, chunk=2):
    return RoundSchedule(rounds=rounds, lr_decay=1.0, eval_every=0, seed=SEED,
                         chunk_size=chunk, sampling="host",
                         host_rng_offset=HOST_RNG_OFFSET_P2)


@pytest.mark.parametrize("algorithm,server_opt,server_lr,tol", [
    ("fedavg", "none", 1.0, 2e-5),
    ("scaffold", "none", 1.0, 2e-5),
    ("fedavg", "momentum", 0.5, 2e-5),
    ("fedavg", "adam", 0.02, 1e-2),
])
def test_pod_fused_matches_tree(lm_setup, algorithm, server_opt, server_lr,
                                tol):
    from repro.fl.local import UPDATE_IMPLS  # noqa: F401 (doc pointer)
    from repro.fl.pod import PodAggregateStrategy
    from repro.launch.mesh import make_host_mesh

    task, data = lm_setup
    mesh = make_host_mesh()
    spec = LocalSpec(n_steps=2, batch_size=4, lr=0.01, momentum=0.9,
                     variant="scaffold" if algorithm == "scaffold"
                     else "plain")
    mk = lambda s: PodAggregateStrategy(         # noqa: E731
        spec=s, algorithm=algorithm, mesh=mesh, clients_per_round=2,
        server_opt=server_opt, server_lr=server_lr)
    tree = run_rounds(task, data, mk(spec), _pod_sched())
    fused = run_rounds(task, data,
                       mk(dc.replace(spec, update_impl="fused_interpret")),
                       _pod_sched())
    np.testing.assert_allclose([h["local_loss"] for h in tree.history],
                               [h["local_loss"] for h in fused.history],
                               atol=1e-5, rtol=1e-5)
    _assert_tree_close(tree.params, fused.params, tol)


# ---------------------------------------------------------------------------
# multi-device: fused == tree under a REAL sharded FSDP×TP layout
# ---------------------------------------------------------------------------

_SHARDED_PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import dataclasses as dc
    import jax
    import numpy as np
    from repro.configs import get_reduced
    from repro.data.synthetic import make_synthetic_tokenlm
    from repro.fl.engine import RoundSchedule, run_rounds
    from repro.fl.local import LocalSpec
    from repro.fl.pod import PodAggregateStrategy, PodRelayStrategy
    from repro.fl.simulation import HOST_RNG_OFFSET_P2
    from repro.fl.task import lm_task

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 4), ("data", "model"))
    cfg = get_reduced("qwen1.5-0.5b")
    task = lm_task(cfg)
    data = make_synthetic_tokenlm(n_clients=8, seq_len=16,
                                  n_seq_per_client=8,
                                  vocab=cfg.vocab_size, beta=0.5, seed=0)
    sched = lambda: RoundSchedule(rounds=2, lr_decay=1.0, eval_every=2,
                                  eval_batch=8, seed=0, chunk_size=2,
                                  sampling="host",
                                  host_rng_offset=HOST_RNG_OFFSET_P2)
    spec = LocalSpec(n_steps=2, batch_size=8, lr=0.01, momentum=0.9)
    mk = lambda s: PodAggregateStrategy(
        spec=s, algorithm="fedavg", mesh=mesh, clients_per_round=2,
        server_opt="adam", server_lr=0.02)
    fspec = dc.replace(spec, update_impl="fused_interpret")

    # the carry buckets really shard over their mesh-axis groups
    fops = mk(fspec).flat_ops(task)
    sh = fops.shardings()
    sharded = [n for n, s in sh.items() if any(ax is not None
                                               for ax in s.spec)]
    assert sharded, ("no sharded bucket", {n: s.spec for n, s in sh.items()})
    bufs = fops.place(fops.flatten(task.init(jax.random.PRNGKey(0))))
    for name in sharded:
        spec0 = bufs[name].sharding.spec
        assert spec0 and spec0[0] is not None, (name, spec0)

    tree = run_rounds(task, data, mk(spec), sched())
    fused = run_rounds(task, data, mk(fspec), sched())
    np.testing.assert_allclose([h["local_loss"] for h in tree.history],
                               [h["local_loss"] for h in fused.history],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(tree.history[-1]["acc"],
                               fused.history[-1]["acc"], atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(tree.params),
                    jax.tree_util.tree_leaves(fused.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-2, rtol=1e-2)

    # P1 relay parity on the same mesh (flat relay carry)
    mkr = lambda s: PodRelayStrategy(spec=s, mesh=mesh, clients_per_round=2)
    rsched = lambda: RoundSchedule(rounds=2, lr_decay=1.0, eval_every=0,
                                   seed=0, chunk_size=2, sampling="host",
                                   host_rng_offset=31)
    rt = run_rounds(task, data, mkr(spec), rsched())
    rf = run_rounds(task, data, mkr(fspec), rsched())
    np.testing.assert_allclose([h["local_loss"] for h in rt.history],
                               [h["local_loss"] for h in rf.history],
                               atol=1e-5, rtol=1e-5)
    print("FUSED_SHARDED_PARITY_OK")
""")


@pytest.mark.slow
def test_pod_fused_sharded_layout_parity_subprocess():
    """fused == tree on the pod backend under a 4×4 FSDP×TP mesh: the
    flat-first carries shard per mesh-axis bucket (no more fused/sharded
    mutual exclusion) and both aggregate + relay rounds agree with the
    tree oracle, in-program eval included."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SHARDED_PARITY_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "FUSED_SHARDED_PARITY_OK" in out.stdout


# ---------------------------------------------------------------------------
# hypothesis sweep: fused DP aggregation == tree DP aggregation
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    def _dp_case(seed, n_leaves, k):
        """Deterministic (params, w_locals, weights) from a drawn seed."""
        rng = np.random.default_rng(seed)
        shapes = [tuple(rng.integers(1, 7, size=rng.integers(1, 3)))
                  for _ in range(n_leaves)]
        params = {f"p{i}": jnp.asarray(rng.normal(size=s), jnp.float32)
                  for i, s in enumerate(shapes)}
        w_locals = {f"p{i}": jnp.asarray(
            rng.normal(size=(k,) + s, scale=rng.uniform(0.01, 3.0)),
            jnp.float32) for i, s in enumerate(shapes)}
        weights = jnp.asarray(rng.uniform(0.5, 4.0, size=k), jnp.float32)
        ids = jnp.asarray(rng.choice(32, size=k, replace=False), jnp.int32)
        return params, w_locals, weights, ids

    @given(seed=st.integers(0, 2 ** 30),
           n_leaves=st.integers(1, 4),
           k=st.integers(1, 6),
           clip=st.one_of(st.none(),
                          st.floats(0.05, 20.0, allow_nan=False)),
           sigma=st.sampled_from([0.0, 0.05, 0.7]),
           secure_agg=st.booleans())
    @settings(max_examples=30, deadline=None)
    def test_fused_dp_aggregate_matches_tree_sweep(
            seed, n_leaves, k, clip, sigma, secure_agg):
        """For random (clip, sigma, K, shapes, secure-agg flag) the fused
        single-pass DP aggregate and the tree oracle agree: same clip
        scales, same noise/mask bits (per-leaf keyed draws), one kernel
        pass vs tree_map arithmetic."""
        if clip is None and sigma > 0.0:
            sigma = 0.0         # DPSpec: noise requires a finite clip
        dp = None if clip is None else privacy.DPSpec(clip, sigma)
        params, w_locals, weights, ids = _dp_case(seed, n_leaves, k)
        rk = jax.random.PRNGKey(seed % 997)

        ref = privacy.tree_dp_aggregate(dp, secure_agg, rk, ids, params,
                                        w_locals, weights)
        view = FlatView.of(params)
        fops = FlatParamOps(view=view, interpret=True)
        got = fops.unflatten(privacy.fused_dp_aggregate(
            dp, secure_agg, fops, rk, ids, fops.flatten(params),
            view.flatten_stacked(w_locals), weights))
        for a, b in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, rtol=3e-5)
