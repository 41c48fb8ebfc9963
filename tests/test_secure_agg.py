"""Secure-aggregation simulation (repro.fl.privacy pairwise masks).

The mechanism: clients i < j share the per-round pair key
``fold_in(fold_in(fold_in(rk, MASK_TAG), i), j)``; both draw the same
``z`` and add ``+z`` (lower id) / ``−z`` (higher id) to their weighted
uploads.  Antisymmetry ``m_ij = −m_ji`` is BITWISE (shared key + sign
convention); the per-client masks therefore telescope to zero over a
full participant set up to float reassociation, and a masked round is
numerically the unmasked round on host, pod, and the hierarchical
psum-lowered combine (16-fake-device subprocess).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.federated import FederatedDataset
from repro.fl import privacy
from repro.fl.engine import RoundSchedule, run_rounds
from repro.fl.local import FlatParamOps, LocalSpec
from repro.fl.pod import PodAggregateStrategy
from repro.fl.simulation import FLConfig, run_federated
from repro.fl.task import vision_task
from repro.launch.mesh import make_host_mesh
from repro.utils.flatten import FlatView

SEED = 0


# ---------------------------------------------------------------------------
# the mask algebra itself
# ---------------------------------------------------------------------------

def test_pair_key_and_sign_antisymmetry_bitwise():
    mk = privacy.mask_base_key(jax.random.PRNGKey(3))
    kij = privacy.pair_mask_key(mk, jnp.int32(2), jnp.int32(7))
    kji = privacy.pair_mask_key(mk, jnp.int32(7), jnp.int32(2))
    np.testing.assert_array_equal(np.asarray(kij), np.asarray(kji))
    assert float(privacy.pair_sign(2, 7)) == 1.0
    assert float(privacy.pair_sign(7, 2)) == -1.0
    assert float(privacy.pair_sign(5, 5)) == 0.0
    # distinct pairs draw from distinct keys
    other = privacy.pair_mask_key(mk, jnp.int32(2), jnp.int32(6))
    assert (np.asarray(kij) != np.asarray(other)).any()


def _tree():
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    return {"w": jax.random.normal(ks[0], (17, 33)),
            "b": jax.random.normal(ks[1], (65,))}


def test_full_participation_masks_sum_to_zero_tree_and_flat():
    tree = _tree()
    mk = privacy.mask_base_key(jax.random.PRNGKey(4))
    ids = jnp.asarray([9, 2, 5, 0, 7])

    def tree_zeros():
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), tree)

    masks = [privacy.client_mask(mk, cid, ids,
                                 lambda k: privacy.tree_normal(k, tree),
                                 tree_zeros)
             for cid in np.asarray(ids)]
    total = jax.tree_util.tree_map(lambda *ms: sum(ms), *masks)
    for leaf, src in zip(jax.tree_util.tree_leaves(total),
                         jax.tree_util.tree_leaves(tree)):
        # each pair contributes +z and −z; only reassociation survives
        np.testing.assert_allclose(np.asarray(leaf),
                                   np.zeros(src.shape, np.float32),
                                   atol=1e-5)
    # a 2-client set cancels BITWISE: m_i = +z, m_j = −z exactly
    pair = jnp.asarray([3, 8])
    mi = privacy.client_mask(mk, pair[0], pair,
                             lambda k: privacy.tree_normal(k, tree),
                             tree_zeros)
    mj = privacy.client_mask(mk, pair[1], pair,
                             lambda k: privacy.tree_normal(k, tree),
                             tree_zeros)
    for a, b in zip(jax.tree_util.tree_leaves(mi),
                    jax.tree_util.tree_leaves(mj)):
        np.testing.assert_array_equal(np.asarray(a), -np.asarray(b))

    # flat buffers draw the same bits per parameter (single draws are
    # bitwise twins; the scan-accumulated mask is compared at ulp level
    # because XLA fuses the draw pipeline into the scan body differently
    # per representation — fma contraction in erfinv)
    view = FlatView.of(tree)
    fops = FlatParamOps(view=view, interpret=True)
    k01 = privacy.pair_mask_key(mk, ids[0], ids[1])
    np.testing.assert_array_equal(
        np.asarray(fops.normal(k01)["float32"]),
        np.asarray(fops.pad(view.flatten(privacy.tree_normal(k01, tree)))
                   ["float32"]))
    flat = privacy.client_mask(mk, ids[0], ids, fops.normal,
                               lambda: fops.zeros(jnp.float32))
    packed = fops.pad(view.flatten(
        jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), masks[0])))
    for name in flat:
        np.testing.assert_allclose(np.asarray(flat[name]),
                                   np.asarray(packed[name]),
                                   atol=2e-6, rtol=2e-6)


def test_masked_aggregate_equals_unmasked_host():
    # direct aggregate-level check: masks change nothing but fp order
    tree = _tree()
    K = 4
    w_locals = jax.tree_util.tree_map(
        lambda p: p[None] + 0.1 * jax.random.normal(
            jax.random.PRNGKey(21), (K,) + p.shape), tree)
    weights = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    ids = jnp.asarray([6, 1, 4, 2])
    rk = jax.random.PRNGKey(5)
    base = privacy.tree_dp_aggregate(None, False, rk, ids, tree,
                                     w_locals, weights)
    masked = privacy.tree_dp_aggregate(None, True, rk, ids, tree,
                                       w_locals, weights)
    for a, b in zip(jax.tree_util.tree_leaves(base),
                    jax.tree_util.tree_leaves(masked)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)

    view = FlatView.of(tree)
    fops = FlatParamOps(view=view, interpret=True)
    fused = fops.unflatten(privacy.fused_dp_aggregate(
        None, True, fops, rk, ids, fops.flatten(tree),
        view.flatten_stacked(w_locals), weights))
    for a, b in zip(jax.tree_util.tree_leaves(masked),
                    jax.tree_util.tree_leaves(fused)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# ---------------------------------------------------------------------------
# engine runs: masked == unmasked on host and pod
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def vision_setup():
    rng = np.random.default_rng(SEED)
    N, per = 8, 16
    x = rng.normal(size=(N, per, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(N, per)).astype(np.int32)
    data = FederatedDataset(x=x, y=y, n_real=np.full((N,), per, np.int32),
                            test_x=x[0], test_y=y[0], n_classes=10,
                            name="secure-agg-test")
    task = vision_task("mlp", in_ch=1, seed_kwargs={"img": 8, "d_hidden": 16})
    return task, data


@pytest.mark.parametrize("update_impl", ["tree", "fused_interpret"])
def test_masked_run_matches_unmasked_host(vision_setup, update_impl):
    task, data = vision_setup

    def run(**kw):
        return run_federated(task, data, FLConfig(
            rounds=3, chunk_size=3, participation=0.5, local_steps=2,
            batch_size=8, lr=0.05, eval_every=0, seed=SEED,
            update_impl=update_impl, **kw))

    base, masked = run(), run(secure_agg=True)
    for a, b in zip(jax.tree_util.tree_leaves(base.params),
                    jax.tree_util.tree_leaves(masked.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(
        [h["local_loss"] for h in base.history],
        [h["local_loss"] for h in masked.history], atol=1e-4, rtol=1e-4)


def test_masked_run_matches_unmasked_pod(vision_setup):
    task, data = vision_setup
    mesh = make_host_mesh()

    def run(secure_agg):
        strat = PodAggregateStrategy(
            spec=LocalSpec(n_steps=2, batch_size=8, lr=0.05,
                           update_impl="fused_interpret",
                           secure_agg=secure_agg),
            algorithm="fedavg", mesh=mesh, clients_per_round=4)
        return run_rounds(task, data, strat,
                          RoundSchedule(rounds=3, eval_every=0, seed=SEED,
                                        chunk_size=3, sampling="host",
                                        host_rng_offset=17))

    base, masked = run(False), run(True)
    for a, b in zip(jax.tree_util.tree_leaves(base.params),
                    jax.tree_util.tree_leaves(masked.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-4, rtol=2e-4)


def test_secure_agg_bytes_in_ledger(vision_setup):
    from repro.core.comm_accounting import CommLedger, secure_agg_mask_bytes
    task, data = vision_setup
    ledger = CommLedger()
    run_federated(task, data, FLConfig(
        rounds=2, chunk_size=2, participation=0.5, local_steps=2,
        batch_size=8, lr=0.05, eval_every=0, seed=SEED, secure_agg=True),
        ledger=ledger)
    led = ledger.summary()
    k = max(1, int(round(0.5 * 8)))
    assert led["mask_bytes"] == 2 * secure_agg_mask_bytes(k)
    assert led["total_bytes"] == led["p2_bytes"] + led["mask_bytes"]


# ---------------------------------------------------------------------------
# multi-device: masked == unmasked under the hierarchical psum combine
# ---------------------------------------------------------------------------

_MASK_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data.federated import FederatedDataset
    from repro.fl.engine import RoundSchedule, run_rounds
    from repro.fl.local import LocalSpec
    from repro.fl.pod import PodAggregateStrategy
    from repro.fl.privacy import DPSpec
    from repro.fl.task import vision_task

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 4), ("data", "model"))
    task = vision_task("mlp", in_ch=1, seed_kwargs={"img": 8, "d_hidden": 16})
    rng = np.random.default_rng(0)
    N, per = 8, 16
    x = rng.normal(size=(N, per, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(N, per)).astype(np.int32)
    data = FederatedDataset(x=x, y=y, n_real=np.full((N,), per, np.int32),
                            test_x=x[0], test_y=y[0], n_classes=10,
                            name="mask-psum-test")
    sched = RoundSchedule(rounds=4, lr_decay=1.0, eval_every=0, seed=0,
                          chunk_size=2, sampling="host", host_rng_offset=17)

    def run(aggregation, **spec_kw):
        strat = PodAggregateStrategy(
            spec=LocalSpec(n_steps=2, batch_size=4, lr=0.05,
                           update_impl="fused_interpret", **spec_kw),
            algorithm="fedavg", mesh=mesh, clients_per_round=4,
            aggregation=aggregation, n_pods=4)
        return run_rounds(task, data, strat, sched)

    # the sharded-lane psum path engages (G == |data| == 4, fused):
    # masked == unmasked under the hierarchical combine
    base = run("hierarchical")
    masked = run("hierarchical", secure_agg=True)
    for a, b in zip(jax.tree_util.tree_leaves(base.params),
                    jax.tree_util.tree_leaves(masked.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-4, rtol=5e-4)

    # DP clipping on the psum path carries the coefficient sum next to
    # the p-free partials: hierarchical == sequential for ONE round
    # (tight — identical noise bits, only reduction order differs)
    sched1 = RoundSchedule(rounds=1, eval_every=0, seed=0, chunk_size=1,
                           sampling="host", host_rng_offset=17)

    def run1(aggregation, **spec_kw):
        strat = PodAggregateStrategy(
            spec=LocalSpec(n_steps=2, batch_size=4, lr=0.05,
                           update_impl="fused_interpret", **spec_kw),
            algorithm="fedavg", mesh=mesh, clients_per_round=4,
            aggregation=aggregation, n_pods=4)
        return run_rounds(task, data, strat, sched1)

    kw = dict(dp=DPSpec(0.5, 0.3), secure_agg=True)
    seqp = run1("sequential", **kw)
    hierp = run1("hierarchical", **kw)
    for a, b in zip(jax.tree_util.tree_leaves(seqp.params),
                    jax.tree_util.tree_leaves(hierp.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-6, rtol=5e-6)

    # identity spec stays bitwise on the psum path too
    ident = run("hierarchical", dp=DPSpec(float("inf"), 0.0))
    for a, b in zip(jax.tree_util.tree_leaves(base.params),
                    jax.tree_util.tree_leaves(ident.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("SECURE_AGG_PSUM_OK")
""")


@pytest.mark.slow
def test_secure_agg_hierarchical_psum_16dev_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _MASK_SUBPROCESS_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SECURE_AGG_PSUM_OK" in out.stdout
