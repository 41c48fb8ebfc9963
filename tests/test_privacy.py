"""DP-FedAvg privacy primitives (repro.fl.privacy).

Three property families, each pinned on the tree oracle AND the fused
flat path, host and pod:

  - clipping bounds every client's aggregated contribution by C
    (scale = min(1, C/‖δ‖) folded into the aggregation coefficients);
  - the identity spec ``DPSpec(clip=inf, sigma=0)`` is BITWISE the
    baseline program on the fused path — the privacy switches are
    static, so turning DP "on but neutral" changes nothing;
  - aggregated noise has the calibrated variance σ²C²/K (zero-delta
    aggregate isolates the noise term; fixed seed, the bound is ~13
    standard errors wide so the test cannot flake).

Cross-backend (host vmap vs pod scan) DP runs match tightly for one
round — identical threefry noise bits by construction — and only
loosely after several (noise-perturbed trajectories amplify fp
reassociation chaotically), so the parity assertions here are
single-round.
"""
import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.federated import FederatedDataset
from repro.fl import privacy
from repro.fl.engine import AggregateStrategy, RoundSchedule, run_rounds
from repro.fl.local import FlatParamOps, LocalSpec
from repro.fl.pod import PodAggregateStrategy
from repro.fl.privacy import DPSpec
from repro.fl.simulation import FLConfig, run_federated
from repro.fl.task import vision_task
from repro.launch.mesh import make_host_mesh
from repro.utils.flatten import FlatView

SEED = 0


# ---------------------------------------------------------------------------
# spec validation + static switches
# ---------------------------------------------------------------------------

def test_dpspec_validation():
    assert DPSpec(1.0, 0.1).clips and DPSpec(1.0, 0.1).noised
    ident = DPSpec(float("inf"), 0.0)
    assert not ident.clips and not ident.noised
    with pytest.raises(ValueError):
        DPSpec(0.0)                     # clip must be positive
    with pytest.raises(ValueError):
        DPSpec(-1.0)
    with pytest.raises(ValueError):
        DPSpec(1.0, -0.5)               # sigma must be >= 0
    with pytest.raises(ValueError):
        DPSpec(float("inf"), 0.5)       # noise needs a finite bound


def test_relay_rejects_privacy():
    from repro.fl.engine import RelayStrategy
    with pytest.raises(ValueError):
        RelayStrategy(spec=LocalSpec(n_steps=1, batch_size=1, lr=0.1,
                                     dp=DPSpec(1.0)))
    with pytest.raises(ValueError):
        RelayStrategy(spec=LocalSpec(n_steps=1, batch_size=1, lr=0.1,
                                     secure_agg=True))


# ---------------------------------------------------------------------------
# leaf-keyed draws: tree oracle == FlatView buffers bit-for-bit
# ---------------------------------------------------------------------------

def _mixed_tree(key):
    ks = jax.random.split(key, 3)
    return {"w": jax.random.normal(ks[0], (9, 33)),
            "b": jax.random.normal(ks[1], (33,), jnp.float32),
            "head": {"w": jax.random.normal(ks[2], (33, 5))},
            "step": jnp.int32(3)}


def test_tree_normal_matches_flat_normal():
    tree = _mixed_tree(jax.random.PRNGKey(1))
    key = jax.random.PRNGKey(7)
    want = privacy.tree_normal(key, tree)
    view = FlatView.of(tree)
    bufs = view.normal(key)
    leaves = jax.tree_util.tree_leaves(want)
    for slot, leaf in zip(view.slots, leaves):
        got = bufs[slot.buffer][slot.offset:slot.offset + slot.size]
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(leaf).reshape(-1))
    # non-inexact source leaves draw zeros, so they perturb nothing
    int_draws = [draw for src, draw in
                 zip(jax.tree_util.tree_leaves(tree), leaves)
                 if not np.issubdtype(np.asarray(src).dtype, np.inexact)]
    assert int_draws and not np.asarray(int_draws[0]).any()


# ---------------------------------------------------------------------------
# clipping bounds the per-client contribution
# ---------------------------------------------------------------------------

def test_clip_bounds_every_client_tree_and_fused():
    clip = 0.5
    dp = DPSpec(clip)
    key = jax.random.PRNGKey(2)
    params = _mixed_tree(key)
    K = 3
    # client deltas of very different magnitudes: tiny (unclipped),
    # moderate, huge (heavily clipped)
    w_locals = jax.tree_util.tree_map(
        lambda p: jnp.stack([p + s * jax.random.normal(
            jax.random.fold_in(key, int(s * 100)), p.shape, jnp.float32)
            .astype(p.dtype) if jnp.issubdtype(p.dtype, jnp.inexact) else p
            for s in (0.01, 1.0, 30.0)]), params)
    weights = jnp.asarray([1.0, 2.0, 1.0])
    ids = jnp.arange(K)
    rk = jax.random.PRNGKey(3)

    scales = privacy.stacked_clip_scales(
        dp, jax.tree_util.tree_leaves(params),
        jax.tree_util.tree_leaves(w_locals))
    norms = np.sqrt(np.asarray(sum(
        jnp.sum((wl.astype(jnp.float32) - p.astype(jnp.float32)[None]) ** 2,
                axis=tuple(range(1, wl.ndim)))
        for p, wl in zip(jax.tree_util.tree_leaves(params),
                         jax.tree_util.tree_leaves(w_locals)))))
    # every clipped norm obeys the bound; the tiny client is untouched
    clipped = norms * np.asarray(scales)
    assert (clipped <= clip * (1 + 1e-5)).all(), (norms, clipped)
    assert np.isclose(scales[0], 1.0), scales
    assert scales[2] < 0.1

    # the aggregates implement exactly Σ w̄ᵢ·scaleᵢ·δᵢ
    got_tree = privacy.tree_dp_aggregate(dp, False, rk, ids, params,
                                         w_locals, weights)
    wbar = np.asarray(weights / jnp.sum(weights), np.float32)
    for p, wl, g in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(w_locals),
                        jax.tree_util.tree_leaves(got_tree)):
        p32 = np.asarray(p, np.float32)
        d = np.tensordot(wbar * np.asarray(scales),
                         np.asarray(wl, np.float32) - p32[None], axes=1)
        np.testing.assert_allclose(np.asarray(g, np.float32), p32 + d,
                                   atol=1e-5, rtol=1e-5)

    view = FlatView.of(params)
    fops = FlatParamOps(view=view, interpret=True)
    got_fused = fops.unflatten(privacy.fused_dp_aggregate(
        dp, False, fops, rk, ids, fops.flatten(params),
        view.flatten_stacked(w_locals), weights))
    for a, b in zip(jax.tree_util.tree_leaves(got_tree),
                    jax.tree_util.tree_leaves(got_fused)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-5, rtol=1e-5)


def test_dp_clip_noise_kernel_matches_reference():
    # the standalone one-pass upload kernel: clip_scale·d (+ ns·z)
    tree = _mixed_tree(jax.random.PRNGKey(4))
    view = FlatView.of(tree)
    fops = FlatParamOps(view=view, interpret=True)
    d = fops.pad(view.normal(jax.random.PRNGKey(5)))
    z = fops.normal(jax.random.PRNGKey(6))
    out = fops.dp_clip_noise(d, z, jnp.float32(0.25), jnp.float32(0.1))
    for name, o in out.items():
        want = 0.25 * np.asarray(d[name]) + 0.1 * np.asarray(z[name])
        np.testing.assert_allclose(np.asarray(o), want, atol=1e-6, rtol=1e-6)
    out_nz = fops.dp_clip_noise(d, None, jnp.float32(0.25), jnp.float32(0.0))
    for name, o in out_nz.items():
        np.testing.assert_allclose(np.asarray(o), 0.25 * np.asarray(d[name]),
                                   atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# engine runs: identity spec bitwise, DP-on host/pod parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def vision_setup():
    rng = np.random.default_rng(SEED)
    N, per = 8, 16
    x = rng.normal(size=(N, per, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(N, per)).astype(np.int32)
    data = FederatedDataset(x=x, y=y, n_real=np.full((N,), per, np.int32),
                            test_x=x[0], test_y=y[0], n_classes=10,
                            name="privacy-test")
    task = vision_task("mlp", in_ch=1, seed_kwargs={"img": 8, "d_hidden": 16})
    return task, data


def _host_cfg(**kw):
    kw.setdefault("update_impl", "fused_interpret")
    return FLConfig(rounds=2, chunk_size=2, participation=0.5, local_steps=2,
                    batch_size=8, lr=0.05, eval_every=0, seed=SEED, **kw)


def test_identity_dpspec_bitwise_host_fused(vision_setup):
    task, data = vision_setup
    base = run_federated(task, data, _host_cfg())
    ident = run_federated(task, data,
                          _host_cfg(dp=DPSpec(float("inf"), 0.0)))
    for a, b in zip(jax.tree_util.tree_leaves(base.params),
                    jax.tree_util.tree_leaves(ident.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        [h["local_loss"] for h in base.history],
        [h["local_loss"] for h in ident.history])


def _pod_run(task, data, mesh, rounds, **spec_kw):
    spec_kw.setdefault("update_impl", "fused_interpret")
    strat = PodAggregateStrategy(
        spec=LocalSpec(n_steps=2, batch_size=8, lr=0.05, **spec_kw),
        algorithm="fedavg", mesh=mesh, clients_per_round=4)
    return run_rounds(task, data, strat,
                      RoundSchedule(rounds=rounds, eval_every=0, seed=SEED,
                                    chunk_size=rounds, sampling="host",
                                    host_rng_offset=17))


def _host_run(task, data, rounds, **spec_kw):
    spec_kw.setdefault("update_impl", "fused_interpret")
    strat = AggregateStrategy(
        spec=LocalSpec(n_steps=2, batch_size=8, lr=0.05, **spec_kw),
        algorithm="fedavg", participation=0.5)
    return run_rounds(task, data, strat,
                      RoundSchedule(rounds=rounds, eval_every=0, seed=SEED,
                                    chunk_size=rounds, sampling="host",
                                    host_rng_offset=17))


def test_identity_dpspec_bitwise_pod_fused(vision_setup):
    task, data = vision_setup
    mesh = make_host_mesh()
    base = _pod_run(task, data, mesh, 2)
    ident = _pod_run(task, data, mesh, 2, dp=DPSpec(float("inf"), 0.0))
    for a, b in zip(jax.tree_util.tree_leaves(base.params),
                    jax.tree_util.tree_leaves(ident.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("spec_kw", [
    {"dp": DPSpec(0.5, 0.3)},
    {"dp": DPSpec(0.5, 0.0)},           # clip only
    {"dp": DPSpec(0.5, 0.3), "secure_agg": True},
])
def test_dp_round_host_pod_parity(vision_setup, spec_kw):
    # one round: host vmap aggregate and pod scan draw IDENTICAL noise
    # bits from the same round key, so they match to reduction-order fp
    task, data = vision_setup
    mesh = make_host_mesh()
    host = _host_run(task, data, 1, **spec_kw)
    pod = _pod_run(task, data, mesh, 1, **spec_kw)
    for a, b in zip(jax.tree_util.tree_leaves(host.params),
                    jax.tree_util.tree_leaves(pod.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-6, rtol=2e-6)


def test_dp_clip_changes_params_noise_reproducible(vision_setup):
    task, data = vision_setup
    cfg = _host_cfg(dp=DPSpec(0.5, 0.3))
    a = run_federated(task, data, cfg)
    b = run_federated(task, data, cfg)
    base = run_federated(task, data, _host_cfg())
    # same seed -> identical noisy run; noise -> differs from baseline
    for x, y in zip(jax.tree_util.tree_leaves(a.params),
                    jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    diffs = [np.abs(np.asarray(x, np.float32) - np.asarray(y, np.float32)).max()
             for x, y in zip(jax.tree_util.tree_leaves(a.params),
                             jax.tree_util.tree_leaves(base.params))]
    assert max(diffs) > 1e-3, diffs


# ---------------------------------------------------------------------------
# the calibrated noise variance: σ²C²/K on a zero-delta aggregate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["tree", "fused"])
def test_noise_variance_sigma2_c2_over_k(impl):
    sigma, clip, K, n = 1.0, 0.1, 8, 1 << 17
    dp = DPSpec(clip, sigma)
    params = {"w": jnp.zeros((n,), jnp.float32)}
    w_locals = {"w": jnp.zeros((K, n), jnp.float32)}   # δᵢ = 0
    weights = jnp.ones((K,))
    ids = jnp.arange(K)
    rk = jax.random.PRNGKey(123)
    if impl == "tree":
        new_p = privacy.tree_dp_aggregate(dp, False, rk, ids, params,
                                          w_locals, weights)
    else:
        view = FlatView.of(params)
        fops = FlatParamOps(view=view, interpret=True)
        new_p = fops.unflatten(privacy.fused_dp_aggregate(
            dp, False, fops, rk, ids, fops.flatten(params),
            view.flatten_stacked(w_locals), weights))
    noise = np.asarray(new_p["w"], np.float64)
    want_var = sigma ** 2 * clip ** 2 / K
    # sample-variance standard error is var·sqrt(2/n) ≈ 0.4% — the 5%
    # bound is ~13 standard errors, deterministic seed, cannot flake
    assert abs(np.var(noise) / want_var - 1.0) < 0.05, np.var(noise)
    assert abs(noise.mean()) < 5e-4


def test_fused_and_tree_noise_bits_identical():
    # same round key -> the extra term matches bit-for-bit across reprs
    sigma, clip, K, n = 0.7, 0.2, 4, 4096
    dp = DPSpec(clip, sigma)
    params = {"w": jnp.zeros((n,), jnp.float32)}
    w_locals = {"w": jnp.zeros((K, n), jnp.float32)}
    weights = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    ids = jnp.asarray([5, 1, 3, 2])
    rk = jax.random.PRNGKey(9)
    tree_p = privacy.tree_dp_aggregate(dp, False, rk, ids, params,
                                       w_locals, weights)
    view = FlatView.of(params)
    fops = FlatParamOps(view=view, interpret=True)
    fused_p = fops.unflatten(privacy.fused_dp_aggregate(
        dp, False, fops, rk, ids, fops.flatten(params),
        view.flatten_stacked(w_locals), weights))
    np.testing.assert_array_equal(np.asarray(tree_p["w"]),
                                  np.asarray(fused_p["w"]))
