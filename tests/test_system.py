"""End-to-end behaviour tests for the CyclicFL system.

These assert the paper's QUALITATIVE claims at test scale (seconds, not
benchmark-grade):
  - the pipeline runs P1→P2 and improves over random init (RQ1/RQ2),
  - all four FL algorithms compose with cyclic pre-training,
  - the communication ledger matches Table IV closed forms exactly,
  - the pod-scale (sharded) driver agrees with the host simulator's
    semantics and reduces training loss,
  - switch policies terminate P1 when they should.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm_accounting as acc
from repro.core.cyclic import CyclicConfig, cyclic_pretrain
from repro.core.pipeline import run_cyclic_then_federated
from repro.core.switch import AccuracyPlateau, BudgetFraction, FixedRounds
from repro.data.synthetic import DATASETS, make_synthetic_tokenlm
from repro.fl.simulation import FLConfig, run_federated
from repro.fl.task import charlm_task, vision_task

SEED = 0


@pytest.fixture(scope="module")
def vision_setup():
    data = DATASETS.get("cifar10-like")(n_clients=8, beta=0.5, seed=SEED,
                                        n_train=512, n_test=256)
    task = vision_task("lenet5", n_classes=10, in_ch=3)
    return task, data


def _tiny_cyc(rounds=2, steps=4):
    return CyclicConfig(rounds=rounds, participation=0.25, local_steps=steps,
                        eval_every=1, seed=SEED)


def _tiny_fl(algorithm="fedavg", rounds=3, steps=4):
    return FLConfig(algorithm=algorithm, rounds=rounds, participation=0.25,
                    local_steps=steps, eval_every=1, seed=SEED)


def test_cyclic_pretrain_reduces_loss(vision_setup):
    task, data = vision_setup
    res = cyclic_pretrain(task, data, _tiny_cyc(rounds=3, steps=8))
    losses = [h["local_loss"] for h in res.history]
    assert losses[-1] < losses[0]
    assert len(res.history) == 3


def test_pipeline_beats_random_init_same_budget(vision_setup):
    """RQ1/RQ2 at test scale: with a fixed total budget, Cyclic+FedAvg
    reaches at-least-as-good accuracy as FedAvg from random init."""
    task, data = vision_setup
    cyc = run_cyclic_then_federated(task, data, _tiny_cyc(rounds=3, steps=8),
                                    _tiny_fl(rounds=5, steps=8))
    base = run_cyclic_then_federated(task, data, None,
                                     _tiny_fl(rounds=8, steps=8))
    a = cyc.best_acc().get("acc", 0.0)
    b = base.best_acc().get("acc", 0.0)
    # generous slack: tiny scale is noisy, but cyclic must not be WORSE
    # by a wide margin, and usually wins
    assert a >= b - 0.05, (a, b)


@pytest.mark.parametrize("algorithm", ["fedavg", "fedprox", "scaffold", "moon"])
def test_all_algorithms_run_and_learn(vision_setup, algorithm):
    task, data = vision_setup
    res = run_federated(task, data, _tiny_fl(algorithm, rounds=3))
    assert len(res.history) == 3
    accs = [h["acc"] for h in res.history if "acc" in h]
    assert accs and all(np.isfinite(a) for a in accs)
    assert accs[-1] > 1.0 / data.n_classes * 0.8  # above-chance-ish


@pytest.mark.parametrize("algorithm", ["fedavg", "scaffold"])
def test_ledger_matches_closed_form(vision_setup, algorithm):
    task, data = vision_setup
    res = run_cyclic_then_federated(task, data, _tiny_cyc(rounds=2),
                                    _tiny_fl(algorithm, rounds=3))
    led = res.ledger.summary()
    k_p1 = _tiny_cyc().n_selected(data.n_clients)
    k_p2 = _tiny_fl(algorithm).n_selected(data.n_clients)
    want = acc.overhead_with_cyclic(algorithm, k_p1, 2, k_p2, 3,
                                    led["model_bytes"])
    assert led["total_bytes"] == want


def test_cyclic_is_strictly_sequential(vision_setup):
    """Algorithm-1 semantics: the relay visits clients IN ORDER — running
    one round over clients [a, b] must equal local(local(w, a), b)."""
    from repro.core.cyclic import make_cyclic_round_fn
    from repro.fl.local import make_local_fn

    task, data = vision_setup
    ccfg = _tiny_cyc(rounds=1, steps=3)
    round_fn = make_cyclic_round_fn(task, ccfg)
    x_all, y_all, _ = data.device_arrays()
    params = task.init(jax.random.PRNGKey(SEED))
    key = jax.random.PRNGKey(42)
    ids = jnp.asarray([2, 5])

    got, _ = round_fn(key, params, x_all, y_all, ids, jnp.float32(1.0))

    local = make_local_fn(task, ccfg.local_spec())
    keys = jax.random.split(key, 2)
    w1, _ = local(keys[0], params, {}, x_all[2], y_all[2], jnp.float32(1.0))
    w2, _ = local(keys[1], w1, {}, x_all[5], y_all[5], jnp.float32(1.0))

    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(w2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-5, rtol=1e-5)


def test_switch_policies():
    hist_flat = [{"round": i, "acc": 0.5} for i in range(10)]
    hist_rising = [{"round": i, "acc": 0.1 * i} for i in range(10)]
    assert FixedRounds(t_cyc=3).should_switch(2, hist_flat[:3])
    assert not FixedRounds(t_cyc=3).should_switch(1, hist_flat[:2])
    p = AccuracyPlateau(patience=2, min_delta=0.01, min_rounds=2)
    assert p.should_switch(9, hist_flat)
    assert not p.should_switch(9, hist_rising)
    b = BudgetFraction(total_rounds=20, fraction=0.25)
    assert b.should_switch(4, hist_flat) and not b.should_switch(3, hist_flat)


def test_charlm_task_runs():
    data = DATASETS.get("shakespeare-like")(n_clients=8, seed=SEED,
                                            n_seq_per_client=16, n_test=64)
    task = charlm_task(vocab=64)
    res = run_federated(task, data, _tiny_fl(rounds=2, steps=4))
    assert np.isfinite(res.history[-1]["local_loss"])


# ---------------------------------------------------------------------------
# paper models: the conv primitive and its clients-merged kernel gradient
# ---------------------------------------------------------------------------

NHWC = ("NHWC", "HWIO", "NHWC")

# kernel, C_in, C_out, stride, image side, clients (None: one model, no
# vmap)
CONVS = {
    "lenet5_conv1": (5, 3, 6, 1, 32, 3),
    "lenet5_conv2": (5, 6, 16, 1, 16, 3),
    "stem_3x3_stride2": (3, 3, 16, 2, 17, 3),
    "wide_3x3x64": (3, 64, 64, 1, 8, 3),
    "narrow_c15_two_groups": (3, 15, 8, 1, 8, 10),
    "lenet5_conv1_one_model": (5, 3, 6, 1, 32, None),
    "c_out32_grouped": (5, 1, 32, 1, 12, 3),
}
# the groups in which every conv of the paper's vision models, by its
# params path, merges the kernel gradients of the paper's K=10 clients
# (groups of one: the grouped gradient)
GROUPED = (1,) * 10
PAPER_CONV_GROUPS = {
    "lenet5": {"c1": (10,), "c2": (5, 5)},
    "resnet8": {"stem": (5, 5), "b1/conv1": GROUPED, "b1/conv2": GROUPED,
                "b2/conv1": GROUPED, "b2/conv2": GROUPED,
                "b2/proj": GROUPED, "b3/conv1": GROUPED,
                "b3/conv2": GROUPED, "b3/proj": GROUPED},
    "cnn_femnist": {"c1": GROUPED, "c2": GROUPED},
    "cnn_fashion": {"c1": (5, 5), "c2": GROUPED},
}


def _plain_conv2d(p, x, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, p["w"].astype(x.dtype), (stride, stride), padding,
        dimension_numbers=NHWC,
        precision=jax.lax.Precision.HIGHEST) + p["b"].astype(x.dtype)


def _assert_trees_close(got, want, rel=1e-5):
    for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        w = want
        for k in path:
            w = w[k.key] if hasattr(k, "key") else w[k.idx]
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=rel * scale,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("case", [*CONVS, "paper_model_paths"])
def test_conv2d_gradients_match_plain_conv(case, monkeypatch):
    """``conv2d``'s value and its gradients in ``w``, ``b`` and ``x``,
    vmapped over per-client kernels as the round engine runs them (and
    for one model, as the relay runs it), against a plain
    ``conv_general_dilated`` at HIGHEST precision: with the grouped
    kernel gradient the CPU keeps, with the clients-merged one the TPU
    runs (forced here), and the merged one called directly; and the
    groups in which every paper-model conv merges its clients."""
    from repro.models import paper_models as pm

    if case == "paper_model_paths":
        def kernels(init, key):
            return {"/".join(str(k.key) for k in path[:-1]): leaf
                    for path, leaf in jax.tree_util.tree_flatten_with_path(
                        init(key))[0]
                    if path[-1].key == "w" and getattr(leaf, "ndim", 0) == 4}

        for model, want in PAPER_CONV_GROUPS.items():
            init, apply, _ = pm.PAPER_MODELS.get(model)
            convs = jax.eval_shape(lambda k: kernels(init, k),
                                   jax.random.PRNGKey(0))
            assert set(convs) == set(want), model
            for name, w in convs.items():
                c_in, c_out = w.shape[2:]
                assert pm.client_groups(10, c_in, c_out) == want[name], \
                    f"{model}/{name}"
            # every conv of the model is the primitive
            p = init(jax.random.PRNGKey(0))
            x = jax.ShapeDtypeStruct((2, 28, 28, 1) if "cnn" in model
                                     else (2, 32, 32, 3), jnp.float32)
            jaxpr = str(jax.make_jaxpr(lambda x: apply(p, x))(x))
            assert jaxpr.count("paper_conv[") == len(want), model
            assert "conv_general_dilated" not in jaxpr, model
        return

    k, c_in, c_out, stride, side, clients = CONVS[case]
    lead = () if clients is None else (clients,)
    side_out = -(-side // stride)
    rng = np.random.default_rng(1)
    ps = {"w": rng.standard_normal(lead + (k, k, c_in, c_out), np.float32),
          "b": rng.standard_normal(lead + (c_out,), np.float32)}
    xs = rng.standard_normal(lead + (4, side, side, c_in), np.float32)
    # a cotangent that differs at every output element
    dys = rng.standard_normal(lead + (4, side_out, side_out, c_out),
                              np.float32)

    def value_and_grads(conv):
        def loss(p, x, dy):
            y = conv(p, x, stride)
            return jnp.sum(jnp.sin(y) * dy), y
        f = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
        (_, y), g = jax.jit(f if clients is None else jax.vmap(f))(
            ps, xs, dys)
        return {"y": y, "w": g[0]["w"], "b": g[0]["b"], "x": g[1]}

    want = value_and_grads(_plain_conv2d)
    _assert_trees_close(value_and_grads(pm.conv2d), want)
    if clients is None:
        return
    monkeypatch.setattr(pm, "MERGED_PLATFORMS", ("tpu", "cpu"))
    _assert_trees_close(value_and_grads(pm.conv2d), want)
    # the merged kernel gradient, called directly
    groups = pm.client_groups(clients, c_in, c_out)
    assert (len(groups) < clients) == (
        c_in < pm.MERGED_C_IN and c_out <= pm.MERGED_C_OUT), groups
    assert sum(groups) == clients and max(groups) - min(groups) <= 1
    got = jax.jit(lambda x, dy: pm._clients_kernel_grad(
        x, dy, groups=groups, stride=stride, padding="SAME", kernel=(k, k),
        image=(side, side)))(xs, dys)
    _, vjp = jax.vjp(lambda w: jax.vmap(_plain_conv2d, (0, 0, None))(
        dict(ps, w=w), xs, stride), ps["w"])
    _assert_trees_close(got, vjp(dys)[0])


def _lenet5_loss(n=4):
    from repro.models import paper_models as pm
    init, apply, _ = pm.PAPER_MODELS.get("lenet5")
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((n, 32, 32, 3), np.float32))
    y = jnp.asarray(rng.integers(0, 10, n))

    def loss(p, x=x):
        logp = jax.nn.log_softmax(apply(p, x))
        return -jnp.mean(logp[jnp.arange(n), y])
    return init(jax.random.PRNGKey(0)), x, loss


@pytest.mark.parametrize("form", ["jvp", "grad_of_grad", "hessian_top_eig",
                                  "jvp_of_clients_grad",
                                  "clients_local_run"])
def test_conv2d_autodiff_forms_match_plain_conv(form, monkeypatch):
    """Every form of autodiff goes through ``conv2d``'s primitive, on a
    small LeNet-5, and agrees with the model built on plain
    ``conv_general_dilated``: forward mode, reverse over reverse, the
    Fig 7 Hessian probe (forward over reverse), forward over the
    clients' vmapped gradient and, through ``make_local_fn``'s scan,
    the vmapped local run, each with the clients-merged kernel gradient
    the TPU runs."""
    from repro.core import diagnostics as diag
    from repro.models import paper_models as pm

    p, x, loss = _lenet5_loss()
    K = 3
    ps = jax.tree_util.tree_map(
        lambda a: jnp.stack([a * (1 + 0.1 * i) for i in range(K)]), p)
    xs = jnp.stack([x * (1 + 0.2 * i) for i in range(K)])
    leaves = jax.tree_util.tree_leaves

    def run():
        if form == "jvp":
            return jax.jit(lambda t: jax.jvp(loss, (p,), (t,)))(p)
        if form == "grad_of_grad":
            return jax.jit(jax.grad(lambda p: sum(
                jnp.sum(g ** 2) for g in leaves(jax.grad(loss)(p)))))(p)
        if form == "hessian_top_eig":
            return diag.hessian_top_eig(loss, p, jax.random.PRNGKey(3),
                                        n_iter=4)
        if form == "jvp_of_clients_grad":
            return jax.jit(lambda t: jax.jvp(
                jax.vmap(jax.grad(loss)), (ps, xs),
                (t, jnp.zeros_like(xs))))(ps)
        from repro.fl.local import LocalSpec, make_local_fn
        from repro.fl.task import vision_task
        local = make_local_fn(vision_task("lenet5"),
                              LocalSpec(n_steps=3, batch_size=4, lr=0.05))
        keys = jax.random.split(jax.random.PRNGKey(4), K)
        ys = jnp.asarray(np.random.default_rng(5).integers(0, 10, (K, 8)))
        cx = jnp.concatenate([xs, xs[:, ::-1]], axis=1)
        return jax.jit(jax.vmap(lambda k, x, y: local(
            k, p, {}, x, y, jnp.float32(1.0))))(keys, cx, ys)

    got = run()
    monkeypatch.setattr(pm, "MERGED_PLATFORMS", ("tpu", "cpu"))
    merged = run()
    monkeypatch.setattr(pm, "conv2d", _plain_conv2d)
    want = run()
    if form == "hessian_top_eig":
        assert got > 0
        np.testing.assert_allclose([got, merged], [want, want], rtol=1e-4)
        return
    _assert_trees_close(got, want, rel=1e-4)
    _assert_trees_close(merged, want, rel=1e-4)


# ---------------------------------------------------------------------------
# pod-scale (sharded) driver
# ---------------------------------------------------------------------------

def test_pod_driver_trains_and_matches_budget():
    from repro.configs import get_reduced
    from repro.launch.train import PodFLSpec, run_pod_training

    cfg = get_reduced("qwen1.5-0.5b")
    data = make_synthetic_tokenlm(n_clients=8, seq_len=32,
                                  n_seq_per_client=16,
                                  vocab=cfg.vocab_size, beta=0.5, seed=SEED)
    spec = PodFLSpec(local_steps=3, lr=0.05)
    res = run_pod_training(cfg, data, cyclic_rounds=2, fl_rounds=2,
                           clients_per_round=3, spec=spec, seed=SEED)
    assert len(res.history) == 4
    assert res.history[0]["phase"] == "P1" and res.history[-1]["phase"] == "P2"
    assert res.history[-1]["loss"] < res.history[0]["loss"]


def test_pod_cyclic_round_is_relay():
    """Pod P1 semantics: scan(K clients) == sequential local SGD chain."""
    from repro.configs import get_reduced
    from repro.launch.train import (PodFLSpec, _local_sgd,
                                    make_pod_cyclic_round)
    from repro.models.transformer import init_lm

    cfg = get_reduced("tinyllama-1.1b")
    spec = PodFLSpec(local_steps=2, lr=0.05)
    params = init_lm(jax.random.PRNGKey(SEED), cfg)
    key = jax.random.PRNGKey(7)
    K, B, S = 2, 4, 16
    toks = jax.random.randint(key, (K, spec.local_steps, B, S), 0,
                              cfg.vocab_size)
    batches = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=-1)}

    round_fn = make_pod_cyclic_round(cfg, spec)
    got, _ = round_fn(params, batches, jnp.float32(1.0))

    local = _local_sgd(cfg, spec)
    w = params
    for i in range(K):
        w, _ = local(w, jax.tree_util.tree_map(lambda x: x[i], batches),
                     jnp.float32(1.0), None)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(w)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-5, rtol=2e-5)


def test_pod_fl_round_equals_weighted_mean():
    """Pod P2 semantics: delta aggregation == weighted mean of client
    results (the FedAvg identity)."""
    from repro.configs import get_reduced
    from repro.launch.train import PodFLSpec, _local_sgd, make_pod_fl_round
    from repro.models.transformer import init_lm

    cfg = get_reduced("tinyllama-1.1b")
    spec = PodFLSpec(local_steps=2, lr=0.05)
    params = init_lm(jax.random.PRNGKey(SEED), cfg)
    key = jax.random.PRNGKey(11)
    K, B, S = 3, 4, 16
    toks = jax.random.randint(key, (K, spec.local_steps, B, S), 0,
                              cfg.vocab_size)
    batches = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=-1)}
    weights = jnp.asarray([1.0, 2.0, 3.0])

    round_fn = make_pod_fl_round(cfg, spec)
    got, _ = round_fn(params, batches, weights, jnp.float32(1.0))

    local = _local_sgd(cfg, spec)
    locals_ = [local(params, jax.tree_util.tree_map(lambda x: x[i], batches),
                     jnp.float32(1.0), None)[0] for i in range(K)]
    p32 = jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32), params)
    ws32 = [jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32), w)
            for w in locals_]
    wsum = float(weights.sum())
    want = jax.tree_util.tree_map(
        lambda p, *ws: p + sum(float(weights[i]) / wsum * (ws[i] - p)
                               for i in range(K)),
        p32, *ws32)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                   atol=3e-5, rtol=3e-5)


def test_server_optimizer_none_equals_plain_fedavg(vision_setup):
    """server_opt='none' must reproduce vanilla FedAvg bit-for-bit, and
    server_opt='momentum' with server_lr=1, momentum=0 likewise (the
    pseudo-gradient step degenerates to w ← w_avg)."""
    import dataclasses as dc
    task, data = vision_setup
    base = _tiny_fl(rounds=2, steps=4)
    r_plain = run_federated(task, data, base)
    r_mom0 = run_federated(task, data, dc.replace(
        base, server_opt="momentum", server_lr=1.0, server_momentum=0.0))
    for a, b in zip(jax.tree_util.tree_leaves(r_plain.params),
                    jax.tree_util.tree_leaves(r_mom0.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("server_opt", ["momentum", "adam"])
def test_server_optimizer_runs_and_learns(vision_setup, server_opt):
    """Beyond-paper server optimizers (FedAvgM / FedAdam) train sanely
    and compose with cyclic pre-training."""
    import dataclasses as dc
    task, data = vision_setup
    # adam normalizes the pseudo-gradient, so server_lr IS the parameter
    # step size — keep it small (FedAdam convention)
    cfg = dc.replace(_tiny_fl(rounds=3, steps=6), server_opt=server_opt,
                     server_lr=1.0 if server_opt == "momentum" else 0.03)
    res = run_cyclic_then_federated(task, data, _tiny_cyc(rounds=2), cfg)
    accs = [h["acc"] for h in res.history if "acc" in h]
    assert accs and np.isfinite(accs[-1])
    assert accs[-1] > 1.0 / data.n_classes * 0.8


def test_serve_engine_greedy_decode_matches_forward():
    """Engine.generate greedy path == argmax over the parallel forward."""
    from repro.configs import get_reduced
    from repro.launch.serve import Engine
    from repro.models.transformer import lm_forward

    cfg = get_reduced("qwen2-1.5b")
    eng = Engine(cfg, seed=SEED)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0,
                              cfg.vocab_size)
    out, _ = eng.generate({"tokens": toks}, new_tokens=3)
    # replay: greedy continuation via repeated full forwards
    seq = toks
    for _ in range(3):
        logits, _, _ = lm_forward(eng.params, cfg, {"tokens": seq})
        seq = jnp.concatenate([seq, jnp.argmax(logits[:, -1], -1)[:, None]],
                              axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq[:, 12:]))
