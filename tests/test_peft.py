"""Trainable-slice / PEFT execution (repro.fl.local + utils.flatten +
sharding.rules + models.layers LoRA).

The contract under test, layer by layer:

  - LoRA layer: B zero-init makes the adapted forward equal the base
    forward BITWISE at init (and the base ``w`` draw is unchanged by
    adding adapters); ``merge_lora`` folds ``W + (α/r)·B A`` so the
    merged plain model matches the adapter model's forward;
  - filter partition: an all-matching filter == filter=None bitwise
    through a full engine run (the filtered program with zero frozen
    leaves IS the current program — the rest of the suite is the
    filter=None oracle);
  - frozen residency: across multi-round host AND pod runs every
    frozen leaf comes back bitwise-identical to its init value while
    every trainable leaf moves; host == pod round-for-round;
  - wire accounting: the P2 upload payload is the dtype-aware byte
    count of the trainable slice, EXACTLY (ledger == closed form), and
    a lossy spec compresses the slice (ratios compose);
  - invalid configs fail loudly AT CONSTRUCTION with actionable
    messages (unknown peft spec, rank ≤ 0, tree impl, zero-leaf
    filter, peft on the P1 relay);
  - (slow) a 16-fake-device subprocess run: the trainable buckets keep
    their sharded (dtype × axes) decomposition, the frozen buckets get
    their own sharded groups, and frozen invariance holds on a real
    4×4 mesh.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced, with_peft
from repro.core import comm_accounting as acc
from repro.core.comm_accounting import CommLedger
from repro.data.synthetic import make_synthetic_tokenlm
from repro.fl.compression import CompressionSpec
from repro.fl import compression as comp
from repro.fl.engine import AggregateStrategy, RelayStrategy, RoundSchedule, run_rounds
from repro.fl.local import (
    LocalSpec,
    effective_trainable_filter,
    host_flat_ops,
    parse_peft,
    validate_peft,
)
from repro.fl.pod import PodAggregateStrategy, PodFLSpec, PodRelayStrategy
from repro.fl.task import lm_task
from repro.launch.mesh import make_host_mesh
from repro.models import layers
from repro.models.transformer import init_lm, lm_forward
from repro.sharding import rules

SEED = 0


# ---------------------------------------------------------------------------
# knob parsing / construction-time validation
# ---------------------------------------------------------------------------

def test_parse_peft():
    assert parse_peft("lora:8") == ("lora", 8)
    with pytest.raises(ValueError, match="unknown peft spec"):
        parse_peft("adapters:8")
    with pytest.raises(ValueError, match="unknown peft spec"):
        parse_peft("lora")
    with pytest.raises(ValueError, match="positive integer"):
        parse_peft("lora:0")
    with pytest.raises(ValueError, match="positive integer"):
        parse_peft("lora:-3")


def test_validate_peft_rejects_tree_impl():
    with pytest.raises(ValueError, match="fused flat path"):
        validate_peft("lora:8", update_impl="tree")
    with pytest.raises(ValueError, match="fused flat path"):
        LocalSpec(2, 4, 0.05, peft="lora:8")            # default impl is tree
    with pytest.raises(ValueError, match="fused flat path"):
        PodFLSpec(peft="lora:8")
    # filter alone needs the flat partition too
    with pytest.raises(ValueError, match="fused flat path"):
        LocalSpec(2, 4, 0.05, trainable_filter="head")


def test_effective_trainable_filter():
    assert effective_trainable_filter(
        LocalSpec(2, 4, 0.05, update_impl="fused", peft="lora:4")) == "lora"
    assert effective_trainable_filter(
        LocalSpec(2, 4, 0.05, update_impl="fused", trainable_filter="head")) == "head"
    assert effective_trainable_filter(LocalSpec(2, 4, 0.05)) is None


def test_zero_leaf_filter_raises_at_construction():
    cfg = get_reduced("qwen1.5-0.5b")       # no adapters built
    p_specs = jax.eval_shape(lambda k: init_lm(k, cfg), jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="matches zero leaves"):
        rules.trainable_mask(p_specs, "lora")


def test_relay_rejects_peft():
    spec = LocalSpec(2, 4, 0.05, update_impl="fused_interpret", peft="lora:4")
    with pytest.raises(ValueError, match="P2 rounds only"):
        RelayStrategy(spec=spec)
    with pytest.raises(ValueError, match="P2 rounds only"):
        PodRelayStrategy(spec=spec, mesh=make_host_mesh())


def test_lora_rank_validation():
    with pytest.raises(ValueError, match="positive integer"):
        layers.init_lora_linear(jax.random.PRNGKey(0), 8, 8, rank=0)


# ---------------------------------------------------------------------------
# LoRA layer semantics
# ---------------------------------------------------------------------------

def test_lora_zero_init_is_base_forward_bitwise():
    key = jax.random.PRNGKey(SEED)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
    base = layers.init_linear(key, 16, 8)
    lora = layers.init_lora_linear(key, 16, 8, rank=4)
    # adding adapters does not redraw the base weight
    np.testing.assert_array_equal(np.asarray(base["w"]),
                                  np.asarray(lora["w"]))
    np.testing.assert_array_equal(np.asarray(layers.linear(base, x)),
                                  np.asarray(layers.linear(lora, x)))


def test_lora_merge_forward_parity():
    key = jax.random.PRNGKey(SEED)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
    p = layers.init_lora_linear(key, 16, 8, rank=4)
    # perturb B so the adapter actually contributes
    p["lora_b"] = jax.random.normal(jax.random.PRNGKey(2), p["lora_b"].shape,
                                    p["lora_b"].dtype) * 0.1
    merged = layers.merge_lora(p)
    assert "lora_a" not in merged and "lora_b" not in merged
    np.testing.assert_allclose(np.asarray(layers.linear(merged, x)),
                               np.asarray(layers.linear(p, x)),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# engine runs: filter partition + frozen residency (host and pod)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lora_setup():
    cfg = with_peft(get_reduced("qwen1.5-0.5b"), "lora:4")
    task = lm_task(cfg)
    data = make_synthetic_tokenlm(n_clients=8, seq_len=16,
                                  n_seq_per_client=8,
                                  vocab=cfg.vocab_size, beta=0.5, seed=SEED)
    return cfg, task, data


def _sched(rounds=4, chunk=2):
    return RoundSchedule(rounds=rounds, lr_decay=1.0, eval_every=0,
                         seed=SEED, chunk_size=chunk, sampling="host",
                         host_rng_offset=17)


def _run(task, data, *, peft=None, trainable_filter=None, backend="host",
         rounds=4, ledger=None, compression=None):
    spec = LocalSpec(n_steps=2, batch_size=4, lr=0.05,
                     update_impl="fused_interpret", peft=peft,
                     trainable_filter=trainable_filter,
                     compression=compression)
    if backend == "host":
        strat = AggregateStrategy(spec=spec, participation=0.25)
    else:
        strat = PodAggregateStrategy(spec=spec, mesh=make_host_mesh(),
                                     clients_per_round=2)
    return run_rounds(task, data, strat, _sched(rounds), ledger=ledger)


def test_all_matching_filter_equals_unfiltered_bitwise(lora_setup):
    """A filter selecting EVERY leaf partitions nothing — it must
    compile to the exact unfiltered program (the suite's oracle)."""
    cfg, task, data = lora_setup
    base = _run(task, data)
    allf = _run(task, data, trainable_filter=r".")      # matches all paths
    np.testing.assert_array_equal([h["local_loss"] for h in base.history],
                                  [h["local_loss"] for h in allf.history])
    for a, b in zip(jax.tree_util.tree_leaves(base.params),
                    jax.tree_util.tree_leaves(allf.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("backend", ["host", "pod"])
def test_frozen_leaves_bitwise_invariant(lora_setup, backend):
    """Multi-round LoRA run: every frozen leaf returns bitwise-equal to
    its init value, every adapter leaf moves."""
    cfg, task, data = lora_setup
    p0 = task.init(jax.random.PRNGKey(SEED))
    mask = rules.trainable_mask(p0, "lora")
    res = _run(task, data, peft="lora:4", backend=backend)
    moved = 0
    for (pa, a), b, m in zip(jax.tree_util.tree_leaves_with_path(p0),
                             jax.tree_util.tree_leaves(res.params), mask):
        if m:
            moved += int(not np.array_equal(np.asarray(a), np.asarray(b)))
        else:
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"frozen leaf changed: {pa}")
    assert moved == sum(mask)               # every adapter leaf trained


def test_host_pod_lora_parity(lora_setup):
    cfg, task, data = lora_setup
    host = _run(task, data, peft="lora:4", backend="host")
    pod = _run(task, data, peft="lora:4", backend="pod")
    np.testing.assert_allclose([h["local_loss"] for h in host.history],
                               [h["local_loss"] for h in pod.history],
                               atol=1e-5, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(host.params),
                    jax.tree_util.tree_leaves(pod.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-4, rtol=1e-4)


def test_lora_merge_model_forward_parity(lora_setup):
    """Merging the TRAINED adapters into the base weights reproduces the
    adapter model's forward — the deployment path."""
    cfg, task, data = lora_setup
    res = _run(task, data, peft="lora:4", rounds=2)
    params = jax.device_get(res.params)
    merged = layers.merge_lora(params)
    assert not any("lora" in str(p)
                   for p, _ in jax.tree_util.tree_leaves_with_path(merged))
    toks = {"tokens": jnp.asarray(data.x[0][:2])}
    plain_cfg = dataclasses.replace(cfg, lora_rank=0)
    out_adapter, _, _ = lm_forward(params, cfg, toks)
    out_merged, _, _ = lm_forward(merged, plain_cfg, toks)
    np.testing.assert_allclose(np.asarray(out_adapter),
                               np.asarray(out_merged),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# wire accounting: the upload is the trainable slice
# ---------------------------------------------------------------------------

def _trainable_bytes(task, filter_spec):
    p_specs = jax.eval_shape(task.init, jax.random.PRNGKey(0))
    mask = rules.trainable_mask(p_specs, filter_spec)
    leaves = jax.tree_util.tree_leaves(p_specs)
    return int(sum(np.dtype(l.dtype).itemsize * np.prod(l.shape)
                   for l, m in zip(leaves, mask) if m))


def test_ledger_counts_trainable_slice_only(lora_setup):
    cfg, task, data = lora_setup
    led = CommLedger()
    rounds = 2
    _run(task, data, peft="lora:4", rounds=rounds, ledger=led)
    payload = _trainable_bytes(task, "lora")
    x = led.summary()["model_bytes"]
    k = 2                                   # participation 0.25 of 8
    assert led.p2_upload_bytes == rounds * k * payload
    assert led.p2_bytes == rounds * acc.compressed_round_bytes(
        "fedavg", k, x, payload)            # downloads still ship X
    assert led.summary()["payload_ratio"] == x / payload
    assert led.summary()["payload_ratio"] > 5


def test_peft_composes_with_compression(lora_setup):
    """A lossy wire spec compresses the SLICE: payload_bytes over the
    trainable buffer sizes — the two ratios multiply."""
    cfg, task, data = lora_setup
    spec = CompressionSpec(bits=8)
    led = CommLedger()
    rounds = 2
    _run(task, data, peft="lora:4", rounds=rounds, ledger=led,
         compression=spec)
    sizes = tuple(host_flat_ops(task, True, "lora").view
                  .buffer_sizes.values())
    payload = comp.payload_bytes(spec, sizes)
    assert payload < _trainable_bytes(task, "lora")
    assert led.p2_upload_bytes == rounds * 2 * payload


# ---------------------------------------------------------------------------
# (slow) pod: sharded trainable/frozen buckets on a 16-device mesh
# ---------------------------------------------------------------------------

_PEFT_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax
    import numpy as np
    from repro.configs import get_reduced, with_peft
    from repro.data.synthetic import make_synthetic_tokenlm
    from repro.fl.engine import RoundSchedule, run_rounds
    from repro.fl.local import LocalSpec
    from repro.fl.pod import PodAggregateStrategy
    from repro.fl.task import lm_task
    from repro.sharding import rules
    from repro.utils.flatten import is_frozen_bucket

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 4), ("data", "model"))
    cfg = with_peft(get_reduced("qwen1.5-0.5b"), "lora:4")
    task = lm_task(cfg)
    data = make_synthetic_tokenlm(n_clients=8, seq_len=16,
                                  n_seq_per_client=8,
                                  vocab=cfg.vocab_size, beta=0.5, seed=0)
    spec = LocalSpec(n_steps=2, batch_size=4, lr=0.05,
                     update_impl="fused_interpret", peft="lora:4")
    strat = PodAggregateStrategy(spec=spec, mesh=mesh, clients_per_round=4)
    fops = strat.flat_ops(task)

    # the partition split buckets: trainable AND frozen groups exist,
    # with disjoint names, and the frozen groups carry their own
    # sharded (dtype x axes) decomposition
    t_names = {g.name for g in fops.view.trainable_groups}
    f_names = {g.name for g in fops.view.frozen_groups}
    assert t_names and f_names and not (t_names & f_names), (t_names, f_names)
    assert all(is_frozen_bucket(n) for n in f_names)
    fz_sh = rules.frozen_flat_shardings(fops.view, mesh)
    assert set(fz_sh) == f_names
    # at least one frozen bucket actually shards over the mesh (the big
    # frozen base must not replicate)
    assert any(sh.spec != jax.sharding.PartitionSpec(None, None)
               for sh in fz_sh.values()), {n: s.spec for n, s in fz_sh.items()}

    p0 = task.init(jax.random.PRNGKey(0))
    mask = rules.trainable_mask(p0, "lora")
    res = run_rounds(task, data, strat,
                     RoundSchedule(rounds=2, lr_decay=1.0, eval_every=0,
                                   seed=0, chunk_size=2, sampling="host",
                                   host_rng_offset=17))
    moved = 0
    for a, b, m in zip(jax.tree_util.tree_leaves(p0),
                       jax.tree_util.tree_leaves(res.params), mask):
        if m:
            moved += int(not np.array_equal(np.asarray(a), np.asarray(b)))
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert moved == sum(mask), (moved, sum(mask))
    print("POD_PEFT_SUBPROCESS_OK")
""")


@pytest.mark.slow
def test_pod_peft_sharded_buckets_16dev_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _PEFT_SUBPROCESS_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "POD_PEFT_SUBPROCESS_OK" in out.stdout
