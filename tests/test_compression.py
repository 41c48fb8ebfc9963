"""Compressed communication (repro.fl.compression) — parity + accounting.

The contract under test, layer by layer:

  - kernel parity: the blocked Pallas compress kernel == the pure-jnp
    ``reference_compress`` == the NumPy ground truth ``numpy_compress``,
    BITWISE, across sizes / bits / densities (plus hypothesis sweeps
    when installed: quantization error ≤ wire-scale/2, identity specs
    bit-exact);
  - engine parity: the identity spec compiles to the exact baseline
    program (bitwise, fused AND tree); lossy fused == lossy tree
    bitwise (same flat buckets, same accumulation order);
  - error feedback: residual rows ride the ClientStateStore contract —
    sparse == dense bitwise across LRU eviction/spill, sync and
    overlapped; EF-FedAvg tracks the uncompressed run within tolerance;
  - wire accounting: ``CommLedger`` totals == the closed forms exactly,
    and the int8 dense upload ratio clears the ≥3.9× gate;
  - invalid combos fail loudly AT CONSTRUCTION with actionable messages;
  - (slow) a 16-fake-device subprocess run: compressed hierarchical ==
    compressed sequential on a real 4×4 mesh, identity == baseline
    bitwise on the pod.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm_accounting as acc
from repro.core.comm_accounting import CommLedger
from repro.data.federated import FederatedDataset
from repro.fl import compression as comp
from repro.fl.compression import CompressionSpec
from repro.fl.engine import (
    AggregateStrategy,
    DenseClientStateStore,
    RelayStrategy,
    RoundSchedule,
    SparseClientStateStore,
    run_rounds,
)
from repro.fl.local import LocalSpec, host_flat_ops
from repro.fl.pod import PodAggregateStrategy, PodFLSpec
from repro.fl.privacy import DPSpec
from repro.fl.simulation import FLConfig
from repro.fl.task import vision_task
from repro.kernels import ops
from repro.launch.mesh import make_host_mesh

SEED = 0
N_CLIENTS = 8

try:
    from hypothesis import given, settings
    from hypothesis import strategies as hst
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# kernel ↔ jnp reference ↔ NumPy oracle, bitwise
# ---------------------------------------------------------------------------

def _kernel_compress(d, spec):
    """The blocked kernel, called the way FlatParamOps.compress_delta
    calls it (threshold computed outside, logical k)."""
    d = jnp.asarray(d, jnp.float32)
    tau = (comp.topk_threshold(d, comp.topk_k(spec, d.shape[-1]))
           if spec.sparsifies else jnp.float32(0.0))
    c, r = ops.fused_compress_delta(d, tau, bits=spec.bits,
                                    topk=spec.sparsifies,
                                    with_residual=True, interpret=True)
    return np.asarray(c), np.asarray(r)


def _check_parity(d, spec):
    c_np, r_np = comp.numpy_compress(d, spec)
    c_ref, r_ref = comp.reference_compress(jnp.asarray(d), spec)
    c_k, r_k = _kernel_compress(d, spec)
    np.testing.assert_array_equal(c_np, np.asarray(c_ref))
    np.testing.assert_array_equal(r_np, np.asarray(r_ref))
    np.testing.assert_array_equal(c_np, c_k)
    np.testing.assert_array_equal(r_np, r_k)
    np.testing.assert_array_equal(r_np, d.astype(np.float32) - c_np)
    return c_np, r_np


def _delta(n, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    d = (rng.normal(size=n) * scale).astype(np.float32)
    if n >= 256:
        d[128:256] = 0.0        # a whole zero block → guarded divide path
    return d


@pytest.mark.parametrize("n", [1, 7, 128, 1000, 1024, 5000])
@pytest.mark.parametrize("bits", [8, 16, 32])
@pytest.mark.parametrize("density", [1.0, 0.25])
def test_kernel_matches_numpy_oracle(n, bits, density):
    spec = CompressionSpec(bits=bits, density=density)
    if spec.identity:
        pytest.skip("identity spec never reaches the kernel")
    d = _delta(n, seed=n + bits)
    c, _ = _check_parity(d, spec)
    if spec.sparsifies:
        # the threshold mask keeps AT LEAST k elements (ties kept) and
        # only elements at/above the k-th largest magnitude
        k = comp.topk_k(spec, n)
        tau = np.partition(np.abs(d), n - k)[n - k]
        kept = np.flatnonzero(c)
        assert len(np.flatnonzero(np.abs(d) >= tau)) >= k
        assert np.all(np.abs(d[kept]) >= tau)


def test_quantization_error_bounded_by_half_scale():
    """SCALE_PAD rounds the bf16 wire scale UP, so no value clips and
    the per-element error is ≤ scale/2 (round-half-even)."""
    from repro.kernels.fused_update import LANES, QMAX, SCALE_PAD
    import ml_dtypes
    for bits in (8, 16):
        spec = CompressionSpec(bits=bits)
        d = _delta(1000, seed=bits, scale=3.0)
        c, _ = _check_parity(d, spec)
        rows = -(-1000 // LANES)
        xb = np.pad(d, (0, rows * LANES - 1000)).reshape(rows, LANES)
        amax = np.max(np.abs(xb), axis=-1, keepdims=True)
        scale = ((amax / np.float32(QMAX[bits])) * np.float32(SCALE_PAD)) \
            .astype(ml_dtypes.bfloat16).astype(np.float32)
        err = np.abs(xb - np.pad(c, (0, rows * LANES - 1000))
                     .reshape(rows, LANES))
        assert np.all(err <= 0.5 * scale * (1 + 1e-6) + 1e-30)
        assert np.all(scale * np.float32(QMAX[bits]) >= amax)  # no clipping


def test_zero_delta_compresses_to_zero():
    for spec in (CompressionSpec(bits=8), CompressionSpec(density=0.5),
                 CompressionSpec(bits=16, density=0.5)):
        c, r = _check_parity(np.zeros(300, np.float32), spec)
        assert not c.any() and not r.any()


def test_padded_buffer_with_logical_k_is_exact():
    """Zero padding changes neither τ nor block scales: compressing the
    padded buffer with a LOGICAL k equals compressing the logical
    prefix (the invariant the padded engine carries rely on)."""
    spec = CompressionSpec(bits=8, density=0.5)
    n, padded_n = 700, 1024
    d = _delta(n, seed=3)
    dp_ = np.zeros(padded_n, np.float32)
    dp_[:n] = d
    c_logical, _ = comp.numpy_compress(d, spec)
    c_padded, _ = comp.numpy_compress(dp_, spec, logical_size=n)
    np.testing.assert_array_equal(c_padded[:n], c_logical)
    assert not c_padded[n:].any()


def test_error_feedback_mass_is_deferred_not_lost():
    """T rounds of compress(δ + r) with a CONSTANT per-round delta: the
    cumulative compressed sum tracks T·δ with error = |r_T|, bounded
    independent of T — without EF the sparsification error grows ∝ T."""
    spec = CompressionSpec(bits=8, density=0.25, error_feedback=True)
    d = _delta(512, seed=5)
    r = np.zeros_like(d)
    total = np.zeros_like(d)
    T = 12
    for _ in range(T):
        c, r = comp.numpy_compress(d + r, spec)
        total += c
    ef_err = np.max(np.abs(total - T * d))
    # Σc telescopes to T·δ − r_T (up to f32 rounding of the running sum)
    np.testing.assert_allclose(total, T * d - r, atol=1e-5, rtol=0)
    c1, _ = comp.numpy_compress(d, spec)
    no_ef_err = T * np.max(np.abs(d - c1))
    assert ef_err < 0.25 * no_ef_err


# ---------------------------------------------------------------------------
# hypothesis sweeps (skipped cleanly when hypothesis is absent)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(n=hst.integers(1, 2048),
           bits=hst.sampled_from([8, 16, 32]),
           density=hst.floats(0.01, 1.0),
           seed=hst.integers(0, 2**31 - 1),
           scale_pow=hst.integers(-8, 8))
    def test_hypothesis_roundtrip_parity(n, bits, density, seed, scale_pow):
        spec = CompressionSpec(bits=bits, density=density)
        d = _delta(n, seed=seed, scale=float(2.0 ** scale_pow))
        if spec.identity:
            c, r = comp.numpy_compress(d, spec)
            np.testing.assert_array_equal(c, d.astype(np.float32))
            assert not r.any()
            return
        _check_parity(d, spec)

    @settings(max_examples=40, deadline=None)
    @given(n=hst.integers(1, 1024), bits=hst.sampled_from([8, 16]),
           seed=hst.integers(0, 2**31 - 1))
    def test_hypothesis_quantization_error_half_scale(n, bits, seed):
        from repro.kernels.fused_update import LANES, QMAX, SCALE_PAD
        import ml_dtypes
        spec = CompressionSpec(bits=bits)
        d = _delta(n, seed=seed)
        c, _ = comp.numpy_compress(d, spec)
        rows = -(-n // LANES)
        xb = np.pad(d, (0, rows * LANES - n)).reshape(rows, LANES)
        amax = np.max(np.abs(xb), axis=-1, keepdims=True)
        scale = ((amax / np.float32(QMAX[bits])) * np.float32(SCALE_PAD)) \
            .astype(ml_dtypes.bfloat16).astype(np.float32)
        err = np.abs(xb - np.pad(c, (0, rows * LANES - n)).reshape(rows,
                                                                   LANES))
        assert np.all(err <= 0.5 * scale * (1 + 1e-6) + 1e-30)
else:                                                 # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_hypothesis_roundtrip_parity():
        pass


# ---------------------------------------------------------------------------
# wire accounting — closed forms and the ledger
# ---------------------------------------------------------------------------

def test_payload_bytes_closed_forms():
    assert comp.payload_bytes(None, (1000,)) == 4000
    assert comp.payload_bytes(CompressionSpec(), (1000,)) == 4000
    # int8 dense: 1 byte/elt + one bf16 scale per 128-lane block
    assert comp.payload_bytes(CompressionSpec(bits=8), (1000,)) == \
        1000 + 2 * 8
    # top-k: bits/8 per kept + int32 coordinate per kept
    s = CompressionSpec(density=0.25)
    assert comp.payload_bytes(s, (1000,)) == 250 * 4 + 250 * 4
    both = CompressionSpec(bits=8, density=0.25)
    assert comp.payload_bytes(both, (1000,)) == 250 + 250 * 4 + 2 * 8
    assert comp.payload_bytes(both, (0, 1000)) == \
        comp.payload_bytes(both, (1000,))


def test_int8_dense_ratio_clears_the_gate():
    """bf16 block scales keep the int8 dense upload ratio at
    4/(1 + 2/128) ≈ 3.94 ≥ 3.9 — f32 scales would cap it at 3.88."""
    ratio = comp.payload_ratio(CompressionSpec(bits=8), (1 << 20,))
    assert ratio >= 3.9, ratio


def test_topk_k_edges():
    assert comp.topk_k(CompressionSpec(density=1e-9), 1000) == 1
    assert comp.topk_k(CompressionSpec(density=1.0), 1000) == 1000
    assert comp.topk_k(CompressionSpec(density=0.5), 3) == 2
    assert comp.topk_k(CompressionSpec(density=0.5), 1) == 1


# ---------------------------------------------------------------------------
# construction-time validation
# ---------------------------------------------------------------------------

LOSSY = CompressionSpec(bits=8)


def _lspec(**kw):
    return LocalSpec(n_steps=1, batch_size=4, lr=0.1, **kw)


def test_spec_rejects_bad_bits():
    with pytest.raises(ValueError, match="bits must be one of 8\\|16\\|32"):
        CompressionSpec(bits=12)


@pytest.mark.parametrize("density", [0.0, -0.1, 1.5])
def test_spec_rejects_bad_density(density):
    with pytest.raises(ValueError, match="density must be in \\(0, 1\\]"):
        CompressionSpec(density=density)


def test_spec_rejects_ef_on_identity():
    with pytest.raises(ValueError, match="error_feedback=True needs lossy"):
        CompressionSpec(error_feedback=True)


def test_local_spec_rejects_secure_agg_plus_lossy():
    with pytest.raises(ValueError, match="pairwise masks cancel only"):
        _lspec(secure_agg=True, compression=LOSSY)


def test_local_spec_rejects_dp_plus_lossy():
    with pytest.raises(ValueError, match="dp is incompatible"):
        _lspec(dp=DPSpec(1.0, 0.1), compression=LOSSY)


def test_fl_config_rejects_invalid_combo_at_construction():
    with pytest.raises(ValueError, match="pairwise masks cancel only"):
        FLConfig(secure_agg=True, compression=LOSSY)


def test_relay_strategy_rejects_lossy_compression():
    with pytest.raises(ValueError, match="P2 round deltas only"):
        RelayStrategy(spec=_lspec(compression=LOSSY))


def test_pod_spec_rejects_tree_plus_lossy():
    with pytest.raises(ValueError, match="fused flat path"):
        PodFLSpec(update_impl="tree", compression=LOSSY)
    with pytest.raises(ValueError, match="fused flat path"):
        PodAggregateStrategy(spec=_lspec(compression=LOSSY),
                             mesh=make_host_mesh())


# ---------------------------------------------------------------------------
# engine-level parity (host)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    task = vision_task("mlp", in_ch=1, seed_kwargs={"img": 8, "d_hidden": 16})
    rng = np.random.default_rng(SEED)
    per = 16
    x = rng.normal(size=(N_CLIENTS, per, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(N_CLIENTS, per)).astype(np.int32)
    data = FederatedDataset(x=x, y=y,
                            n_real=np.full((N_CLIENTS,), per, np.int32),
                            test_x=x[0], test_y=y[0], n_classes=10,
                            name="compression-test")
    return task, data


def _run_host(task, data, *, compression=None, impl="fused_interpret",
              algo="fedavg", store=None, rounds=6, ledger=None,
              overlap=False):
    spec = LocalSpec(n_steps=2, batch_size=4, lr=0.05,
                     variant="scaffold" if algo == "scaffold" else "plain",
                     update_impl=impl, compression=compression)
    kw = {"state_store": store} if store is not None else {}
    strat = AggregateStrategy(spec=spec, algorithm=algo,
                              participation=0.25, **kw)
    sched = RoundSchedule(rounds=rounds, lr_decay=1.0, eval_every=0,
                          seed=SEED, chunk_size=2, sampling="host",
                          host_rng_offset=17, overlap=overlap)
    return run_rounds(task, data, strat, sched, ledger=ledger)


def _assert_same_run(a, b, *, bitwise=True, state=False):
    la = [h["local_loss"] for h in a.history]
    lb = [h["local_loss"] for h in b.history]
    if bitwise:
        np.testing.assert_array_equal(la, lb)
    else:
        np.testing.assert_allclose(la, lb, atol=5e-5, rtol=0)
    for x, y in zip(jax.tree_util.tree_leaves(a.params),
                    jax.tree_util.tree_leaves(b.params)):
        if bitwise:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        else:
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=5e-5, rtol=0)
    if state:
        for x, y in zip(jax.tree_util.tree_leaves(a.algo_state),
                        jax.tree_util.tree_leaves(b.algo_state)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("impl", ["fused_interpret", "tree"])
def test_identity_compression_is_baseline_bitwise(setup, impl):
    task, data = setup
    base = _run_host(task, data, compression=None, impl=impl)
    ident = _run_host(task, data, compression=CompressionSpec(), impl=impl)
    _assert_same_run(base, ident, state=True)


@pytest.mark.parametrize("spec", [
    CompressionSpec(bits=8),
    CompressionSpec(density=0.5),
    CompressionSpec(bits=8, density=0.5, error_feedback=True),
], ids=["int8", "topk", "int8+topk+ef"])
def test_lossy_fused_matches_tree_bitwise(setup, spec):
    """Compression is defined on the flat buckets, so the tree path (the
    parity oracle, via reference_compress) and the fused kernel path
    agree BITWISE — same blocks, same accumulation order."""
    task, data = setup
    fused = _run_host(task, data, compression=spec)
    tree = _run_host(task, data, compression=spec, impl="tree")
    _assert_same_run(fused, tree)


@pytest.mark.parametrize("algo", ["fedavg", "scaffold"])
def test_ef_residuals_sparse_equals_dense_bitwise(setup, algo):
    """EF residual rows ride the ClientStateStore contract: the sparse
    active-set table (capacity forcing eviction + spill + refault across
    every dispatch) carries them bitwise-identically to the dense
    stacks, sync and overlapped."""
    task, data = setup
    spec = CompressionSpec(bits=8, density=0.5, error_feedback=True)
    dense = _run_host(task, data, compression=spec, algo=algo,
                      store=DenseClientStateStore())
    assert "ef_residuals" in dense.algo_state
    for overlap in (False, True):
        sparse = _run_host(task, data, compression=spec, algo=algo,
                           store=SparseClientStateStore(capacity=4),
                           overlap=overlap)
        _assert_same_run(dense, sparse)


def test_ef_fedavg_tracks_uncompressed(setup):
    """int8+EF FedAvg stays close to the uncompressed run — quantization
    error is ≤ half a block scale per element and EF defers the rest."""
    task, data = setup
    base = _run_host(task, data, compression=None)
    ef = _run_host(task, data,
                   compression=CompressionSpec(bits=8, error_feedback=True))
    np.testing.assert_allclose([h["local_loss"] for h in base.history],
                               [h["local_loss"] for h in ef.history],
                               atol=0.05, rtol=0)
    for a, b in zip(jax.tree_util.tree_leaves(base.params),
                    jax.tree_util.tree_leaves(ef.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3, rtol=0)


def test_ledger_matches_closed_form_and_clears_ratio_gate(setup):
    task, data = setup
    spec = CompressionSpec(bits=8)
    led = CommLedger()
    rounds = 4
    _run_host(task, data, compression=spec, rounds=rounds, ledger=led)
    view = host_flat_ops(task, True).view
    sizes = tuple(view.buffer_sizes.values())
    payload = comp.payload_bytes(spec, sizes)
    x = led.summary()["model_bytes"]
    assert x == 4 * sum(sizes)          # f32 model, logical bytes
    k = 2                               # participation 0.25 of 8 clients
    assert led.p2_bytes == rounds * acc.compressed_round_bytes(
        "fedavg", k, x, payload)
    assert led.p2_upload_bytes == rounds * k * payload
    assert led.summary()["payload_ratio"] == x / payload
    assert led.summary()["payload_ratio"] >= 3.9


# ---------------------------------------------------------------------------
# (slow) pod: compressed hierarchical == compressed sequential, 16 devices
# ---------------------------------------------------------------------------

_COMPRESS_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax
    import numpy as np
    from repro.data.federated import FederatedDataset
    from repro.fl.compression import CompressionSpec
    from repro.fl.engine import RoundSchedule, run_rounds
    from repro.fl.local import LocalSpec
    from repro.fl.pod import PodAggregateStrategy, ShardedSparseClientStateStore
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
                            name="compress-pod")
    sched = RoundSchedule(rounds=4, lr_decay=1.0, eval_every=0, seed=0,
                          chunk_size=2, sampling="host", host_rng_offset=17)

    def run(aggregation, compression):
        spec = LocalSpec(n_steps=2, batch_size=4, lr=0.05, variant="plain",
                         update_impl="fused_interpret",
                         compression=compression)
        strat = PodAggregateStrategy(
            spec=spec, algorithm="fedavg", mesh=mesh, clients_per_round=4,
            aggregation=aggregation, n_pods=4,
            state_store=ShardedSparseClientStateStore(capacity=8, mesh=mesh))
        return run_rounds(task, data, strat, sched)

    comp = CompressionSpec(bits=8, density=0.5, error_feedback=True)
    seq = run("sequential", comp)
    hier = run("hierarchical", comp)     # G=4 sharded lanes + one psum
    np.testing.assert_allclose(
        [h["local_loss"] for h in seq.history],
        [h["local_loss"] for h in hier.history], atol=5e-5, rtol=0)
    for a, b in zip(jax.tree_util.tree_leaves(seq.params),
                    jax.tree_util.tree_leaves(hier.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=0)

    # identity spec == baseline, BITWISE, on the sharded backend too
    base = run("hierarchical", None)
    ident = run("hierarchical", CompressionSpec())
    np.testing.assert_array_equal(
        [h["local_loss"] for h in base.history],
        [h["local_loss"] for h in ident.history])
    for a, b in zip(jax.tree_util.tree_leaves(base.params),
                    jax.tree_util.tree_leaves(ident.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("POD_COMPRESS_SUBPROCESS_OK")
""")


@pytest.mark.slow
def test_pod_compressed_hierarchical_matches_sequential_16dev_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _COMPRESS_SUBPROCESS_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "POD_COMPRESS_SUBPROCESS_OK" in out.stdout
