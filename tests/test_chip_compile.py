"""The fused update kernels, compiled for a described TPU v5e chip.

Each test AOT-compiles one kernel of ``repro.kernels.fused_update`` with
Mosaic (``interpret=False``) at qwen1.5-0.5b's carried flat length —
the length the engine pads its flat carries to — for one chip of a
described ``v5e:2x2`` topology: no chip is attached, the TPU compiler
runs here.  A compile that passes is not a chip run; it proves the
kernels tile, fit VMEM and lower at model width.

Each test also asserts that the compiled program's temporaries stay
below one f32 buffer of that length: a kernel over a carried buffer
must not copy it (an operand off the compiled grid would be padded into
a whole-model temporary per call).

The topology, the sharding and the shapes are built inside fixtures,
never at import: only one process may load the TPU compiler's library
at a time, and every test worker imports this file.  JAX's persistent
compilation cache is off around these compiles (a compile for a
described chip is written to it but cannot be read back without one).
"""
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops

KERNELS = ["local_step", "weighted_delta_k4", "weighted_delta_k16",
           "delta_accum", "delta_accum_no_p", "compress_delta_topk8",
           "dp_clip_noise", "server_update_momentum", "server_update_adam"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                   # pragma: no cover
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def qwen_len():
    """qwen1.5-0.5b's flat length as the engine carries it (one bf16
    bucket, padded by FlatParamOps)."""
    from repro.configs import get_config
    from repro.fl.local import FlatParamOps
    from repro.fl.task import lm_task
    from repro.utils.flatten import FlatView

    task = lm_task(get_config("qwen1.5-0.5b"))
    p_specs = jax.eval_shape(task.init, jax.random.PRNGKey(0))
    view = FlatView.of(p_specs)
    assert view.buffer_sizes == {"bfloat16": 463_987_712}
    (n,) = FlatParamOps(view=view, interpret=False).padded_sizes.values()
    return n


def _lower(name, n, sharding):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    p = sds((n,), jnp.bfloat16)         # params, client ends
    d = sds((n,), jnp.float32)          # deltas, moments, momentum
    if name == "local_step":
        return ops.fused_local_step.lower(
            p, p, d, None, 0.5, 0.01, momentum=0.9, interpret=False)
    if name.startswith("weighted_delta"):
        # the kernel alone, on a stack that arrives in its own tiles; the
        # host round program hands it a (K, n) stack in another layout
        # (test_host_round_runs_kernels_on_the_carried_grid)
        K = int(name.rsplit("k", 1)[1])
        w = sds((K,), jnp.float32)
        return jax.jit(lambda st, p, w: ops.fused_weighted_delta(
            st.reshape(K, n), p, w, interpret=False)).lower(
            sds((K, n // 128, 128), jnp.bfloat16), p, w)
    if name == "delta_accum":
        return ops.fused_delta_accum.lower(d, p, p, 0.25, interpret=False)
    if name == "delta_accum_no_p":
        return ops.fused_delta_accum.lower(d, p, None, 0.25, interpret=False)
    if name == "compress_delta_topk8":
        return ops.fused_compress_delta.lower(
            d, 0.1, bits=8, topk=True, with_residual=True, interpret=False)
    if name == "dp_clip_noise":
        return ops.fused_dp_clip_noise.lower(d, d, 0.7, 0.05,
                                             interpret=False)
    opt = name.rsplit("_", 1)[1]
    moments = (d,) if opt == "momentum" else (d, d)
    scalars = (0.01,) if opt == "momentum" else (0.01, 0.1, 0.01)
    return ops.fused_server_update.lower(p, d, moments, scalars, opt=opt,
                                         interpret=False)


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e_without_copies(name, one_chip, qwen_len):
    compiled = _lower(name, qwen_len, one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 4 * qwen_len, (
        f"{name}: {temp} bytes of temporaries at length {qwen_len} — an "
        f"operand is being copied to reach the kernel grid")


def test_qwen_carry_is_on_the_compiled_grid(qwen_len):
    from repro.kernels.fused_update import (
        DEFAULT_BLOCK_ROWS, LANES, _grid_rows)
    for interpret in (False, True):
        rows_p, _ = _grid_rows(qwen_len, DEFAULT_BLOCK_ROWS, interpret)
        assert rows_p * LANES == qwen_len
    # the pad is a rounding, not a second model
    assert qwen_len - 463_987_712 < DEFAULT_BLOCK_ROWS * LANES



def test_host_round_runs_kernels_on_the_carried_grid(one_chip, qwen_len):
    """The host backend's round at qwen1.5-0.5b's flat length, K=4: the
    vmapped flat local run (unflatten, backward, step-tail kernel) and
    the weighted-delta aggregate, with a loss that is cheap but reads
    every leaf.  Every kernel must run on the carried length itself (an
    operand off the compiled grid would be padded first), and the
    temporaries must stay at the step's three client stacks — params,
    gradients, new params.  A ``(K, n)`` scan carry is laid out in
    ``(K, 128)`` tiles and relaid for every step-tail call: two more
    stacks, 18.56 GB in all, over the chip's 16 GB.

    Still open (PERF.md, finding 4): the stack leaves the vmapped run as
    ``(K, n)`` and is relaid into the aggregate, two copies per round
    that do not raise the peak.
    """
    import re

    from repro.configs import get_config
    from repro.fl.engine import fused_aggregate
    from repro.fl.local import FlatParamOps, LocalSpec, make_local_fn
    from repro.fl.task import Task, lm_task
    from repro.kernels.fused_update import LANES
    from repro.utils.flatten import FlatView

    K = 4
    qwen = lm_task(get_config("qwen1.5-0.5b"))

    def loss_fn(params, bx, by, rng):
        scale = jnp.mean(bx.astype(jnp.float32))
        return scale * sum(jnp.mean(leaf.astype(jnp.float32) ** 2)
                           for leaf in jax.tree_util.tree_leaves(params))

    task = Task(name="qwen-leaves", kind="tokenlm", init=qwen.init,
                loss_fn=loss_fn, repr_fn=None, predict_fn=None)
    fops = FlatParamOps(view=FlatView.of(jax.eval_shape(
        qwen.init, jax.random.PRNGKey(0))), interpret=False)
    local = make_local_fn(task, LocalSpec(n_steps=2, batch_size=4, lr=0.01,
                                          update_impl="fused"),
                          flat_ops=fops)

    def round_fn(p, keys, cx, cy, w):
        ends, _ = jax.vmap(lambda k, x, y: local(
            k, p, {}, x, y, jnp.float32(1.0)))(keys, cx, cy)
        return fused_aggregate(fops, p, ends, w)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(round_fn).lower(
        {"bfloat16": sds((qwen_len,), jnp.bfloat16)},
        sds((K, 2), jnp.uint32), sds((K, 16, 8), jnp.int32),
        sds((K, 16, 8), jnp.int32), sds((K,), jnp.float32)).compile()
    kernels = [line.split(" custom-call(")[0]
               for line in compiled.as_text().splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(kernels) >= 2          # step tail (in the scan) + aggregate
    stacks = 3 * K * qwen_len * 2     # bf16 p, g and new p for K clients
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < stacks + 4 * qwen_len, (
        f"{temp} bytes of temporaries, {stacks} for the step's stacks: "
        f"a client stack is being copied")
    for result in kernels:
        for dims in re.findall(r"\[([\d,]+)\]", result):
            shape = [int(d) for d in dims.split(",")]
            if shape[-1] == LANES and shape[-2] > LANES:
                assert shape[-2] * LANES == qwen_len, (
                    f"kernel result {result.strip()} is off the carried "
                    f"length {qwen_len}")


def test_lenet5_weight_gradients_merge_the_clients(one_chip):
    """LeNet-5's local run as the paper's cell runs it: K=10 clients
    vmapped, 15 steps of 32 images of 32x32x3, the fused step tail.
    Autodiff's weight gradient of a per-client kernel is a convolution
    grouped over the clients (folded in as a dilated dimension) whose
    window is the whole input image, 32x32 for conv1 and 16x16 for
    conv2, with C_in rows a step; ``paper_models.conv2d`` leaves none of
    these: conv1 and conv2 each take one ungrouped convolution over the
    stacked channels of each group of ``client_groups`` (one group for
    conv1, two for conv2).  The temporaries stay under 96 MB (67.1 MB
    measured; 18.5 MB with the grouped form)."""
    import re

    from repro.fl.local import FlatParamOps, LocalSpec, make_local_fn
    from repro.models.paper_models import client_groups
    from repro.fl.task import vision_task
    from repro.utils.flatten import FlatView

    K, n_data = 10, 64
    task = vision_task("lenet5")
    fops = FlatParamOps(view=FlatView.of(jax.eval_shape(
        task.init, jax.random.PRNGKey(0))), interpret=False)
    local = make_local_fn(task, LocalSpec(n_steps=15, batch_size=32, lr=0.01,
                                          update_impl="fused"),
                          flat_ops=fops)

    def run(p, keys, cx, cy):
        return jax.vmap(lambda k, x, y: local(
            k, p, {}, x, y, jnp.float32(1.0)))(keys, cx, cy)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    (n,) = fops.padded_sizes.values()
    compiled = jax.jit(run).lower(
        {"float32": sds((n,), jnp.float32)}, sds((K, 2), jnp.uint32),
        sds((K, n_data, 32, 32, 3), jnp.float32),
        sds((K, n_data), jnp.int32)).compile()
    convs = [line for line in compiled.as_text().splitlines()
             if " convolution(" in line]
    windows = {}
    for line in convs:
        size = re.search(r"window=\{size=([\dx]+)", line).group(1)
        # two window dims of 16 or more: the window spans an image
        dims = sorted(int(d) for d in size.split("x"))
        windows.setdefault("lhs_dilate=" in line, []).append(
            dims[-2:] if len(dims) > 1 and dims[-2] >= 16 else None)
    grouped = [w for w in windows.get(True, []) if w]
    assert not grouped, convs
    merged = [w for w in windows.get(False, []) if w]
    assert sorted(merged) == sorted(
        [[32, 32]] * len(client_groups(K, 3, 6))
        + [[16, 16]] * len(client_groups(K, 6, 16))), convs
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 96 * 2 ** 20, f"{temp} bytes of temporaries"
