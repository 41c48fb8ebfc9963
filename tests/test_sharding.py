"""Sharding-rule tests.

Host-mesh (1×1) checks run in-process; multi-device layout checks run in
a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=16 so
the main test process keeps its single-device view (the dry-run rule:
never set the flag globally).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced
from repro.launch.mesh import make_host_mesh
from repro.models.transformer import init_lm
from repro.sharding import rules


def test_host_mesh_pspecs_are_valid():
    cfg = get_reduced("qwen2-1.5b")
    params = jax.eval_shape(lambda k: init_lm(k, cfg), jax.random.PRNGKey(0))
    mesh = make_host_mesh()
    specs = rules.param_pspecs(params, mesh)
    # on a 1×1 mesh every axis must have been dropped (nothing divides >1)
    for s in jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(
            x, jax.sharding.PartitionSpec)):
        assert all(a is None for a in s), s


def test_batch_pspec_layouts_host():
    mesh = make_host_mesh()
    batch = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
    for layout in rules.LAYOUTS:
        specs = rules.batch_pspecs(batch, mesh, layout)
        assert isinstance(specs["tokens"], jax.sharding.PartitionSpec)


_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_reduced
    from repro.models.transformer import init_lm
    from repro.sharding import rules

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 4), ("data", "model"))
    cfg = get_reduced("tinyllama-1.1b")
    params = jax.eval_shape(lambda k: init_lm(k, cfg), jax.random.PRNGKey(0))

    # fsdp_tp: at least one leaf sharded on 'model' and one on 'data'
    specs = rules.param_pspecs(params, mesh, "fsdp_tp")
    leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    axes = {a for s in leaves for a in s if a is not None}
    flat_axes = set()
    for a in axes:
        if isinstance(a, tuple): flat_axes.update(a)
        else: flat_axes.add(a)
    assert "model" in flat_axes and "data" in flat_axes, flat_axes

    # fsdp_only: NO pure 'model' entries — only combined-axis sharding
    specs2 = rules.param_pspecs(params, mesh, "fsdp_only")
    leaves2 = jax.tree_util.tree_leaves(
        specs2, is_leaf=lambda x: isinstance(x, P))
    for s in leaves2:
        for a in s:
            assert a is None or isinstance(a, tuple), (s,)

    # batch: fsdp_only shards batch over BOTH axes
    batch = {"tokens": jax.ShapeDtypeStruct((32, 16), jnp.int32)}
    bs = rules.batch_pspecs(batch, mesh, "fsdp_only")["tokens"]
    assert bs[0] == ("data", "model"), bs

    # end-to-end: a loss lowers under both layouts on the 4x4 mesh
    from repro.models.transformer import lm_loss
    toks = jax.ShapeDtypeStruct((32, 16), jnp.int32)
    for layout in rules.LAYOUTS:
        p_sh = rules.param_shardings(params, mesh, layout)
        b_sh = rules.batch_shardings({"tokens": toks, "labels": toks},
                                     mesh, layout)
        with mesh:
            f = jax.jit(lambda p, b: lm_loss(p, cfg, b)[0],
                        in_shardings=(p_sh, b_sh))
            f.lower(params, {"tokens": toks, "labels": toks}).compile()
    print("SUBPROCESS_OK")
""")


@pytest.mark.slow
def test_multi_device_layouts_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SUBPROCESS_OK" in out.stdout
