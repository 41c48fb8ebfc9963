"""Helpers of the tests that show the comparison deciding ``correct`` can
fail (``test_bench_faults_*.py``).

Each cell's harness runs end to end here on the CPU, at a tiny size and
with the chip check skipped, against the cell's own limits: once sound,
and once with the timed path broken underneath, for each fault a
one-chip training cell can have:

  unchanged   the round returns the params it was given
  half_batch  half of every batch left out, the mean loss taken over
              the rest
  doubled     the round's update, as it is produced, applied twice
  stale_key   the chunk program hands back the key it was given, so
              every dispatch of a call draws the first one's clients and
              batches again

and the control, the plain reference in the nearest precision below the
configuration's, put in the program's place, fails too, as do the
reference's own half-batch and stale-key variants that ``controls.py``
reads on the chip.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from bench import compare, run
from bench.controls import readings_of

# the tiny stand-ins of each cell: its own config or a narrower one, and
# its own traffic with fewer clients, rows and steps
TINY = {
    "qwen05b.p2-fedavg": dict(
        config={"hidden_size": 64, "intermediate_size": 128,
                "num_hidden_layers": 2, "num_attention_heads": 4,
                "num_key_value_heads": 4, "head_dim": 16,
                "vocab_size": 256},
        data={"clients": 4, "sequences_per_client": 8, "seq_len": 16},
        fl={"clients_per_round": 2, "local_steps": 2}),
    "lenet5.p2-fedavg": dict(
        config={},
        data={"clients": 10, "train": 400, "test": 64},
        fl={"participation": 0.2, "local_steps": 2, "batch_size": 8,
            "chunk_size": 2, "eval_every": 3, "eval_batch": 32}),
}


def tiny_cell(name: str) -> run.Cell:
    cell = run.load_cell(name)
    t = TINY[name]
    tr = dict(cell.traffic, data=dict(cell.traffic["data"], **t["data"]),
              fl=dict(cell.traffic["fl"], **t["fl"]))
    return dataclasses.replace(cell, config=dict(cell.config, **t["config"]),
                               traffic=tr)


@pytest.fixture
def fresh_programs():
    """Round programs traced anew for each run, so that a planted fault
    reaches them and does not outlive its test."""
    from repro.fl import engine
    engine._cached_chunk_fn.cache_clear()
    yield
    engine._cached_chunk_fn.cache_clear()


def plant(fault: str, monkeypatch) -> None:
    from repro.fl import engine, task
    from repro.fl.local import FlatParamOps
    from repro.models import transformer

    if fault == "unchanged":
        monkeypatch.setattr(FlatParamOps, "apply_delta",
                            lambda self, p, d: p)
        monkeypatch.setattr(engine, "fused_aggregate",
                            lambda fops, p, stacked, w: p)
    elif fault == "doubled":
        apply, agg = FlatParamOps.apply_delta, engine.fused_aggregate
        monkeypatch.setattr(
            FlatParamOps, "apply_delta",
            lambda self, p, d: apply(self, p, {k: 2 * v for k, v in d.items()}))

        def twice(fops, p, stacked, w):
            new = agg(fops, p, stacked, w)
            return {k: (2 * new[k].astype(jnp.float32) -
                        p[k].astype(jnp.float32)).astype(p[k].dtype)
                    for k in p}
        monkeypatch.setattr(engine, "fused_aggregate", twice)
    elif fault == "half_batch":
        xent, softmax_xent = transformer._xent, task._softmax_xent

        def half(fn):
            return lambda logits, labels, *a: fn(
                logits[:logits.shape[0] // 2], labels[:labels.shape[0] // 2],
                *a)
        monkeypatch.setattr(transformer, "_xent", half(xent))
        monkeypatch.setattr(task, "_softmax_xent", half(softmax_xent))
    elif fault == "stale_key":
        from repro.fl import pod
        for cls in (engine.HostBackend, pod.PodBackendMixin):
            jit_chunk = cls.jit_chunk

            def stale(self, chunk, task, n_clients, _jit=jit_chunk):
                def same_key(key, *args):
                    return (key,) + tuple(chunk(key, *args)[1:])
                return _jit(self, same_key, task, n_clients)
            monkeypatch.setattr(cls, "jit_chunk", stale)
    else:
        raise ValueError(fault)


def run_tiny(name: str, monkeypatch) -> dict:
    # the faults need the run's path, not a converged warm-up
    monkeypatch.setattr(run, "MAX_WARMUP", 1)
    return run.run_cell(tiny_cell(name), seed=2147483911, seconds=0.2,
                        trace=False, t_start=time.perf_counter(),
                        require_chip=False)


def check_sound(name: str, monkeypatch) -> None:
    out = run_tiny(name, monkeypatch)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["failed"] == 0 and out["attempted"] > 0


def check_fault(name: str, fault: str, monkeypatch) -> None:
    plant(fault, monkeypatch)
    out = run_tiny(name, monkeypatch)
    assert not out["correct"], out["checks"]


def check_control(name: str) -> None:
    cell = tiny_cell(name)
    h = run.Harness(cell, jax.devices()[:1])
    out = readings_of(h, 2147483913, controls=True)
    assert compare.judge(out["program"], cell.limits)[0], out["program"]
    assert not compare.judge(out["control"], cell.limits)[0], out["control"]
    for fault in ("half_batch", "stale_key"):
        assert not compare.judge(out[fault], cell.limits)[0], out[fault]
