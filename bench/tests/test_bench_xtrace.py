"""The trace reduction, on a small trace recorded on a TPU v5e
(``data/trace_lenet_v5e.json``): busy and idle time, kernel time by
name, exclusive time by op kind and labelled gaps, each against a count
made here by brute force over every nanosecond."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import xtrace

DATA = pathlib.Path(__file__).parent / "data" / "trace_lenet_v5e.json"


@pytest.fixture(scope="module")
def rec():
    fx = json.loads(DATA.read_text())
    ops = [xtrace.Event(n, s, d) for n, s, d in fx["device_ops"]]
    host = [xtrace.Event(n, s, d) for n, s, d in fx["host"]]
    return xtrace.Trace({0: ops}, host), tuple(fx["window"])


def covered(events, window, inner=False):
    """Per-nanosecond coverage of ``window`` by ``events``."""
    t0, t1 = (int(x) for x in window)
    cov = np.zeros(t1 - t0, bool)
    for e in events:
        a = max(int(e.start_ns), t0) - t0
        b = min(int(e.end_ns), t1) - t0
        if b > a:
            cov[a:b] = True
    return cov


def test_busy_and_idle_match_brute_force(rec):
    tr, win = rec
    ops = tr.device_ops[0]
    cov = covered(ops, win)
    assert xtrace.busy_ns(ops, win) == pytest.approx(cov.sum(), abs=2)
    gaps = xtrace.idle_gaps(ops, win)
    assert sum(b - a for a, b in gaps) == pytest.approx((~cov).sum(), abs=2)
    # the window's idle share, as device_idle_share reads it
    idle = 1 - xtrace.busy_ns(ops, win) / (win[1] - win[0])
    assert 0.9 < idle < 1.0


def test_kernel_and_collective_time_by_name(rec):
    tr, win = rec
    ops = tr.device_ops[0]
    for kind in ("fused_weighted_delta", "fused_local_step", "all-reduce"):
        mine = [e for e in ops if e.name.startswith(f"%{kind}.")
                or e.name.startswith(f"%{kind} ")]
        want = sum(max(0, min(e.end_ns, win[1]) - max(e.start_ns, win[0]))
                   for e in mine)
        got = xtrace.summed_ns(xtrace.of_kind(ops, kind), win)
        assert got == pytest.approx(want)


def test_exclusive_time_sums_to_busy(rec):
    tr, win = rec
    ops = tr.device_ops[0]
    own = xtrace.self_ns(ops, win)
    assert sum(own.values()) == pytest.approx(xtrace.busy_ns(ops, win),
                                              rel=1e-9)
    top = xtrace.top_ops(ops, win, 3)
    assert [k for k, _ in top] == sorted(own, key=lambda k: -own[k])[:3]


def test_nested_ops_count_once():
    # a while of 100 ns running two ops of 30 and 20 ns
    ops = [xtrace.Event("%while.1 = (f32[]) while(", 0.0, 100.0),
           xtrace.Event("%fusion.2 = f32[8] fusion(", 10.0, 30.0),
           xtrace.Event("%fused_local_step.3 = f32[8] custom-call(",
                        50.0, 20.0)]
    assert xtrace.self_ns(ops, (0.0, 100.0)) == {
        "while": 50.0, "fusion": 30.0, "fused_local_step": 20.0}
    assert xtrace.busy_ns(ops, (0.0, 200.0)) == 100.0
    assert xtrace.idle_gaps(ops, (0.0, 200.0)) == [(100.0, 200.0)]


def test_longest_gap_is_labelled_by_the_host(rec):
    tr, win = rec
    gaps = xtrace.longest_gaps(tr, win, 2)
    assert gaps[0][1] > gaps[1][1] > 0
    assert gaps[0][0] == "PjitFunction(convert_element_type)"


def test_load_reads_a_profiler_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = xtrace.load(str(tmp_path))
    span = tr.annotation("bench.window")
    assert span is not None and span.dur_ns > 0
    assert tr.device_ops and all(e.dur_ns >= 0 for e in tr.device_ops[0])
