"""The round engine's spans and phases in a trace: the span readers and
the phase reduction on a small trace recorded on a TPU v5e
(``data/trace_scopes_v5e.json``), each against a count made here by
brute force, the same trace without the engine's names reading None or
no phase, and the loader and its command line on real profiler
traces."""
import json
import pathlib
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run, scopes, xtrace

DATA = pathlib.Path(__file__).parent / "data" / "trace_scopes_v5e.json"
METRICS = ("engine_pack_ms_per_call", "engine_unpack_ms_per_call")


def _ctx(fx, window, named=True):
    """A reader's context over the recorded trace; ``named=False`` is
    the same trace from a program without spans or scopes."""
    ops = [xtrace.Event(*o) for o in fx["device_ops"]]
    host = [xtrace.Event(*h) for h in fx["host"]] if named else []
    names = fx["op_names"] if named else {
        m: {i: re.sub(r"fl_[a-z_]+", "x", op) for i, op in ops_.items()}
        for m, ops_ in fx["op_names"].items()}
    phase_ops, runs = scopes.phase_ops(
        [xtrace.Event(*m) for m in fx["modules"]], ops, names)
    return types.SimpleNamespace(
        trace=xtrace.Trace({0: ops}, host), window=tuple(window),
        rounds=fx["rounds"], scopes=scopes.Phases(phase_ops, runs))


@pytest.fixture(scope="module")
def fx():
    return json.loads(DATA.read_text())


def _read(name, ctx):
    return run.load_module([run.BENCH], "metrics", name).read(ctx)


def _own_by_brute_force(fx):
    """Per nanosecond of the window, the phase of the innermost round
    program op running (the last-starting of those that cover it)."""
    t0, t1 = (int(x) for x in fx["window"])
    mods = [m for m in fx["modules"] if m[0] in fx["op_names"]]
    label = np.full(t1 - t0, -1)
    kinds = list(scopes.PHASES) + [scopes.UNSCOPED]
    for name, s, d in sorted(fx["device_ops"], key=lambda o: (o[1], -o[2])):
        if not any(m[1] <= s and s + d <= m[1] + m[2] for m in mods):
            continue
        a, b = max(int(s), t0) - t0, min(int(s + d), t1) - t0
        instr = xtrace.op_name(xtrace.Event(name, s, d))
        op = next(iter(fx["op_names"].values())).get(instr, "")
        if b > a:
            label[a:b] = kinds.index(scopes.phase_of(op))
    return {k: float((label == i).sum()) * 1e-6 for i, k in enumerate(kinds)}


def test_device_phases_match_brute_force(fx):
    ctx = _ctx(fx, fx["window"])
    want = _own_by_brute_force(fx)
    got = ctx.scopes.self_ms(ctx.window)
    for k, v in want.items():
        assert got.get(k, 0.0) == pytest.approx(v, abs=5e-6), k
    # every phase of the lenet5 round shows in this stretch
    assert {k for k, v in want.items() if v > 0} == set(scopes.PHASES) - {
        "fl_server_update"} | {scopes.UNSCOPED}


def test_phases_sum_to_the_program_run(fx):
    ctx = _ctx(fx, fx["window"])
    own = ctx.scopes.self_ms(ctx.window)
    assert sum(own.values()) == pytest.approx(ctx.scopes.run_ms(ctx.window),
                                              rel=0.02)


def test_span_readers_match_the_spans(fx):
    ctx = _ctx(fx, fx["call"])
    ms = {}
    for name, _, d in fx["host"]:
        ms[name] = ms.get(name, 0.0) + d * 1e-6
    assert _read("engine_pack_ms_per_call", ctx) == pytest.approx(
        ms["engine.pack"] + ms["engine.prepare_data"])
    assert _read("engine_unpack_ms_per_call", ctx) == pytest.approx(
        ms["engine.unpack"])
    # a window holding no call reads nothing
    assert _read("engine_unpack_ms_per_call",
                 _ctx(fx, (0.0, fx["call"][0]))) is None


def test_idle_inside_each_span(fx):
    ctx = _ctx(fx, fx["window"])
    idle = scopes.idle_by_span(ctx.trace, ctx.window)
    t0, t1 = (int(x) for x in ctx.window)
    cov = np.zeros(t1 - t0, bool)
    for _, s, d in fx["device_ops"]:
        a, b = max(int(s), t0) - t0, min(int(s + d), t1) - t0
        cov[max(a, 0):max(b, 0)] = True
    for name, s, d in fx["host"]:
        if s >= t0 and s + d <= t1:
            want = (~cov[int(s) - t0:int(s + d) - t0]).sum() * 1e-6
            assert idle[name][2] == pytest.approx(want, abs=1e-5), name
    # the gap between the two dispatches: history and planning on the
    # host, nothing on the device
    owners = scopes.gap_owners(ctx.trace, ctx.window, 1)
    assert owners[0][0] == "engine.plan" and owners[0][2] > 0.010


@pytest.mark.parametrize("metric", METRICS + ("phases",))
def test_a_program_without_names_reads_none(fx, metric):
    if metric == "phases":
        def named(ctx):
            own = ctx.scopes.self_ms(ctx.window)
            return [k for k, v in own.items() if k in scopes.PHASES and v]
        assert named(_ctx(fx, fx["window"], named=False)) == []
        assert named(_ctx(fx, fx["window"]))
        return
    assert _read(metric, _ctx(fx, fx["call"], named=False)) is None
    assert _read(metric, _ctx(fx, fx["call"])) is not None


@pytest.mark.parametrize("op_name,phase", [
    ("jit(chunk)/while/body/closed_call/fl_eval/cond/branch_1_fun/dot",
     "fl_eval"),
    ("jit(chunk)/while/body/vmap()/fl_fwd_bwd/transpose(jvp(fl_unflatten))"
     "/pad", "fl_unflatten"),
    ("jit(chunk)/fl_fwd_bwd/transpose(fl_fwd_bwd)/jvp(fl_unflatten)/"
     "fl_unflatten/shard_map/jit(_pad)/pad", "fl_unflatten"),
    ("jit(chunk)/fl_step_tail/jit(fused_local_step)", "fl_step_tail"),
    ("jit(chunk)/while/body/closed_call/add", "unscoped"),
    ("jit(chunk)/fl_fwd_bwd/jvp()/fl_not_a_phase/mul", "fl_fwd_bwd"),
    ("", "unscoped"),
])
def test_phase_is_the_innermost_fl_scope(op_name, phase):
    assert scopes.phase_of(op_name) == phase


def _profile(tmp_path, fn, *args, span="bench.window"):
    fn(*args)[0].block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(span):
        fn(*args)[0].block_until_ready()
    jax.profiler.stop_trace()
    return str(tmp_path)


def _chunk(scoped):
    def chunk(w, x):
        def step(c, _):
            ctx = jax.named_scope("fl_fwd_bwd") if scoped else \
                jax.named_scope("other")
            with ctx:
                l, g = jax.value_and_grad(
                    lambda c: jnp.sum(jnp.tanh(x @ c.reshape(16, 16))))(c)
            return c - 0.1 * g, l
        return jax.lax.scan(step, w, None, length=3)
    return jax.jit(chunk)


def test_load_reads_op_names_from_a_profiler_trace(tmp_path):
    fn, args = _chunk(True), (jnp.ones(256), jnp.ones((8, 16)))
    d = _profile(tmp_path, fn, *args)
    raw = pathlib.Path(xtrace.find_xplane(d)).read_bytes()
    # every round program alive in the process is in the trace's metadata
    names = {m: o for m, o in scopes._hlo_op_names(raw).items()
             if m.startswith(scopes.PROGRAM)}
    # the wire-format reader agrees with the compiled program's text
    text = fn.lower(*args).compile().as_text()
    want = dict(re.findall(r'%(\S+) = .*op_name="([^"]*)"', text))
    assert want and any({k: got.get(k) for k in want} == want
                        for got in names.values())
    ph = scopes.load(d)
    tr = xtrace.load(d)
    win = (tr.annotation("bench.window").start_ns,
           tr.annotation("bench.window").end_ns)
    own = ph.self_ms(win)
    assert own.get("fl_fwd_bwd", 0) > 0


def test_load_without_scopes_reads_none(tmp_path):
    d = _profile(tmp_path, _chunk(False), jnp.ones(256), jnp.ones((8, 16)))
    tr = xtrace.load(d)
    win = (tr.annotation("bench.window").start_ns,
           tr.annotation("bench.window").end_ns)
    ctx = types.SimpleNamespace(trace=tr, window=win, rounds=3,
                                scopes=scopes.load(d))
    for metric in METRICS:
        assert _read(metric, ctx) is None, metric
    assert not set(ctx.scopes.self_ms(win)) & set(scopes.PHASES)


def test_command_line_prints_spans_and_phases(tmp_path, capsys):
    d = _profile(tmp_path, _chunk(True), jnp.ones(256), jnp.ones((8, 16)),
                 span="engine.drain")
    assert scopes.main([d, "--rounds", "3"]) == 0
    out = capsys.readouterr().out
    row = next(line for line in out.splitlines() if "engine.drain" in line)
    assert row.split()[1] == "1"                    # one span, counted once
    assert "device ms per round by phase: fl_fwd_bwd" in out


def test_command_line_needs_engine_spans(tmp_path, capsys):
    d = _profile(tmp_path, _chunk(True), jnp.ones(256), jnp.ones((8, 16)))
    assert scopes.main([d]) == 1
    assert "no engine spans" in capsys.readouterr().err
