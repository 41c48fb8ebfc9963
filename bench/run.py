"""The chip benchmark's entry point.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json``, on the machine it is started
on:

1. It fails, printing no result, unless JAX's first device is a TPU
   whose ``device_kind`` is in ``peaks.py`` and there are as many devices
   as the cell asks for.
2. Set-up (``setup_s``, timed from the start of this process to the
   start of the window's ``run_rounds`` call): the weights from the
   seed in one jitted call, the cell's data from the seed
   (``traffic.py``), and the program's round loop
   (``repro.fl.engine.run_rounds``) built as its users build it.  One
   call of the cell's first ``steps`` dispatches gives the results the
   comparison reads; warm-up calls of at least two dispatches follow
   until two in a row compile nothing, and the faster of those two
   sizes the window to a whole number of dispatches that fills
   ``--seconds``.
3. The window: one whole ``run_rounds`` call, timed by the host clock
   from the call to its last result, the call's packing of the params
   before its dispatch loop and its unpacking after included.
   ``rounds_per_s`` is its rounds over its wall time.  With ``--trace
   1`` the window is shorter (the traffic's ``trace_seconds``), runs
   under the profiler, and the run reports the per-layer metrics
   instead.
4. After the window: the peak device memory, then the plain reference
   runs the same rounds and ``compare.py`` decides ``correct``.

The last line of stdout is one JSON object: ``correct``, ``attempted``
and ``failed`` (rounds of the window; a round whose loss is not finite
failed), ``metrics``, ``device``, with ``--trace 1`` a ``breakdown``,
and last ``checks``, each compared number beside its limit.  The same
numbers are the last lines of stderr.  The compile cache is
``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                               # noqa: E402
import contextlib                                             # noqa: E402
import dataclasses                                            # noqa: E402
import gc                                                     # noqa: E402
import importlib.util                                         # noqa: E402
import json                                                   # noqa: E402
import math                                                   # noqa: E402
import pathlib                                                # noqa: E402
import shutil                                                 # noqa: E402
import sys                                                    # noqa: E402
import tempfile                                               # noqa: E402
from typing import Dict, List, Optional, Sequence             # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np                                            # noqa: E402

from bench import compare, peaks, traffic, xtrace             # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
WINDOW = "bench.window"          # the profiler span around the window
MAX_WARMUP = 5
# what the plain FedAvg reference computes; a traffic file that asks for
# more is refused before any work
SUPPORTED = {"algorithm": ("fedavg",), "server_opt": ("none", None),
             "momentum": (0, 0.0, None), "weight_decay": (0, 0.0, None),
             "grad_clip": (None,), "sampling": ("device",),
             "update_impl": ("fused", "tree")}


class NoChip(RuntimeError):
    """JAX sees no device the benchmark can measure."""


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files named there
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    dirs: Sequence[pathlib.Path]


def _find(dirs: Sequence[pathlib.Path], kind: str, name: str,
          ext: str) -> pathlib.Path:
    for d in dirs:
        p = d / kind / f"{name}{ext}"
        if p.is_file():
            return p
    raise FileNotFoundError(f"no {kind}/{name}{ext} under "
                            f"{[str(d) for d in dirs]}")


def _json(dirs, kind, name) -> dict:
    return json.loads(_find(dirs, kind, name, ".json").read_text())


_MODULES: Dict[pathlib.Path, object] = {}


def load_module(dirs: Sequence[pathlib.Path], kind: str, name: str):
    """``<dir>/<kind>/<name>.py`` from the first of ``dirs`` holding it."""
    path = _find(dirs, kind, name, ".py")
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            f"_bench_{kind}_{len(_MODULES)}", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def load_cell(name: str, benchmark: Optional[pathlib.Path] = None,
              dirs: Sequence[pathlib.Path] = (BENCH,)) -> Cell:
    spec = json.loads((benchmark or ROOT / "BENCHMARK.json").read_text())
    cells = [w for w in spec["workloads"] if w["name"] == name]
    if not cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[0]

    def ours(m):
        return name in m.get("workloads", [name])

    return Cell(name=name, chips=int(w["chips"]),
                config=_json(dirs, "configs", w["config"]),
                traffic=_json(dirs, "traffic", w["traffic"]),
                limits=_json(dirs, "cells", name),
                end_to_end=[m for m in spec["end_to_end"] if ours(m)],
                per_layer=[m for m in spec["per_layer"] if ours(m)],
                dirs=list(dirs))


# ---------------------------------------------------------------------------
# device, compile cache and compile events
# ---------------------------------------------------------------------------

def chip_devices(chips: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    print(f"jax {jax.__version__} platform={d0.platform} "
          f"device_kind={d0.device_kind} count={len(devs)}",
          file=sys.stderr, flush=True)
    if d0.platform != "tpu":
        raise NoChip(f"platform {d0.platform!r}: the benchmark measures a "
                     "TPU and has nothing to measure here")
    try:
        peaks.peaks_of(d0.device_kind)
    except ValueError as e:
        raise NoChip(str(e)) from None
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def use_compile_cache() -> None:
    """JAX's persistent cache at the checkout's fixed path, for every
    program however fast it compiles, so that a second run in the same
    checkout compiles nothing.  Eviction stays off: with it on, JAX
    reads a time stamp file beside every entry on every write, and one
    entry without one (left by another JAX process) fails every write."""
    import jax
    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileEvents:
    """JAX's compile events, counted by one pair of monitoring listeners
    per process: traces (one per jit-cache miss), compiles (requests to
    the backend that the persistent cache did not answer), and the
    seconds of tracing, lowering and compiling or loading from the
    persistent cache."""
    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"
    SECONDS = (TRACE, "/jax/core/compile/jaxpr_to_mlir_module_duration",
               BACKEND, "/jax/compilation_cache/cache_retrieval_time_sec")
    _installed: Optional["CompileEvents"] = None

    def __init__(self):
        self.traces = 0
        self.requests = 0
        self.hits = 0
        self.seconds = 0.0

    @property
    def compiles(self) -> int:
        return self.requests - self.hits

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.TRACE:
            self.traces += 1
        if event == self.BACKEND:
            self.requests += 1
        if event in self.SECONDS:
            self.seconds += duration

    def count(self, event: str, **_) -> None:
        if event == self.CACHE_HIT:
            self.hits += 1

    def snapshot(self) -> tuple:
        return self.traces, self.compiles

    @classmethod
    def listener(cls) -> "CompileEvents":
        if cls._installed is None:
            import jax
            cls._installed = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._installed)
            jax.monitoring.register_event_listener(cls._installed.count)
        return cls._installed


def peak_bytes(devs) -> Optional[int]:
    stats = [d.memory_stats() for d in devs]
    if not all(stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


# ---------------------------------------------------------------------------
# the program under test and its reference
# ---------------------------------------------------------------------------

def seeds_of(seed: int) -> Dict[str, int]:
    """31-bit seeds for every stream of a run, derived from ``--seed``
    (any size): JAX's keys keep only the low 32 bits of a seed."""
    s = (np.random.SeedSequence(seed).generate_state(8) >> 1).tolist()
    return {"init": s[0], "data": s[1], "steps": s[2], "warm": s[3],
            "window": s[4]}


def _fields(cls, d: dict) -> dict:
    names = {f.name for f in dataclasses.fields(cls)}
    return {k: v for k, v in d.items() if k in names}


def build_round_loop(t: dict, devs):
    """``(strategy, schedule)`` of the traffic's phase, built from its
    ``fl`` part the way ``run_pod_training`` and ``run_federated`` build
    theirs, on one chip."""
    fl = t["fl"]
    if len(devs) != 1:
        raise ValueError(f"the round loop is built for one chip, the cell "
                         f"asks for {len(devs)}")
    if t["backend"] == "pod":
        from repro.fl.pod import PodFLConfig, PodFLSpec
        from repro.launch.mesh import make_host_mesh
        cfg = PodFLConfig(mesh=make_host_mesh(),
                          spec=PodFLSpec(**_fields(PodFLSpec, fl)),
                          **_fields(PodFLConfig, dict(fl, rounds=1)))
    elif t["backend"] == "host":
        from repro.fl.simulation import FLConfig
        cfg = FLConfig(**_fields(FLConfig, dict(fl, rounds=1)))
    else:
        raise ValueError(f"unknown backend {t['backend']!r}")
    return cfg.strategy(), cfg.schedule()


class DispatchClock:
    """Watches the dispatches of one ``run_rounds`` call through the
    strategy's ``jit_chunk`` backend hook.  By default it only counts
    them.  Asked to, it keeps a copy of the params that the first
    dispatch returns (the comparison's first update), or times the
    dispatch loop from the first dispatch to the last one's result (a
    warm-up call, which sizes the window).  The window's own call runs
    with neither."""

    def __init__(self):
        self.start(0)

    def start(self, dispatches: int, keep_first: bool = False,
              time_loop: bool = False) -> None:
        self.expect, self.calls = dispatches, 0
        self.keep_first, self.time_loop = keep_first, time_loop
        self.first = None
        self.t_first = self.t_last = None

    def wrap(self, fn):
        import jax
        import jax.numpy as jnp

        def watched(*args):
            if self.calls == 0 and self.time_loop:
                self.t_first = time.perf_counter()
            out = fn(*args)
            self.calls += 1
            if self.calls == 1 and self.keep_first:
                # out: (key, params, algo_state, server_state, losses,
                # metrics); the params are donated to the next dispatch
                self.first = jax.block_until_ready(
                    jax.tree_util.tree_map(jnp.copy, out[1]))
            if self.calls == self.expect and self.time_loop:
                jax.block_until_ready(out)
                self.t_last = time.perf_counter()
            return out
        return watched

    @property
    def loop_s(self) -> float:
        return self.t_last - self.t_first


def clocked(strategy, clock: DispatchClock):
    """``strategy`` as it is, with its compiled chunk program watched by
    ``clock``."""
    cls = type(strategy)

    def jit_chunk(self, chunk, task, n_clients):
        return clock.wrap(cls.jit_chunk(self, chunk, task, n_clients))

    sub = type(f"Clocked{cls.__name__}", (cls,), {"jit_chunk": jit_chunk})
    return sub(**{f.name: getattr(strategy, f.name)
                  for f in dataclasses.fields(strategy)})


class Harness:
    """One cell's program, plain reference and data, built once per
    process; each seed then draws its own weights and data."""

    def __init__(self, cell: Cell, devs):
        import jax
        from repro.fl.engine import run_rounds

        self.cell, self.devs = cell, devs
        cfg, t = cell.config, cell.traffic
        traffic.check(t, SUPPORTED)
        self.ref = load_module(cell.dirs, "reference", cfg["family"])
        self.numerics = load_module(cell.dirs, "reference", "numerics")
        self.fedavg = load_module(cell.dirs, "reference", "fedavg")
        self.model = self.ref.from_config(cfg)
        self.num = self.numerics.numerics(
            cfg["precision"], cfg.get("matmul_precision", "highest"))
        self.task = load_module(cell.dirs, "programs", cfg["family"]).task(cfg)
        strategy, self.schedule = build_round_loop(t, devs)
        self.clock = DispatchClock()
        self.strategy = clocked(strategy, self.clock)
        self.fl, self.K = t["fl"], traffic.clients_per_round(t)
        self.chunk, self.steps = int(self.fl["chunk_size"]), int(t["steps"])
        self._run_rounds = run_rounds
        self.stacked = getattr(self.ref, "STACKED", ())
        self.norms = jax.jit(
            lambda a, b: self.numerics.diff_norms(a, b, self.stacked))
        self._init = jax.jit(self.init_params, static_argnums=1)
        self._ref_runs: Dict[tuple, object] = {}

    def init_params(self, key, num):
        return self.ref.init_params(key, self.model, num)

    def weights(self, sd: Dict[str, int]):
        """The seeded weights, in one jitted call on the device."""
        import jax
        p0 = self._init(jax.random.PRNGKey(sd["init"]), self.num)
        ok, why = self.numerics.same_layout(
            p0, jax.eval_shape(self.task.init, jax.random.PRNGKey(0)))
        if not ok:
            raise RuntimeError(f"the seeded weights do not fit the "
                               f"program's model: {why}")
        return p0

    def population(self, seed: int, sd: Dict[str, int]):
        """``(population, FederatedDataset)``: the cell's data, drawn from
        the seed, and the program's view of the same arrays."""
        import jax
        from repro.data.federated import FederatedDataset
        t = self.cell.traffic
        t = dict(t, data=dict(t["data"],
                              vocab=self.cell.config.get("vocab_size")))
        pop = traffic.make(t, seed, jax.random.PRNGKey(sd["data"]))
        return pop, FederatedDataset(
            x=pop.x, y=pop.y, n_real=pop.n_real, test_x=pop.test_x,
            test_y=pop.test_y, n_classes=pop.n_classes, name=self.cell.name)

    def call(self, data, rounds: int, seed: int, params,
             keep_first: bool = False, time_loop: bool = False):
        """One ``run_rounds`` call of the program, to its last result:
        ``(EngineResult, seconds of the whole call)``."""
        import jax
        sched = dataclasses.replace(self.schedule, rounds=rounds, seed=seed)
        self.clock.start(-(-rounds // self.chunk), keep_first, time_loop)
        t0 = time.perf_counter()
        res = self._run_rounds(self.task, data, self.strategy, sched,
                               init_params=params)
        jax.block_until_ready(res.params)
        return res, time.perf_counter() - t0

    def program_steps(self, data, p0, sd):
        """One call of the program's first ``steps`` dispatches from
        ``p0``, made as the window's call is made: its round losses, the
        first dispatch's update and the call's whole change (leaf
        norms), the params it ends at, and the call's seconds."""
        res, seconds = self.call(data, self.steps * self.chunk,
                                 sd["steps"], p0, keep_first=True)
        first = self.clock.first
        fops = self.strategy.flat_ops(self.task)
        if fops is not None:        # the carry is the flat buffers
            first = fops.unflatten(
                first, fops.place_frozen(fops.flatten_frozen(p0)))
        got = {"losses": [h["local_loss"] for h in res.history],
               "update1": self.norms(first, p0),
               "change3": self.norms(res.params, p0)}
        self.clock.first = None
        return ({k: np.asarray(v) for k, v in got.items()}, res.params,
                seconds)

    def reference_steps(self, pop, sd, num=None, loss=None,
                        stale_key: bool = False):
        """The same rounds through the plain reference: one key chain
        from the call's seed and the learning-rate decay over the
        call's rounds, run a dispatch's rounds at a time so that the
        first dispatch's update can be read.  In ``num`` (the
        configuration's precision by default) and with ``loss`` in
        place of the model's loss when given; ``stale_key`` starts
        every dispatch from the call's first key, as a chunk that
        returns its key unchanged would."""
        import jax
        import jax.numpy as jnp
        num = num or self.num
        if (num, loss) not in self._ref_runs:
            self._ref_runs[num, loss] = self.fedavg.make_rounds(
                loss or (lambda q, bx, by: self.ref.loss(self.model, num, q,
                                                         bx, by)),
                num, clients=self.cell.traffic["data"]["clients"],
                per_round=self.K, steps=int(self.fl["local_steps"]),
                batch=int(self.fl["batch_size"]), lr=float(self.fl["lr"]))
        run_ref = self._ref_runs[num, loss]
        decay = float(self.fl.get("lr_decay", 1.0))
        scales = jnp.asarray([decay ** j for j in
                              range(self.steps * self.chunk)], jnp.float32)
        q0 = self._init(jax.random.PRNGKey(sd["init"]), num)
        key0 = jax.random.PRNGKey(sd["steps"])
        q, key, want = q0, key0, {"losses": []}
        n_real = jnp.asarray(pop.n_real)
        for s in range(self.steps):
            q, key, ls = run_ref(q, key0 if stale_key else key, pop.x,
                                 pop.y, n_real,
                                 scales[s * self.chunk:(s + 1) * self.chunk])
            want["losses"] += np.asarray(ls).tolist()
            if s == 0:
                want["update1"] = self.norms(q, q0)
        want["change3"] = self.norms(q, q0)
        return {k: np.asarray(v) for k, v in want.items()}

    def names(self, p):
        return self.numerics.leaf_names(p, self.stacked)

    def flops_per_round(self) -> float:
        seq = self.cell.traffic["data"].get("seq_len", 0)
        return traffic.samples_per_round(self.cell.traffic) * \
            self.ref.train_flops_per_sample(self.model, seq)


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read of a traced run."""
    trace: xtrace.Trace
    window: tuple               # (t0_ns, t1_ns) of the traced window
    window_s: float
    rounds: int
    dispatches: int
    timing: dict
    compiles_in_window: int
    traces_in_window: int
    flops_per_round: float
    params: int                 # logical parameter count
    param_bytes: int            # bytes per stored parameter
    clients_per_round: int
    local_steps: int
    chips: int
    peaks: peaks.Peaks


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float = T_START, require_chip: bool = True) -> dict:
    import jax

    devs = chip_devices(cell.chips) if require_chip \
        else jax.devices()[:cell.chips]
    if require_chip:
        use_compile_cache()
    events = CompileEvents.listener()
    h = Harness(cell, devs)
    sd = seeds_of(seed)
    chunk = h.chunk

    # -- set-up: weights and data from the seed, the first steps --------
    p0 = h.weights(sd)
    names = h.names(p0)
    pop, data = h.population(seed, sd)
    got, p, steps_s = h.program_steps(data, p0, sd)
    del p0

    # warm-up: calls of at least two dispatches, each about a twentieth
    # of the window long, until two in a row compile nothing; the faster
    # of those two dispatch loops, and the less time spent around one,
    # size the window, so that one host stall does not shorten it
    clock = h.clock
    span = float(cell.traffic.get("trace_seconds", seconds)) if trace \
        else seconds
    per_round, outside = steps_s / (h.steps * chunk), 0.0
    clean: List[tuple] = []
    for i in range(MAX_WARMUP):
        n = max(2, round(0.05 * span / (per_round * chunk))) * chunk
        before = events.compiles
        res, call_s = h.call(data, n, sd["warm"] + i, p, time_loop=True)
        p = res.params
        per_round, outside = clock.loop_s / n, call_s - clock.loop_s
        warm_compiles = events.compiles - before
        clean = clean + [(per_round, outside)] if warm_compiles == 0 else []
        if len(clean) == 2:
            per_round = min(r for r, _ in clean)
            outside = min(o for _, o in clean)
            break
    del res
    R = max(2, math.floor((span - outside) / per_round / chunk)) * chunk
    print(f"[{cell.name}] set-up compile/load {events.seconds:.1f}s; "
          f"{warm_compiles} compiles in the last of {i + 1} warm-up calls; "
          f"{per_round:.4f}s per round and {outside:.3f}s around the "
          f"dispatch loop: window of {R} rounds", file=sys.stderr,
          flush=True)

    # -- the window: one whole call ------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    span_cm = contextlib.nullcontext()
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        span_cm = jax.profiler.TraceAnnotation(WINDOW)
    traces0, compiles0 = events.traces, events.compiles
    t_window = time.perf_counter()
    with span_cm:
        res, window_s = h.call(data, R, sd["window"], p)
    traces_in_window = events.traces - traces0
    compiles_in_window = events.compiles - compiles0
    if trace:
        jax.profiler.stop_trace()
    setup_s = t_window - t_start
    del p
    losses = np.asarray([r["local_loss"] for r in res.history])
    failed = int(np.sum(~np.isfinite(losses)))
    timing, dispatches = dict(res.timing), res.dispatches
    mem = peak_bytes(devs)
    del res
    gc.collect()

    # -- the comparison, after the window --------------------------------
    t_ref = time.perf_counter()
    want = h.reference_steps(pop, sd)
    read = compare.readings(got, want, names)
    print(f"[{cell.name}] the reference took "
          f"{time.perf_counter() - t_ref:.1f}s", file=sys.stderr, flush=True)
    correct, checks = compare.judge(read, cell.limits)
    correct = correct and failed == 0
    print(f"[{cell.name}] round losses, program {got['losses'].tolist()}, "
          f"reference {want['losses'].tolist()}", file=sys.stderr,
          flush=True)
    print(f"[{cell.name}] worst leaves: update1 {read['update1_leaf']}, "
          f"change3 {read['change3_leaf']}; left out "
          f"{len(read['left_out'])} quiet leaves {read['left_out'][:8]}",
          file=sys.stderr, flush=True)
    unheld = [k for k in compare.NUMBERS if k not in checks]
    if unheld:
        print(f"[{cell.name}] read and not compared (no limit): " +
              ", ".join(f"{k} {read[k]!r}" for k in unheld),
              file=sys.stderr, flush=True)

    # -- metrics --------------------------------------------------------
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    out = {"correct": bool(correct), "attempted": int(R), "failed": failed}
    if not trace:
        values = {"rounds_per_s": R / window_s,
                  "peak_hbm_gb": None if mem is None else mem / 1e9,
                  "setup_s": setup_s}
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    else:
        tr = xtrace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        span_ev = tr.annotation(WINDOW)
        win = (span_ev.start_ns, span_ev.end_ns)
        cores = [tr.device_ops[c] for c in sorted(tr.device_ops)][:cell.chips]
        busy = float(np.mean([xtrace.busy_ns(ev, win) for ev in cores]))
        ctx = Context(
            trace=tr, window=win, window_s=window_s, rounds=R,
            dispatches=dispatches, timing=timing,
            compiles_in_window=compiles_in_window,
            traces_in_window=traces_in_window,
            flops_per_round=h.flops_per_round(),
            params=h.ref.n_params(h.model),
            param_bytes=np.dtype(h.num.carrier).itemsize,
            clients_per_round=h.K, local_steps=int(h.fl["local_steps"]),
            chips=cell.chips,
            peaks=peaks.peaks_of(d0.device_kind) if require_chip
            else peaks.PEAKS["TPU v5 lite"])
        metrics = {}
        for m in cell.per_layer:
            v = load_module(cell.dirs, "metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out["metrics"] = metrics
        device["busy_s"] = busy * 1e-9
        device["window_s"] = (win[1] - win[0]) * 1e-9
        out["breakdown"] = {
            "device_ops": xtrace.top_ops(cores[0], win),
            "idle_gaps": xtrace.longest_gaps(tr, win, skip=(WINDOW,))}
    out["device"] = device
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 1
    for line in compare.lines(out["checks"]):
        print(line, file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
