"""The round engine's own names in a trace: its host spans and the
phases of its compiled round.

Host spans.  ``repro.fl.engine.run_rounds`` marks every stretch of host
work with a ``jax.profiler.TraceAnnotation`` named ``engine.<stage>``
(``pack``, ``prepare_data``, ``plan``, ``stage``, ``dispatch``,
``drain``, ``history``, ``unpack``), and its sparse store two more
(``store.transfer``, ``store.spill``).  They are events of the host
plane, read from :class:`xtrace.Trace` as it is; the span metrics
(``metrics/engine_*_ms_per_call.py``) read them.

Phases.  Every op of the round program (``jit_chunk``) carries its
``jax.named_scope`` path in its ``op_name`` metadata; the innermost
``fl_*`` name in it is the op's phase (``fl_fwd_bwd``, ``fl_conv_wgrad``,
``fl_unflatten``, ``fl_step_tail``, ``fl_aggregate``,
``fl_server_update``, ``fl_eval``), and an op under none is carry
plumbing (``unscoped``).  Each op also keeps its instruction's kind, so
that a phase's collectives (``COLLECTIVES``) read apart from its
compute: on a mesh the cross-pod combine and the tensor-parallel
all-reduces are the same kind of op, told apart by their phase.  A TPU op event
names only its instruction; :func:`load` maps each instruction to its
``op_name`` through the compiled program's HLO, which the profiler keeps
in the trace file (:func:`_hlo_instructions`), and takes the program's ops
from its ``XLA Modules`` events.  A fusion carries the ``op_name`` that
XLA gave it, its root's.  The reductions take a window and, like
``xtrace.self_ns``, count each op's own time, less its children's.
The phases need the trace file itself: ``bench/run.py`` loads them
into its readers' context before it deletes the trace, and the
``*_device_ms_per_round`` and collective readers read them through
:func:`ms_per_round`; :func:`main` prints them for a trace on disk::

    python -m repro.launch.train ... --trace-dir DIR
    python3 -m bench.scopes DIR --rounds <rounds in the trace>

A program without these names (an engine before them) gives no spans
and no phases: the span and phase metrics then return None.
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from bench import xtrace

ENGINE = ("engine.", "store.")
PHASES = ("fl_fwd_bwd", "fl_conv_wgrad", "fl_unflatten", "fl_step_tail",
          "fl_aggregate", "fl_server_update", "fl_eval")
UNSCOPED = "unscoped"
# instruction kinds of the collectives, each also as its async pair
COLLECTIVES = frozenset(k + form for k in ("all-reduce", "all-gather",
                                           "reduce-scatter",
                                           "collective-permute")
                        for form in ("", "-start", "-done"))
PROGRAM = "jit_chunk("              # its runs: jit_chunk(<id>)
MODULES = "XLA Modules"             # a TPU core's line of program runs
_PHASE = re.compile(r"\bfl_[a-z_]+")


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

def engine_spans(trace: xtrace.Trace, window: xtrace.Window,
                 name: Optional[str] = None) -> List[xtrace.Event]:
    """The engine's host spans (of ``name``, or all of them) that lie in
    ``window``, in time order."""
    t0, t1 = window
    return sorted((e for e in trace.host
                   if (e.name == name if name else e.name.startswith(ENGINE))
                   and e.start_ns >= t0 and e.end_ns <= t1),
                  key=lambda e: e.start_ns)


def ms_per_call(trace: xtrace.Trace, window: xtrace.Window,
                names: Sequence[str]) -> Optional[float]:
    """Summed ms of the spans called ``names`` inside ``window``, per
    span of the first name (one per ``run_rounds`` call); None when the
    program marks none."""
    calls = len(engine_spans(trace, window, names[0]))
    if not calls:
        return None
    ns = sum(e.dur_ns for n in names for e in engine_spans(trace, window, n))
    return ns * 1e-6 / calls


def idle_by_span(trace: xtrace.Trace, window: xtrace.Window
                 ) -> Dict[str, Tuple[int, float, float]]:
    """``{span name: (count, span ms, device-idle ms inside)}`` over the
    engine's spans in ``window``: which host stretch each idle
    nanosecond of the first device falls in."""
    gaps = xtrace.idle_gaps(trace.device_ops[min(trace.device_ops)], window)
    out: Dict[str, Tuple[int, float, float]] = {}
    for e in engine_spans(trace, window):
        idle = sum(b - a for a, b in xtrace.clip(
            (xtrace.Event("", g0, g1 - g0) for g0, g1 in gaps),
            (e.start_ns, e.end_ns)))
        n, ms, idle_ms = out.get(e.name, (0, 0.0, 0.0))
        out[e.name] = (n + 1, ms + e.dur_ns * 1e-6, idle_ms + idle * 1e-6)
    return out


def gap_owners(trace: xtrace.Trace, window: xtrace.Window, n: int = 10,
               skip: Sequence[str] = ()) -> List[list]:
    """``[[engine span, host activity, seconds], ...]`` for the ``n``
    longest device-idle gaps of ``window``: the innermost engine span
    open across most of the gap ("outside" if none), beside
    ``xtrace.label_gap``'s label."""
    spans = engine_spans(trace, window)
    core = trace.device_ops[min(trace.device_ops)]
    out = []
    for a, b in sorted(xtrace.idle_gaps(core, window),
                       key=lambda g: g[0] - g[1])[:n]:
        best, key = "outside", (0.0, 0.0)
        for e in spans:
            k = (min(e.end_ns, b) - max(e.start_ns, a), -e.dur_ns)
            if k[0] > 0 and k > key:
                best, key = e.name, k
        out.append([best, xtrace.label_gap((a, b), trace.host, skip),
                    (b - a) * 1e-9])
    return out


# ---------------------------------------------------------------------------
# device time by phase
# ---------------------------------------------------------------------------

def phase_of(op_name: str) -> str:
    """The innermost ``fl_*`` scope of an op's ``op_name`` path
    (``.../fl_fwd_bwd/transpose(jvp(fl_unflatten))/pad`` ->
    ``fl_unflatten``), or ``unscoped``."""
    hits = [h for h in _PHASE.findall(op_name) if h in PHASES]
    return hits[-1] if hits else UNSCOPED


@dataclasses.dataclass(frozen=True)
class Op(xtrace.Event):
    """A round-program op event named by its phase, with the kind of
    its instruction (``xtrace.op_kind``: ``all-reduce-start``)."""
    kind: str = ""


@dataclasses.dataclass
class Phases:
    """The round program's op events of the first device, each an
    :class:`Op` named by its phase, and the program's own intervals (its
    ``XLA Modules`` events)."""
    ops: List[xtrace.Event]
    runs: List[xtrace.Event]

    def self_ms(self, window: xtrace.Window, collective: bool = False
                ) -> Dict[str, float]:
        """Own device ms of the round program's ops by phase in
        ``window``; with ``collective``, of its collective ops alone
        (the others still own their time, so that nesting counts
        once)."""
        ops = self.ops
        if collective:
            ops = [dataclasses.replace(
                e, name=e.name if getattr(e, "kind", "") in COLLECTIVES
                else "") for e in ops]
        own = {k: v * 1e-6 for k, v in xtrace.self_ns(ops, window).items()}
        own.pop("", None)
        return own

    def run_ms(self, window: xtrace.Window) -> float:
        """Device ms the round program ran in ``window``, from its
        module events: the sum the phases should come to."""
        return xtrace.busy_ns(self.runs, window) * 1e-6


def ms_per_round(ctx, phases: Sequence[str], collective: bool = False
                 ) -> Optional[float]:
    """Own device ms per round of a reader context's round-program ops
    in ``phases`` (``UNSCOPED`` may be one), or of their collectives
    alone; None without the program's phases (no trace, no round
    program, or a program that names none) or where no op of
    ``phases`` ran."""
    ph = getattr(ctx, "scopes", None)
    if ph is None or not ctx.rounds:
        return None
    own = ph.self_ms(ctx.window)
    if not own.keys() & set(PHASES):
        return None
    if collective:
        own = ph.self_ms(ctx.window, collective=True)
    hits = [v for k, v in own.items() if k in phases]
    return sum(hits) / ctx.rounds if hits else None


def report(trace: xtrace.Trace, window: xtrace.Window,
           phases: Optional[Phases], rounds: Optional[int] = None,
           file=None) -> None:
    """The engine's spans in ``window`` with the device-idle ms inside
    each, the longest idle gaps with their span, and the round program's
    device ms by phase (per round when ``rounds`` is given), beside the
    program's own run time (the phases partition it), and the part of
    each phase's time that its collectives took."""
    out = file or sys.stdout
    if trace.device_ops:
        print("span: count, host ms, device-idle ms inside", file=out)
        for name, (n, ms, idle_ms) in idle_by_span(trace, window).items():
            print(f"  {name:20s} {n:5d} {ms:11.3f} {idle_ms:11.3f}",
                  file=out)
        print(f"longest gaps (span, host label, s): "
              f"{gap_owners(trace, window)}", file=out)
    if phases is not None:
        per = rounds or 1
        own = phases.self_ms(window)
        print(f"round program device ms{' per round' if rounds else ''} "
              "by phase: " +
              ", ".join(f"{k} {v / per:.3f}" for k, v in
                        sorted(own.items(), key=lambda kv: -kv[1])) +
              f"; sum {sum(own.values()) / per:.3f}, program ran "
              f"{phases.run_ms(window) / per:.3f}", file=out)
        coll = phases.self_ms(window, collective=True)
        print("  of it collectives: " + (", ".join(
            f"{k} {v / per:.3f}" for k, v in
            sorted(coll.items(), key=lambda kv: -kv[1])) or "none"),
            file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Print the round engine's spans and the round "
                    "program's device time by phase in a profiler trace.")
    ap.add_argument("trace_dir", help="a directory a profiler trace was "
                    "written to, e.g. by repro.launch.train --trace-dir")
    ap.add_argument("--rounds", type=int, default=None,
                    help="the rounds the trace ran: print ms per round")
    args = ap.parse_args(argv)
    tr = xtrace.load(args.trace_dir)
    spans = engine_spans(tr, (-math.inf, math.inf))
    if not spans:
        print(f"no engine spans in {args.trace_dir}", file=sys.stderr)
        return 1
    window = (spans[0].start_ns, max(e.end_ns for e in spans))
    report(tr, window, load(args.trace_dir), args.rounds)
    return 0


# ---------------------------------------------------------------------------
# loading: op events by program, and each program's op_names
# ---------------------------------------------------------------------------

def load(trace_dir: str) -> Optional[Phases]:
    """The round program's ops of the first device in the trace under
    ``trace_dir``, each named by its phase; None when the trace holds
    no round program."""
    from jax.profiler import ProfileData
    path = xtrace.find_xplane(trace_dir)
    with open(path, "rb") as f:
        raw = f.read()
    instrs = {m: ops for m, ops in _hlo_instructions(raw).items()
              if m.startswith(PROGRAM)}
    if not instrs:
        return None
    names = {m: {i: v[0] for i, v in ops.items()}
             for m, ops in instrs.items()}
    opcodes = {m: {i: v[1] for i, v in ops.items()}
               for m, ops in instrs.items()}
    pd = ProfileData.from_serialized_xspace(raw)
    ops, runs = _tpu_ops(pd, names, opcodes)
    if ops is None:
        ops, runs = _cpu_ops(pd, names, opcodes)
    return Phases(ops=sorted(ops, key=lambda e: e.start_ns), runs=runs)


def _tpu_ops(pd, names, opcodes):
    """The first TPU core's phases, or None without a TPU plane."""
    planes = sorted((int(m.group(1)), p) for p in pd.planes
                    for m in [xtrace.DEVICE_PLANE.match(p.name)] if m)
    if not planes:
        return None, []
    lines = {line.name: line for line in planes[0][1].lines}
    if MODULES not in lines or xtrace.OPS_LINE not in lines:
        return [], []
    return phase_ops([xtrace._event(e) for e in lines[MODULES].events],
                     [xtrace._event(e) for e in lines[xtrace.OPS_LINE].events],
                     names, opcodes)


def phase_ops(modules: Sequence[xtrace.Event], ops: Sequence[xtrace.Event],
              names: Dict[str, Dict[str, str]],
              opcodes: Optional[Dict[str, Dict[str, str]]] = None
              ) -> Tuple[List[xtrace.Event], List[xtrace.Event]]:
    """``(ops, runs)``: the op events that lie inside a run of a program
    in ``names`` (``{program: {instruction: op_name}}``), each renamed
    to its phase, and those runs.  ``modules`` are one core's ``XLA
    Modules`` events, named ``<program>(<id>)`` like ``names``' keys;
    ``ops`` its ``XLA Ops`` events, named by their instruction's text.
    Each op's kind is its instruction's opcode from ``opcodes`` (same
    keys as ``names``; a collective may carry the name of the JAX op it
    lowers, ``%psum.22 = ... all-reduce(``), else the kind of its
    name."""
    import bisect
    mods = sorted(modules, key=lambda e: e.start_ns)
    starts = [m.start_ns for m in mods]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if i < 0 or mods[i].name not in names or e.end_ns > mods[i].end_ns:
            continue
        instr = xtrace.op_name(e)
        op = names[mods[i].name].get(instr, "")
        kind = (opcodes or {}).get(mods[i].name, {}).get(instr)
        out.append(Op(phase_of(op), e.start_ns, e.dur_ns,
                      kind or xtrace.op_kind(instr)))
    return out, [m for m in mods if m.name in names]


def _cpu_ops(pd, names, opcodes):
    """JAX's CPU backend runs a program's ops on host threads, each
    event naming its instruction, module and program id."""
    ops = []
    for plane in pd.planes:
        if plane.name != xtrace.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                mod = f"{st.get('hlo_module')}({st.get('program_id')})"
                if "hlo_op" in st and mod in names:
                    instr = st["hlo_op"]
                    ops.append(Op(
                        phase_of(names[mod].get(instr, "")),
                        float(e.start_ns), float(e.duration_ns),
                        opcodes[mod].get(instr) or xtrace.op_kind(instr)))
    return ops, list(ops)


def _hlo_instructions(raw: bytes) -> Dict[str, Dict[str, Tuple[str, str]]]:
    """``{program: {instruction: (op_name, opcode)}}`` from the HLO
    protos that the profiler keeps in the trace's ``/host:metadata``
    plane (XSpace.planes -> XPlane.event_metadata -> XEventMetadata.stats
    -> HloProto -> HloModuleProto.computations ->
    HloInstructionProto{name, opcode, metadata}), read field by field so
    that no protobuf schema is needed."""
    buf = memoryview(raw)

    def text(span):
        return bytes(buf[span[0]:span[1]]).decode()

    out: Dict[str, Dict[str, Tuple[str, str]]] = {}
    for plane in _sub(buf, (0, len(buf)), 1):
        if [text(s) for s in _sub(buf, plane, 2)] != ["/host:metadata"]:
            continue
        for entry in _sub(buf, plane, 4):               # map<id, metadata>
            for meta in _sub(buf, entry, 2):
                program = "".join(text(s) for s in _sub(buf, meta, 2))
                for stat in _sub(buf, meta, 5):
                    for proto in _sub(buf, stat, 6):    # bytes: HloProto
                        ops = out.setdefault(program, {})
                        for module in _sub(buf, proto, 1):
                            for comp in _sub(buf, module, 3):
                                for ins in _sub(buf, comp, 2):
                                    name = "".join(
                                        text(s) for s in _sub(buf, ins, 1))
                                    ops[name] = ("".join(
                                        text(s) for m in _sub(buf, ins, 7)
                                        for s in _sub(buf, m, 2)), "".join(
                                        text(s) for s in _sub(buf, ins, 2)))
    return out


def _varint(buf, i):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def _sub(buf, span, number) -> List[Tuple[int, int]]:
    """The ``(start, end)`` of each length-delimited field ``number`` of
    the protobuf message at ``span``."""
    i, end = span
    out = []
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            _, i = _varint(buf, i)
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        elif wire == 2:
            n, i = _varint(buf, i)
            if key >> 3 == number:
                out.append((i, i + n))
            i += n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
    return out


if __name__ == "__main__":
    sys.exit(main())
