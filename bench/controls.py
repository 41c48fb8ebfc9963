"""Readings that set a cell's limits, read on the chip at the cell's own
size, in one process so that everything compiles once:

    python3 bench/controls.py --workload <cell> --seeds 1 2 ... [--controls 3]

For each seed the program runs the cell's first steps in one call and
the plain reference the same rounds, and ``compare.readings`` gives the sound
reading.  For the first ``--controls`` seeds three more runs stand in the
program's place:

  control     the reference computed in the nearest precision below
              the configuration's (``numerics.LOWER``)
  half_batch  the reference with half of every batch left out, the mean
              loss taken over the rest
  stale_key   the reference with every dispatch started from the call's
              first key, as a chunk program that returns its key
              unchanged would run: the same clients and batches again

A step that returns its state unchanged, or applies its update twice,
reads 1 on ``update1`` by the measure itself and needs no run.

With ``--faults`` the program itself runs broken instead, for a fault
that only exists in its own layout:

  drop_exchange  the cross-pod combine sums each chip's own pod's lanes
                 and leaves out the ``psum`` over the mesh's ``data``

Each seed prints one JSON line; ``cells/<cell>.json`` keeps the limits
set from them.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import compare                                     # noqa: E402
from bench.run import (Harness, chip_devices, load_cell,      # noqa: E402
                       seeds_of, use_compile_cache)


@functools.lru_cache(maxsize=None)
def half_batch(h: Harness):
    """The model's loss over the first half of each batch alone (one
    function per harness, so that its reference compiles once)."""
    def loss(q, bx, by):
        half = bx.shape[0] // 2
        return h.ref.loss(h.model, h.num, q, bx[:half], by[:half])
    return loss


def readings_of(h: Harness, seed: int, controls: bool) -> dict:
    sd = seeds_of(seed)
    p0 = h.weights(sd)
    names = h.names(p0)
    pop, data = h.population(seed, sd)
    got, _, _ = h.program_steps(data, p0, sd)
    del p0
    want = h.reference_steps(pop, sd)
    out = {"seed": seed, "program": compare.readings(got, want, names)}
    if controls:
        lower = h.numerics.numerics(h.numerics.LOWER[h.num.name])
        try:
            ctrl = h.reference_steps(pop, sd, num=lower)
            out["control"] = compare.readings(ctrl, want, names)
        except Exception as e:          # a control that crashes has failed
            out["control"] = {"error": repr(e)}
        out["half_batch"] = compare.readings(
            h.reference_steps(pop, sd, loss=half_batch(h)), want,
            names)
        out["stale_key"] = compare.readings(
            h.reference_steps(pop, sd, stale_key=True), want, names)
    for v in out.values():
        if isinstance(v, dict):
            v.pop("left_out", None)
    return out


def own_pod_only(self, acc_bufs):
    """``ShardedFlatOps.lane_combine`` with the exchange between the
    pods left out."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.sharding import rules
    run = jax.shard_map(lambda a: jnp.sum(a, axis=0), mesh=self.mesh,
                        in_specs=(rules.lane_axis_pspec(),),
                        out_specs=P(None, None), check_vma=False)
    return {name: run(acc) for name, acc in acc_bufs.items()}


def fault_sites():
    """``{fault: (owner, attribute, replacement)}``."""
    from repro.fl.pod import ShardedFlatOps
    return {"drop_exchange": (ShardedFlatOps, "lane_combine", own_pod_only)}


@contextlib.contextmanager
def planted(fault: str):
    """The program with ``fault`` in place, its round programs traced
    anew on the way in and out."""
    from repro.fl import engine
    owner, attr, fn = fault_sites()[fault]
    old = getattr(owner, attr)
    engine._cached_chunk_fn.cache_clear()
    setattr(owner, attr, fn)
    try:
        yield
    finally:
        setattr(owner, attr, old)
        engine._cached_chunk_fn.cache_clear()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3,
                    help="seeds (the first ones) that also run the control "
                         "and the planted faults")
    ap.add_argument("--faults", nargs="+", default=(),
                    help="run the program broken by each of these instead")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    devs = chip_devices(cell.chips)
    use_compile_cache()
    for fault in args.faults:
        with planted(fault):
            h = Harness(cell, devs)
            for seed in args.seeds:
                t0 = time.perf_counter()
                out = readings_of(h, seed, controls=False)
                out["fault"] = fault
                out["seconds"] = time.perf_counter() - t0
                print(json.dumps(out), flush=True)
    if args.faults:
        return 0
    h = Harness(cell, devs)
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        out = readings_of(h, seed, i < args.controls)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
