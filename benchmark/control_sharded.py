"""Readings for the limits of ``correct`` in the sharded dedup cell: the
program's numbers on many seeds and the controls' on a few.

    python benchmark/control_sharded.py --workload dedup.news-pod4 \\
        --seeds 1,2,3 --control-seeds 4,5,6

Program: for each ``--seeds`` seed the cell's entry is set up and every
corpus of its pool runs once through ``dedup_reps_sharded``; the numbers
are those the run's check would compute.

Controls, on the host alone (no chip, no device program), against the
32-bit reference:

- ``shard_local``: the resolve of the signatures shard 0 of the
  configuration's mesh holds before the cross-chip ``pmin`` — each
  article's MinHash over only the blocks that the program's tile grouping
  (``NearDupEngine._sharded_tile_groups``) hands shard 0;
- ``sig16``: the reference's MinHash with 16-bit permuted hashes in
  place of 32-bit ones.

Without ``--rehearse`` program seeds need a TPU with the mesh's chips,
like ``run.py``.  One JSON line per reading on standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_entry(cell):
    from benchmark.run import load_module

    name = cell.config["entry"]
    return load_module(os.path.join(HERE, "entries", name + ".py"), "bench_entry_" + name)


def program_reading(cell, seed: int, say) -> dict:
    import jax

    from benchmark.run import memory_peak

    entry = load_entry(cell).Entry(cell, seed, say)
    results = []
    for i in range(entry.pool_size()):
        t0 = time.perf_counter()
        results.append((i, entry.run(i)))
        say("program corpus", index=i, seconds=time.perf_counter() - t0)
    say("device memory", peak_bytes=memory_peak(jax.devices()[: entry.chips]))
    done = SimpleNamespace(results=results)
    entry.release(done)
    return entry.check(done)


def shard_local_reps(pool: list, cfg: dict, shards: int, executor=None) -> list[np.ndarray]:
    """Each corpus resolved on shard 0's partial signatures of a mesh of
    ``shards`` chips."""
    from advanced_scrapper_tpu.config import DedupConfig
    from advanced_scrapper_tpu.pipeline.dedup import NearDupEngine
    from benchmark.reference import dedup as ref
    from benchmark.reference import lsh

    eng = NearDupEngine(DedupConfig(**cfg))
    blocks, owners = [], []
    for corpus in pool:
        b, o = [], []
        # shards=[]: the grouping is read, nothing is counted or dispatched
        for _shape, batch in eng._sharded_tile_groups(eng._host_tiles(corpus.docs), shards,
                                                      shards=[]):
            tok, lens, own = batch[0]
            for r in np.flatnonzero(lens):
                b.append(tok[r, : lens[r]].tobytes())
                o.append(int(own[r]))
        blocks.append(b)
        owners.append(np.asarray(o, np.int64))
    a32, b32 = ref.device_family(cfg["seed"], cfg["num_perm"])
    sigs = lsh.signatures(blocks, a32, b32, cfg["shingle_k"], executor=executor)
    coarse = lsh.band_salt(cfg["seed"], cfg["num_perm"], cfg["num_bands"])
    fine = lsh.subband_salt(cfg["cand_subbands"])
    out = []
    for corpus, own, s in zip(pool, owners, sigs):
        n = len(corpus.docs)
        part = np.full((n, cfg["num_perm"]), lsh.U32_MAX, np.uint32)
        np.minimum.at(part, own, s)
        valid = np.fromiter(map(len, corpus.docs), np.int64, count=n) >= cfg["shingle_k"]
        out.append(lsh.resolve(part, valid, coarse, fine, cfg["sim_threshold"]))
    return out


def control_readings(cell, seed: int, say, *, shards: int) -> dict:
    from benchmark import generate
    from benchmark.reference import lsh

    mod = load_entry(cell)
    cfg, mix = cell.config["dedup"], cell.traffic
    make = getattr(generate, mix["generator"])
    pool = [make(mix, seed, i) for i in range(int(mix["pool"]))]
    docs = [c.docs for c in pool]
    with mod.executor_for(pool, range(len(pool))) as ex:
        want = dict(enumerate(lsh.reference_reps(docs, cfg, executor=ex)))
        out = {}
        for name, reps in (
            ("shard_local", shard_local_reps(pool, cfg, shards, ex)),
            ("sig16", lsh.reference_reps(docs, cfg, bits=16, executor=ex)),
        ):
            out[name] = mod.compare_window(pool, list(enumerate(reps)), cfg, say, want=want,
                                           executor=ex)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.run import Cell, device_facts, say

    cell = Cell(args.workload, args.rehearse)
    # the controls split signatures as the configuration's own mesh would
    rows, cols = Cell(args.workload, False).config["mesh"]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    if seeds:
        r, c = cell.config["mesh"]
        if device_facts(r * c, args.rehearse) is None:
            return 2
    for kind, seed in [("program", s) for s in seeds] + [("control", s) for s in cseeds]:
        t0 = time.perf_counter()
        if kind == "program":
            reading = program_reading(cell, seed, say)
        else:
            reading = control_readings(cell, seed, say, shards=rows * cols)
        mesh = cell.config["mesh"] if kind == "program" else [rows, cols]
        print(json.dumps({"cell": cell.name, "kind": kind, "seed": seed, "mesh": mesh,
                          "reading": reading, "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
