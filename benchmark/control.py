"""Readings for the limits of ``correct``: the program's numbers on many
seeds and the control's on a few, in one process (it holds the chip).

    python benchmark/control.py --workload <cell> --seeds 1,2,3 --control-seeds 4,5,6

For each ``--seeds`` seed the cell's entry is set up and every item of
its pool runs once through the timed path; the numbers are those the
run's check would compute.  For each ``--control-seeds`` seed the
control takes the program's place:

- ``dedup_reps`` (news-mix): the program's own estimator-only path
  (``rerank=False``, ``exact_verify_band=0``): verdicts on the 128-lane
  estimate alone, the step a later PR would be tempted to take;
- ``dedup_reps`` (signatures): the reference's MinHash with 16-bit
  permuted hashes in place of 32-bit ones, against the 32-bit reference;
- ``match_chunk``: the reference with fuzzy scores kept as whole
  numbers, against the reference.

Without ``--rehearse`` it refuses to run off a TPU, like ``run.py``.
One JSON line per reading on standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def one_pass(entry) -> SimpleNamespace:
    """Every item of the pool once, recorded as a window is."""
    return SimpleNamespace(results=[(i, entry.run(i)) for i in range(entry.pool_size())])


def program_reading(cell, seed: int, say) -> dict:
    entry = load_entry(cell).Entry(cell, seed, say)
    done = one_pass(entry)
    entry.release(done)
    return entry.check(done)


def control_readings(cell, seed: int, say) -> dict:
    mod = load_entry(cell)
    if cell.config["entry"] == "dedup_reps":
        return dedup_controls(cell, mod, seed, say)
    return match_control(cell, mod, seed, say)


def dedup_controls(cell, mod, seed: int, say) -> dict:
    from benchmark.reference import dedup as ref

    out = {}
    if cell.traffic["copy_share"] or cell.traffic["mutant_share"]:
        entry = mod.Entry(cell, seed, say,
                          dedup_overrides={"rerank": False, "exact_verify_band": 0.0})
        done = one_pass(entry)
        entry.release(done)
        est = entry.check(done)
        out["estimator_only"] = {k: v for k, v in est.items() if k != "sig_rows_off"}
    # 16-bit signatures in the program's place, on the sample a run checks
    from benchmark import generate

    cfg = cell.config["dedup"]
    a, b = ref.device_family(cfg["seed"], cfg["num_perm"])
    off = 0
    make = getattr(generate, cell.traffic["generator"])
    for i in range(int(cell.traffic["pool"])):
        docs = make(cell.traffic, seed, i).docs
        sample = [docs[r] for r in mod.sig_sample(docs, seed, i, int(cell.traffic["check_sig_rows"]))]
        low = ref.signatures(sample, a, b, cfg["shingle_k"], bits=16)
        want = ref.signatures(sample, a, b, cfg["shingle_k"])
        off += int((low != want).any(axis=1).sum())
    out["sig16"] = {"sig_rows_off": off}
    return out


def match_control(cell, mod, seed: int, say) -> dict:
    from benchmark import generate
    from benchmark.reference import match as ref

    cfg, mix = cell.config["match"], cell.traffic
    companies = generate.entity_universe(cfg["universe_seed"], cfg["tickers"])
    names = generate.universe_names(companies)
    make = getattr(generate, mix["generator"])
    u = ref.Universe(companies)
    off = 0
    for i in range(int(mix["pool"])):
        rows = make(mix, seed, i, names)
        for k in mod.sample(rows, seed, i, int(mix["check_articles"])):
            args = (u, rows[k]["article_text"], rows[k]["title"], rows[k]["date_time"])
            thr = cfg["fuzzy_threshold"]
            off += int(ref.match_article(*args, threshold=thr, whole_scores=True)
                       != ref.match_article(*args, threshold=thr))
    return {"whole_scores": {"articles_off": off}}


def load_entry(cell):
    from benchmark.run import load_module

    name = cell.config["entry"]
    return load_module(os.path.join(HERE, "entries", name + ".py"), "bench_entry_" + name)


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
    if device_facts(cell.workload["chips"], args.rehearse) is None:
        return 2
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    for kind, seed, fn in ([("program", s, program_reading) for s in seeds]
                           + [("control", s, control_readings) for s in cseeds]):
        t0 = time.perf_counter()
        reading = fn(cell, seed, say)
        print(json.dumps({"cell": cell.name, "kind": kind, "seed": seed, "reading": reading,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
