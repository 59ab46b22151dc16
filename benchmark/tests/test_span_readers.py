"""Every stage-clock metric of a cell reads a number in a traced rehearsal:
the program's clock exists, is filled on the cell's path, and its reader
finds it (``readers_returned`` on standard error)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
#: stages that do no work at a cell's rehearsal size: news-fresh's
#: rehearsal corpora hold no candidate pair, so the tier returns after
#: its work-list
IDLE_AT_REHEARSAL = {
    "dedup.news-fresh": {
        "rerank_sketch_ms_per_1k.dedup",
        "rerank_settle_ms_per_1k.dedup",
        "rerank_exact_ms_per_1k.dedup",
        "rerank_cluster_ms_per_1k.dedup",
    },
}
MARK = "readers_returned"


def span_metrics(cell: str) -> set[str]:
    return {
        m["name"] for m in BENCH["per_layer"]
        if m["source"] == "program_span" and cell in m.get("workloads", [])
    }


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reads_every_span_metric(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", str(2**31 + 777),
         "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 3, p.stderr[-3000:]
    line = next(ln for ln in p.stderr.splitlines() if MARK in ln)
    returned = set(json.loads(line[line.index("{"):])[MARK])
    want = span_metrics(cell) - IDLE_AT_REHEARSAL.get(cell, set())
    assert want, f"{cell} has no stage-clock metric"
    assert want <= returned, sorted(want - returned)
