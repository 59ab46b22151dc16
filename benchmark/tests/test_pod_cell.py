"""The sharded dedup cell (``dedup.news-pod4``): its plain reference equals
the program's own reference of the same semantics, and planted faults
come out ``correct: false`` at rehearsal size."""

import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import control_sharded, generate
from benchmark import run as harness
from benchmark.reference import lsh
from benchmark.run import Cell, compare

CELL = "dedup.news-pod4"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def say(*_a, **_k):
    pass


def failed(values: dict, cell: Cell) -> list[str]:
    return [k for k, c in compare(values, cell.limits).items() if c["value"] > c["limit"]]


def rehearse(seed: int = 20261016) -> dict:
    out = io.StringIO()
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds", "0.5",
                       "--trace", "0", "--rehearse"], out=out)
    assert rc == harness.EXIT_REHEARSAL
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("workers", [0, 2])
def test_reference_equals_the_programs_oracle(workers):
    """``reference/lsh.py`` (its own signatures, salts and resolve) gives
    the representatives of ``cpu.oracle.oracle_lsh_reps`` over the
    program's host signatures, inline and over a process pool."""
    from advanced_scrapper_tpu.core.hashing import make_params
    from advanced_scrapper_tpu.cpu.oracle import device_family_signatures, oracle_lsh_reps
    from advanced_scrapper_tpu.ops.lsh import subband_salt

    cell = Cell(CELL, rehearse=True)
    cfg = cell.config["dedup"]
    corpora = [generate.news_corpus(dict(cell.traffic, articles=600), 2**31 + 5, i).docs
               for i in range(2)]
    params = make_params(cfg["num_perm"], cfg["num_bands"], cfg["shingle_k"], cfg["seed"])
    assert np.array_equal(lsh.band_salt(cfg["seed"], cfg["num_perm"], cfg["num_bands"]),
                          params.band_salt)
    assert np.array_equal(lsh.subband_salt(cfg["cand_subbands"]),
                          subband_salt(cfg["cand_subbands"]))
    if workers:
        with lsh.pool(workers) as ex:
            got = lsh.reference_reps(corpora, cfg, executor=ex)
    else:
        got = lsh.reference_reps(corpora, cfg)
    for docs, reps in zip(corpora, got):
        valid = np.fromiter(map(len, docs), np.int64) >= cfg["shingle_k"]
        want = oracle_lsh_reps(device_family_signatures(docs, params), params,
                               cand_subbands=cfg["cand_subbands"],
                               threshold=cfg["sim_threshold"], valid=valid)
        assert (want != np.arange(len(docs))).sum() > 50  # merges to agree on
        assert np.array_equal(reps, want)


def test_sound_run_is_correct():
    line = rehearse()
    assert line["correct"] is True
    assert line["checks"]["reps_off"]["value"] == 0


def test_one_altered_representative(monkeypatch):
    from advanced_scrapper_tpu.pipeline import dedup

    orig = dedup.NearDupEngine.dedup_reps_sharded

    def altered(self, texts, mesh):
        reps = orig(self, texts, mesh).copy()
        loner = int(np.flatnonzero(reps == np.arange(len(reps)))[-1])
        reps[loner] = 0  # one article merged with an unrelated one
        return reps

    monkeypatch.setattr(dedup.NearDupEngine, "dedup_reps_sharded", altered)
    line = rehearse()
    assert line["correct"] is False
    assert line["checks"]["reps_off"]["value"] > 0


#: the harness on four virtual devices with the cell's own 4x1 mesh, and
#: the cross-chip ``pmin`` taken out: every chip resolves its own partial
SHARD_LOCAL = """
import sys
import jax
sys.path.insert(0, {root!r})
jax.lax.pmin = lambda x, axis_name, **kw: x
from benchmark import run as harness
init = harness.Cell.__init__
def four_chips(self, name, rehearse):
    init(self, name, rehearse)
    self.config["mesh"] = [4, 1]
harness.Cell.__init__ = four_chips
sys.exit(harness.main(["--workload", {cell!r}, "--seed", "20261016", "--seconds", "0.5",
                       "--trace", "0", "--rehearse"]))
"""


def test_shard_local_resolve():
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SHARD_LOCAL.format(root=ROOT, cell=CELL)],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == harness.EXIT_REHEARSAL, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert line["correct"] is False
    assert line["checks"]["reps_off"]["value"] > 0


def test_controls_fail_the_limits():
    """16-bit reference signatures against 32-bit ones, and the host's
    shard-local resolve of the cell's 4x1 mesh."""
    cell = Cell(CELL, rehearse=True)
    reading = control_sharded.control_readings(cell, 7, say, shards=4)
    assert "reps_off" in failed(reading["sig16"], cell)
    assert "reps_off" in failed(reading["shard_local"], cell)
