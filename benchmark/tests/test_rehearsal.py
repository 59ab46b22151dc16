"""Each cell end to end at its rehearsal size on the CPU, as the driver
would start it, plus the refusal to run without a chip."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = [w["name"] for w in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(*args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false",
               **(env_extra or {}))
    return subprocess.run([sys.executable, "benchmark/run.py", *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_last_line(cell, trace):
    p = run("--workload", cell, "--seed", str(2**31 + 12345), "--seconds", "1",
            "--trace", trace, "--rehearse")
    assert p.returncode == 3, p.stderr[-3000:]  # a rehearsal is never a chip run
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) == CONTRACT_KEYS | {"checks"}  # breakdown only on a chip
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["metrics"] == {}  # no CPU number under a device metric's name
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    tail = p.stderr.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and "(limit " in t for t in tail)


@pytest.mark.parametrize("cell", CELLS[:1])
def test_no_tpu_exits_nonzero_without_result(cell):
    p = run("--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bare_checkout_exits_nonzero_without_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files has
    no system to run: the run fails and prints no result."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0", "--rehearse"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode not in (0, 3)
    assert p.stdout.strip() == ""
