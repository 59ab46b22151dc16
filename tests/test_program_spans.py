"""The program's own spans and stage clocks on the blocking path.

- every ``obs.trace`` span is also an ``astpu.<name>`` profiler
  annotation where jax is loaded, so a profiler trace holds the rerank
  tier's seven sub-spans inside ``astpu.dedup.rerank`` on a host plane;
- the seven ``rerank_*`` clocks tile the hook (their sum is at most its
  wall time) and, like ``resolve_wait`` and ``decisions``, are added
  once per corpus;
- the matcher's row build, mask readback wait and verify-pool clocks
  (busy and queue, from the worker processes) are added once per chunk;
- the resolve's phases and the fused tile step carry ``jax.named_scope``
  names in their lowered programs;
- importing the span module never loads jax, and a dispatch span reads
  no environment and sets no gauge.
"""

from __future__ import annotations

import glob
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np
import pandas as pd
import pytest

from advanced_scrapper_tpu.config import DedupConfig
from advanced_scrapper_tpu.obs import devprof, telemetry
from advanced_scrapper_tpu.pipeline.dedup import NearDupEngine
from advanced_scrapper_tpu.pipeline.matcher import (
    EntityIndex,
    make_verify_pool,
    match_chunk,
    process_json_data,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RERANK_STAGES = (
    "rerank_readback",
    "rerank_worklist",
    "rerank_sketch",
    "rerank_settle",
    "rerank_exact",
    "rerank_cluster",
    "rerank_evict",
)
RERANK_SPANS = tuple("dedup.rerank." + s.split("_", 1)[1] for s in RERANK_STAGES)


def _stage_totals() -> dict[str, tuple[float, int]]:
    return {
        h.labels["stage"]: (h.sum, h.count) for h in telemetry.stage_histograms()
    }


def _delta(before: dict, after: dict) -> dict[str, tuple[float, int]]:
    return {
        k: (s - before.get(k, (0.0, 0))[0], c - before.get(k, (0.0, 0))[1])
        for k, (s, c) in after.items()
    }


def _planted_corpus(seed: int = 3, n_base: int = 40) -> list[bytes]:
    """Families of a 600-byte base and four mutants (3 to 26 bytes
    changed): pairs from far above the threshold to inside the rerank
    margin, so every sub-stage, exact re-settle included, does work."""
    rng = np.random.RandomState(seed)
    docs = []
    for _ in range(n_base):
        base = bytearray(rng.randint(32, 127, size=600, dtype=np.uint8))
        docs.append(bytes(base))
        for k in (3, 12, 20, 26):
            mut = bytearray(base)
            for _ in range(k):
                mut[rng.randint(0, len(mut))] = rng.randint(32, 127)
            docs.append(bytes(mut))
    return [docs[i] for i in rng.permutation(len(docs))]


class _HookTimer:
    """The engine's rerank hook, timed; every other attribute is the
    hook's own (the engine reads them)."""

    def __init__(self, inner):
        self.inner = inner
        self.seconds = 0.0

    def __getattr__(self, name):
        if name == "inner":
            raise AttributeError(name)
        return getattr(self.inner, name)

    def __call__(self, *args):
        t0 = time.perf_counter()
        out = self.inner(*args)
        self.seconds += time.perf_counter() - t0
        return out


def _host_spans(xplane: str) -> list[tuple[str, str, float, float]]:
    """``(thread line, name, start_ns, end_ns)`` of every ``astpu.`` event
    on the trace's host planes."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("astpu."):
                    out.append((line.name, e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


@pytest.fixture(scope="module")
def traced_corpus(tmp_path_factory):
    """One warm planted corpus through ``dedup_reps`` (rerank tier on)
    under a CPU profiler trace, with its stage-clock deltas and the
    hook's own wall time."""
    import jax

    eng = NearDupEngine(
        DedupConfig(rerank_tile_rows=64, rerank_sketch=256, batch_size=256)
    )
    docs = _planted_corpus()
    eng.dedup_reps(docs)  # compiles outside the traced corpus
    hook = eng.rerank_hook = _HookTimer(eng.rerank_hook)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    before = _stage_totals()
    jax.profiler.start_trace(log_dir)
    try:
        eng.dedup_reps(docs)
    finally:
        jax.profiler.stop_trace()
    clocks = _delta(before, _stage_totals())
    (xplane,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    return SimpleNamespace(
        clocks=clocks,
        hook_s=hook.seconds,
        stats=dict(eng.rerank_tier.stats),
        spans=_host_spans(xplane),
    )


@pytest.mark.parametrize("span", RERANK_SPANS)
def test_rerank_sub_span_on_profiler_trace_inside_parent(traced_corpus, span):
    spans = traced_corpus.spans
    parents = [s for s in spans if s[1] == "astpu.dedup.rerank"]
    assert len(parents) == 1, [s[1] for s in spans]
    line, _name, p0, p1 = parents[0]
    children = [s for s in spans if s[1] == "astpu." + span]
    assert children, f"no astpu.{span} on the trace"
    for c_line, _n, c0, c1 in children:
        assert c_line == line and p0 <= c0 <= c1 <= p1, (span, (c0, c1), (p0, p1))


def test_rerank_clocks_tile_the_hook(traced_corpus):
    stats = traced_corpus.stats
    assert stats["exact_checks"] > 0 and stats["tiles"] > 0, stats
    total = sum(traced_corpus.clocks[s][0] for s in RERANK_STAGES)
    assert 0 < total <= traced_corpus.hook_s + 1e-3, (total, traced_corpus.hook_s)


@pytest.mark.parametrize("stage", RERANK_STAGES + ("resolve_wait", "decisions"))
def test_dedup_reps_adds_stage_clock_once_per_corpus(traced_corpus, stage):
    seconds, count = traced_corpus.clocks.get(stage, (0.0, 0))
    assert seconds > 0 and count == 1, (stage, seconds, count)


@pytest.mark.parametrize(
    "span", ("dedup.resolve", "dedup.decisions"),
)
def test_oneshot_resolve_spans_on_profiler_trace(traced_corpus, span):
    assert any(s[1] == "astpu." + span for s in traced_corpus.spans)


def test_span_module_leaves_jax_unloaded():
    code = (
        "import sys\n"
        "from advanced_scrapper_tpu.obs import stages, trace\n"
        "with trace.span('probe'):\n"
        "    pass\n"
        "tally = stages.Tally()\n"
        "with tally.timed('probe_stage', 'probe.stage'):\n"
        "    pass\n"
        "tally.flush()\n"
        "assert 'jax' not in sys.modules, sorted(m for m in sys.modules if 'jax' in m)\n"
    )
    env = dict(os.environ, PYTHONPATH=REPO)
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def _match_frame(n: int = 24) -> pd.DataFrame:
    text = (
        "Apple shares rose after Tim Cook presented the new iPhone lineup "
        "to investors on Tuesday."
    )
    return pd.DataFrame([
        {
            "article_text": text if i % 3 == 0 else "nothing relevant here",
            "title": "Apple news" if i % 5 == 0 else "wrap",
            "date_time": "2020-06-01T12:00:00Z",
            "url": f"https://x/{i}.html",
        }
        for i in range(n)
    ])


def _entity() -> dict:
    return {
        "id_label": "Apple Inc.",
        "ticker": "AAPL",
        "country": ["United States"],
        "industry": ["technology"],
        "aliases": ["AAPL", "Apple"],
        "products": ["iPhone"],
        "subsidiaries": [],
        "owned_entities": [],
        "ceos": ["Tim Cook (Start: 2011-08-24T00:00:00Z)"],
        "board_members": [],
    }


@pytest.mark.parametrize("workers", [2, 0], ids=["pool", "serial"])
def test_match_chunk_adds_matcher_clocks_once_per_chunk(workers):
    index = EntityIndex(process_json_data([_entity()]))
    pool = make_verify_pool(index, workers=workers) if workers else None
    if workers and pool is None:
        pytest.skip("this host refuses verify worker processes")
    try:
        frame = _match_frame()
        match_chunk(frame, index, pool=pool)  # compiles the screen shapes
        before = _stage_totals()
        got = match_chunk(frame, index, pool=pool)
        clocks = _delta(before, _stage_totals())
    finally:
        if pool is not None:
            pool.shutdown()
    assert got, "the planted mentions must match"
    want = ["matcher_rows", "matcher_screen_wait", "matcher_verify_busy"]
    if pool is not None:
        want.append("matcher_verify_queue")
    else:
        assert clocks.get("matcher_verify_queue", (0.0, 0))[1] == 0
    for stage in want:
        seconds, count = clocks.get(stage, (0.0, 0))
        assert seconds > 0 and count == 1, (stage, seconds, count)
    # the readback wait is part of the screen's own clock
    assert clocks["matcher_screen_wait"][0] <= clocks["matcher_screen"][0]


def _resolve_lowering():
    import jax.numpy as jnp

    from advanced_scrapper_tpu.ops.lsh import resolve_rep_bands_from_ok

    rb = jnp.zeros((64, 4), jnp.int32)
    return resolve_rep_bands_from_ok.lower(
        rb, jnp.zeros((64, 4), bool), jnp.ones((64,), bool), jump_rounds=2
    )


def _tile_step_lowering():
    import jax.numpy as jnp

    from advanced_scrapper_tpu.core.hashing import make_params
    from advanced_scrapper_tpu.ops.minhash import make_fused_tile_step
    from advanced_scrapper_tpu.ops.pack import packed_nbytes

    step = make_fused_tile_step(make_params(), DedupConfig().backend)
    running = jnp.zeros((64, 128), jnp.uint32)
    packed = jnp.zeros((packed_nbytes(64, 1024),), jnp.uint8)
    return step.lower(running, packed, rows=64, width=1024, num_articles=64)


@pytest.mark.parametrize(
    "lower, scope",
    [
        (_resolve_lowering, "resolve.pull"),
        (_resolve_lowering, "resolve.scatter_min"),
        (_resolve_lowering, "resolve.jump"),
        (_tile_step_lowering, "minhash.fused_tile"),
    ],
    ids=["pull", "scatter_min", "jump", "fused_tile"],
)
def test_kernel_phases_carry_named_scopes(lower, scope):
    assert scope in lower().as_text(debug_info=True)


def test_dispatch_span_reads_no_environment_and_sets_no_gauge(monkeypatch):
    def refuse(*_a, **_kw):
        raise AssertionError("dispatch_span touched the environment or a gauge")

    monkeypatch.setattr(telemetry.REGISTRY, "gauge", refuse)
    monkeypatch.setattr(os, "environ", SimpleNamespace(get=refuse, __getitem__=refuse))
    for _ in range(2):  # the second span runs on the cached histogram
        with devprof.dispatch_span("quiet_kernel", rows=8, width=8):
            pass
    monkeypatch.undo()
    lat = telemetry.REGISTRY.find(devprof.DISPATCH_HISTOGRAM)
    assert sum(h.count for h in lat if h.labels.get("kernel") == "quiet_kernel") == 2
