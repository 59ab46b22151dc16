"""Flagship benchmark: MinHash(k=5, 128-perm) + 16-band LSH dedup throughput.

Prints ONE JSON line with three measured regimes:

- ``value`` (headline, drives ``vs_baseline``): steady-state pipelined
  device throughput on uniform 1024-byte device-resident batches — the
  kernel ceiling.
- ``ragged_articles_per_sec``: the SURVEY §7 hard regime — a realistic
  article-length distribution (1e2..1e5 bytes, log-normal body + heavy
  tail) through the full host path: ``encode_blocks`` bucketed/blockwise
  encode → fixed-shape signature batches → per-article segment-min combine
  → LSH resolve.  Includes host encode time; measured warm (second corpus
  of identical config — no recompilation across corpora).
- ``stream_articles_per_sec``: the composed production path —
  ``HostBatcher.push_many`` (C++ MPMC queue) → ``DeviceFeed`` prefetch →
  sharded dedup step → tag-indexed representatives on host.  End-to-end
  wall clock from first push to last result.

The baseline is the north-star target from BASELINE.json: 50,000
articles/s on a TPU v5e-8 at ≥0.95 recall.  This driver runs on however
many chips are visible (one, under the current harness).

Sweep knobs (env):
  ASTPU_BENCH_QUICK=1         small shapes for smoke runs
  ASTPU_BENCH_BACKEND=...     scan (default) | oph | pallas
  ASTPU_BENCH_BATCH=N         uniform/stream batch size (default 65536)
  ASTPU_BENCH_FEED_WORKERS=N  DeviceFeed put threads for the stream regime
  ASTPU_DEDUP_PUT_WORKERS=N   H2D put threads in the dispatch executor
  ASTPU_DEDUP_DISPATCH_WINDOW=N  in-flight tile window depth (0 = auto)
  ASTPU_DEDUP_RERANK=0|1      precision rerank tier on/off — wins over
                              every regime pin (throughput regimes pin
                              it OFF for bench-history comparability;
                              the rerank regime pins it ON)
  ASTPU_DEDUP_RERANK_TILE_ROWS=N  settle-tile row budget for the packed
                              pair tiles of the rerank regime
  ASTPU_MATCH_DISPATCH_WINDOW=N  matcher screen-tile window depth
  ASTPU_MATCH_SCREEN_TILE_BYTES=N  byte budget per packed screen tile
  ASTPU_BENCH_MESH=DxS        (data, seq) mesh factorisation for the
                              sharded regime (default: all devices on the
                              data axis); the result JSON carries the
                              shape + per-shard put/dispatch/byte ledger
  JAX_COMPILATION_CACHE_DIR=dir  persistent XLA compilation cache
                              (default: the checkout's .jax_cache) — warmup
                              vs steady-state are reported separately
                              (ragged_warmup_articles_per_sec /
                              stream_warmup_s) so the effect is visible

Per-regime device-traffic accounting (always-on counters,
obs/stages.py): the ragged/stream/matcher JSON carries
``<regime>_device_puts`` / ``<regime>_device_dispatches`` /
``<regime>_h2d_bytes`` deltas (matcher: steady-state window only, with
``matcher_warmup_articles_per_sec`` reported apart like the ragged
split), and the exact regime names WHICH tier served
(``exact_backend``; ``exact_backend_reason`` when the native tiers
were unavailable — a silently degraded host tier once cost the exact
regime 4.5×).

Observability (the telemetry plane rides the bench):
  --regime NAME               run one regime (uniform|ragged|stream|sharded|
                              rerank|recall|exact|matcher|index|fleet)
                              instead of the full battery; the JSON line
                              carries only that regime's keys.  The rerank
                              regime measures the precision tier on a
                              near-dup-heavy corpus and gates its
                              tiles+1-launch budget via the always-on
                              ``astpu_rerank_launch_excess`` gauge (SLO
                              ``rerank_launch_budget``)
  ASTPU_TELEMETRY=1           serve live GET /metrics + /status for the
                              whole run (port: ASTPU_METRICS_PORT, default
                              ephemeral — address printed to stderr); the
                              stage histograms behind stage_ms are the same
                              numbers, by construction (obs/stages.py)
  ASTPU_TRACE_DIR=DIR         wrap the measured regimes in
                              jax.profiler.trace(DIR) (obs/profiler.xla_trace)

Every run's JSON also carries ``telemetry``: the end-of-run aggregated
series ledger (always-on device counters, stage histograms with
percentiles, event counters — the full registry under ASTPU_TELEMETRY)
plus the declared-SLO verdict (``obs/slo.py``: per-stage p99 ceilings,
RPC error-ratio budget), so a BENCH_*.json is a complete record, not just
headline rates.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np


def _bench_uniform(jax, mesh, params, backend: str, batch: int, block: int) -> float:
    from advanced_scrapper_tpu.parallel.sharded import make_sharded_dedup, shard_batch

    iters = 10
    rng = np.random.RandomState(0)
    # one distinct input buffer per in-flight step: steady-state timing must
    # not benefit from same-buffer effects or any transport-level caching of
    # repeated (program, input) pairs
    feeds = []
    for _ in range(iters):
        tok = rng.randint(32, 127, size=(batch, block)).astype(np.uint8)
        lengths = np.full((batch,), block, dtype=np.int32)
        # plant 25% duplicates so the merge path does real work
        dup_src = rng.randint(0, batch // 2, size=batch // 4)
        tok[batch // 2 : batch // 2 + batch // 4] = tok[dup_src]
        feeds.append(shard_batch(tok, lengths, mesh))

    step = make_sharded_dedup(mesh, params, backend=backend)

    rep, _hist = step(*feeds[0])  # warmup / compile
    jax.block_until_ready(rep)

    # Steady-state pipelined throughput: the production regime is a stream of
    # batches with dispatch overlapping device compute (per-step host syncs
    # would only measure the control-channel round trip, not the device).
    rounds = []
    for _ in range(3):
        t0 = time.perf_counter()
        outs = [step(*feeds[i]) for i in range(iters)]
        jax.block_until_ready(outs)
        rounds.append((time.perf_counter() - t0) / iters)
    return batch / float(np.median(rounds))


def _ragged_corpus(rng: np.random.RandomState, n: int) -> list[bytes]:
    """Realistic article lengths: log-normal body (median ~700 B), a 25%
    mid tail (4-20 kB) and a 5% long tail (20-100 kB); 20% planted dups."""
    u = rng.rand(n)
    body = rng.lognormal(mean=6.55, sigma=0.8, size=n)          # ~700 B median
    lens = np.clip(body, 100, 4000).astype(np.int64)
    mid = u > 0.70
    lens[mid] = rng.randint(4000, 20000, size=int(mid.sum()))
    long = u > 0.95
    lens[long] = rng.randint(20000, 100000, size=int(long.sum()))
    docs: list[bytes] = []
    for i in range(n):
        if i >= 8 and rng.rand() < 0.20:
            docs.append(docs[rng.randint(0, i)])  # exact near-dup plant
        else:
            docs.append(rng.randint(32, 127, size=int(lens[i]), dtype=np.uint8).tobytes())
    return docs


def _rerank_corpus(rng: np.random.RandomState, n: int) -> list[bytes]:
    """Near-dup-heavy mix for the precision-tier regime: ~35% MUTATED
    dups (~1% edit rate — pairs land across the Jaccard knee instead of
    at J=1) so the settle kernel, margin band and eviction walk all do
    real work; the rest is the ragged length mix capped at 8 kB."""
    docs: list[bytes] = []
    for i in range(n):
        if i >= 8 and rng.rand() < 0.35:
            src = bytearray(docs[rng.randint(0, i)])
            for _ in range(max(1, len(src) // 100)):
                src[rng.randint(0, len(src))] = rng.randint(32, 127)
            docs.append(bytes(src))
        else:
            ln = int(np.clip(rng.lognormal(6.55, 0.8), 100, 8000))
            docs.append(
                rng.randint(32, 127, size=ln, dtype=np.uint8).tobytes()
            )
    return docs


def _ragged_engine(**pins):
    """The ragged-regime engine, built from env so the ASTPU_DEDUP_* sweep
    knobs (notably ASTPU_DEDUP_PUT_WORKERS, the threaded-H2D axis) actually
    reach it — ``NearDupEngine()`` raw defaults silently ignored them.
    ``put_workers=0`` (the default) resolves per transport inside the
    engine (``pipeline.dedup.resolve_put_workers``).

    The throughput regimes pin ``rerank=False`` (via ``pins``) so their
    rates stay comparable against the pre-tier bench history — the tier
    has its own regime — but an explicit ``ASTPU_DEDUP_RERANK`` always
    wins over a pin."""
    from advanced_scrapper_tpu.config import DedupConfig, from_env
    from advanced_scrapper_tpu.pipeline.dedup import NearDupEngine

    if "ASTPU_DEDUP_RERANK" in os.environ:
        pins.pop("rerank", None)
    return NearDupEngine(from_env(DedupConfig, "dedup", **pins))


def _bench_ragged(
    n_articles: int, n_corpora: int = 4
) -> tuple[float, float, dict]:
    """``(warmup_rate, steady_rate, device_counter_deltas)`` over
    distinct corpora; the counter deltas window ONLY the steady-state
    corpora (the warmup corpus compiles and must not inflate the
    per-tile traffic the JSON gates).

    Corpus 0 (timed separately — the warmup figure) compiles every shape
    the config draws: width buckets, the O(log bs) tile chunks, the
    bucketed article axis.  With the persistent compile cache the warmup
    figure converges toward the steady one across processes (compiles
    become cache loads) — reporting them apart is what makes that
    visible.  Steady state: every corpus's full dedup is dispatched async
    (``dedup_reps_async``) before any result is synced, so corpus i+1's
    encode/H2D/compute overlap corpus i's readback — the production
    firehose regime (the reference analogue never stalls between 20k-row
    chunks, match_keywords.py:227-230).  Distinct corpora defeat
    transport-level (program, input) caching."""
    from advanced_scrapper_tpu.obs import devprof, stages

    rng = np.random.RandomState(7)
    engine = _ragged_engine(rerank=False)
    t0 = time.perf_counter()
    # warm the SAME path the steady loop times (dedup_reps_async →
    # fused resolve epilogue) — warming the oneshot path would leave the
    # steady window paying the fused-resolve compile it exists to exclude
    warm = np.asarray(engine.dedup_reps_async(_ragged_corpus(rng, n_articles)))
    assert warm.shape[0] >= n_articles
    warm_rate = n_articles / (time.perf_counter() - t0)
    corpora = [_ragged_corpus(rng, n_articles) for _ in range(n_corpora)]
    dc0 = stages.device_counters()
    jc0 = devprof.jit_compiles_total()
    t0 = time.perf_counter()
    reps_dev = [engine.dedup_reps_async(c) for c in corpora]
    with stages.timed("resolve"):  # rep readback: the device queue drains here
        reps = [np.asarray(r)[:n_articles] for r in reps_dev]
    dt = time.perf_counter() - t0
    dc1 = stages.device_counters()
    for r in reps:
        assert r.shape == (n_articles,)
    deltas = {k: int(dc1[k] - dc0[k]) for k in dc0}
    # recompile sentinel, windowed like the device counters: a healthy
    # steady state reads 0 (the warmup corpus owns every compile) — a
    # nonzero here IS the recompile storm the prewarmed shape set exists
    # to prevent, attributable from the JSON alone
    deltas["jit_compiles"] = int(devprof.jit_compiles_total() - jc0)
    return warm_rate, n_articles * n_corpora / dt, deltas


def _bench_sharded(
    jax, n_articles: int, n_corpora: int = 3
) -> tuple[float, float, dict, dict, dict]:
    """``(warmup_rate, steady_rate, totals, per_shard, mesh_shape)`` —
    the pod-shape regime: the ragged workload through
    ``NearDupEngine.dedup_reps_sharded``'s PACKED plane (per-shard fused
    donated tiles, pmin combine epilogue) on a mesh over every visible
    device.  ``ASTPU_BENCH_MESH=DxS`` pins the (data, seq) factorisation
    (default: all devices on the data axis — shard count is the device
    count either way).  The always-on shard-labelled counters window the
    steady corpora only, so the per-shard 1-put/1-dispatch contract is a
    reported number per shard, and the max−min put skew lands on the
    ``astpu_sharded_put_skew`` gauge the declared SLO set gates at 0."""
    from advanced_scrapper_tpu.core.mesh import build_mesh, parse_mesh_shape
    from advanced_scrapper_tpu.obs import devprof, stages

    ndev = len(jax.devices())
    spec = os.environ.get("ASTPU_BENCH_MESH")
    dp, sp = parse_mesh_shape(spec) if spec else (ndev, 1)
    mesh = build_mesh(dp, sp)
    engine = _ragged_engine(rerank=False)
    rng = np.random.RandomState(7)
    t0 = time.perf_counter()
    warm = engine.dedup_reps_sharded(_ragged_corpus(rng, n_articles), mesh)
    assert warm.shape[0] == n_articles
    warm_rate = n_articles / (time.perf_counter() - t0)
    corpora = [_ragged_corpus(rng, n_articles) for _ in range(n_corpora)]
    dc0 = stages.device_counters()
    ps0 = stages.sharded_device_counters()
    jc0 = devprof.jit_compiles_total()
    t0 = time.perf_counter()
    for c in corpora:
        rep = engine.dedup_reps_sharded(c, mesh)
        assert rep.shape == (n_articles,)
    dt = time.perf_counter() - t0
    dc1 = stages.device_counters()
    ps1 = stages.sharded_device_counters()
    totals = {k: int(dc1[k] - dc0[k]) for k in dc0}
    totals["jit_compiles"] = int(devprof.jit_compiles_total() - jc0)
    per_shard = {
        s: {
            k: int(ps1[s][k] - ps0.get(s, {}).get(k, 0.0)) for k in ps1[s]
        }
        for s in sorted(ps1, key=int)
    }
    stages.record_sharded_put_skew(ps0)  # steady window → the gauge_max SLO
    mesh_shape = {"data": dp, "seq": sp, "shards": dp * sp}
    return warm_rate, n_articles * n_corpora / dt, totals, per_shard, mesh_shape


def _bench_rerank(
    n_articles: int, n_corpora: int = 3
) -> tuple[float, float, dict]:
    """``(warmup_rate, steady_rate, deltas)`` for the precision tier:
    the near-dup-heavy corpus (``_rerank_corpus``) through the DEFAULT
    engine with the rerank tier pinned ON (``ASTPU_DEDUP_RERANK`` still
    wins, like every pin).

    The deltas window ONLY the steady corpora, on the tier's own
    ``"rerank"`` regime ledger (``obs.stages.regime_device_counters``),
    and carry the launch-count gate as data: a settled corpus costs
    exactly ``tiles + 1`` device_puts (settle tiles + the fold-init
    buffer) and ``tiles + 1`` dispatches (settle tiles + finalize).  Any
    surplus lands on the always-on ``astpu_rerank_launch_excess`` gauge
    the declared SLO set gates at 0 — the single-dispatch contract is a
    machine-checked verdict, not prose.  The warmup corpus owns the
    compiles (the engine prewarm compiles the whole shared
    ``tile_rows_options`` shape set first, so steady corpora with
    different pair counts still hit compiled settle tiles)."""
    from advanced_scrapper_tpu.obs import devprof, stages, telemetry

    engine = _ragged_engine(rerank=True)
    if engine.rerank_tier is None:
        raise RuntimeError(
            "rerank regime needs the tier: unset ASTPU_DEDUP_RERANK=0"
        )
    rng = np.random.RandomState(11)
    engine.prewarm(n_articles)
    t0 = time.perf_counter()
    warm = engine.dedup_reps(_rerank_corpus(rng, n_articles))
    assert warm.shape[0] == n_articles
    warm_rate = n_articles / (time.perf_counter() - t0)
    corpora = [_rerank_corpus(rng, n_articles) for _ in range(n_corpora)]
    rr0 = stages.regime_device_counters("rerank")
    jc0 = devprof.jit_compiles_total()
    tiles = pairs = 0
    t0 = time.perf_counter()
    for c in corpora:
        rep = engine.dedup_reps(c)
        assert rep.shape == (n_articles,)
        tiles += int(engine.rerank_tier.stats.get("tiles", 0))
        pairs += int(engine.rerank_tier.stats.get("pairs", 0))
    dt = time.perf_counter() - t0
    rr1 = stages.regime_device_counters("rerank")
    deltas = {k: int(rr1[k] - rr0[k]) for k in rr0}
    deltas["jit_compiles"] = int(devprof.jit_compiles_total() - jc0)
    deltas["tiles"] = tiles
    deltas["pairs"] = pairs
    budget = tiles + n_corpora  # per corpus: tiles + fold-init/finalize
    excess = (
        deltas["device_puts"] + deltas["device_dispatches"] - 2 * budget
    )
    telemetry.REGISTRY.gauge(
        "astpu_rerank_launch_excess",
        "rerank-plane puts+dispatches beyond 2*(tiles + corpora) in the "
        "bench steady window (0 = single-dispatch contract held)",
        always=True,
    ).set(float(excess))
    return warm_rate, n_articles * n_corpora / dt, deltas


def _feed_workers() -> int | None:
    """DeviceFeed worker count for the stream regime (and its profiler —
    one lookup so the decomposition always matches the benchmark).
    ``None`` (knob unset) defers to the product default:
    ``DeviceFeed``'s own (one worker), so bench measures exactly what
    production defaults run."""
    env = os.environ.get("ASTPU_BENCH_FEED_WORKERS")
    return int(env) if env is not None else None


def _stream_corpus(batch: int, block: int, seed: int = 3):
    """The stream regime's doc corpus: uniform rows, 25% planted dups.
    Shared with ``tools/profile_stream.py`` / ``profile_host_composition.py``
    so the per-stage profilers decompose EXACTLY this benchmark's pipeline."""
    rng = np.random.RandomState(seed)
    base = rng.randint(32, 127, size=(batch, block), dtype=np.uint8)
    dup_src = rng.randint(0, batch // 2, size=batch // 4)
    base[batch // 2 : batch // 2 + batch // 4] = base[dup_src]
    return base, [base[i].tobytes() for i in range(batch)]


def _bench_stream(
    jax, mesh, params, backend: str, batch: int, block: int, n_batches: int
) -> float:
    """push_many → DeviceFeed prefetch → sharded dedup → tags on host."""
    from advanced_scrapper_tpu.cpu.hostbatch import HostBatcher
    from advanced_scrapper_tpu.parallel.sharded import make_sharded_dedup, shard_batch
    from advanced_scrapper_tpu.pipeline.feed import DeviceFeed

    total = batch * n_batches
    base, docs = _stream_corpus(batch, block)

    step = make_sharded_dedup(mesh, params, backend=backend)
    warm = shard_batch(base, np.full((batch,), block, np.int32), mesh)
    t0 = time.perf_counter()
    jax.block_until_ready(step(*warm))  # compile outside the timed region
    _bench_stream.last_warmup_s = time.perf_counter() - t0

    batcher = HostBatcher(block)
    # >1 worker overlaps device_put round trips on serializing transports
    feed = DeviceFeed(batcher, batch, depth=4, workers=_feed_workers())

    def produce():
        # feed() chunks through push_many with bounded-backpressure retries —
        # no O(n²) re-slicing of the remaining docs (the r2 producer
        # re-sliced docs[pushed:] on every retry).
        for b in range(n_batches):
            batcher.feed(docs, start_tag=b * batch, chunk=4096)
        batcher.close()

    t0 = time.perf_counter()
    producer = threading.Thread(target=produce, daemon=True)
    producer.start()
    seen = 0
    pending: list[tuple[object, np.ndarray, int]] = []
    from advanced_scrapper_tpu.obs import stages as _stages

    for n, tok_dev, len_dev, tags in feed:
        rep, _hist = step(tok_dev, len_dev)
        _stages.count_dispatch("stream")
        try:
            rep.copy_to_host_async()  # readback streams behind compute
        except AttributeError:
            pass
        pending.append((rep, tags, n))  # sync nothing inside the loop
        seen += n
    rep_tags = [tags[np.asarray(rep)[:n]] for rep, tags, n in pending]
    dt = time.perf_counter() - t0
    producer.join(timeout=30)
    feed.join()
    assert seen == total, (seen, total)
    assert sum(r.shape[0] for r in rep_tags) == total
    return total / dt


def _bench_recall(n_bases: int) -> tuple[float, int, float, float, int]:
    """Measured near-dup recall vs datasketch-semantics oracle on the
    hardened certification corpus (ragged 100 B–100 kB lengths, pairs
    planted across the Jaccard knee) — the driver-visible twin of
    ``tests/test_recall_vs_oracle.py::test_near_dup_recall_certification_hardened``
    so recall is tracked per round, not just pass/fail (BASELINE bar ≥0.95)."""
    from advanced_scrapper_tpu.core.hashing import make_params
    from advanced_scrapper_tpu.cpu.oracle import (
        build_certification_corpus,
        measured_precision,
        measured_recall,
        oracle_near_dup_pairs,
        oracle_reps,
    )
    from advanced_scrapper_tpu.pipeline.dedup import NearDupEngine

    rng = np.random.RandomState(7)
    params = make_params()
    texts = build_certification_corpus(rng, n_bases, n_long=min(12, n_bases // 8))
    reps = NearDupEngine().dedup_reps(texts)
    opairs = oracle_near_dup_pairs(texts, params, 0.7, fast=True)
    recall, pairs = measured_recall(texts, reps, params, 0.7, pairs=opairs)
    precision, _merged, unchained = measured_precision(
        texts, reps, params.shingle_k, 0.7
    )
    # comparator: the oracle's own datasketch+union-find clustering scored
    # by the same metric — the engine's bar is oracle−ε, not an
    # unreachable 1.0 (transitive closure legitimately merges sub-threshold
    # mutant-mutant pairs on both sides)
    precision_oracle, _omerged, _ounchained = measured_precision(
        texts, oracle_reps(texts, params, 0.7, pairs=opairs), params.shingle_k, 0.7
    )
    return recall, pairs, precision, precision_oracle, unchained


def _bench_exact(n_urls: int) -> tuple[float, float, float, float, str, str]:
    """Exact-dedup throughput on URL-shaped rows, and the speedup vs the
    pandas path it byte-identically replaces (``drop_duplicates`` at
    ``yahoo_links_selenium.py:174``).  Parity is asserted, not assumed.

    Both sides are best-of-N over the SAME pinned corpus: the r4 record
    showed a single-shot pandas timing fluctuating ~4× run-to-run
    (exact_vs_pandas 1.43 → 0.29 while the device side moved <10%), so a
    one-shot ratio is noise, not a metric.  Returns
    ``(urls_per_s, ratio, exact_ms, pandas_ms, backend, reason)`` —
    absolute times travel with the ratio so a swing is attributable from
    the JSON alone, and ``backend`` names WHICH tier actually served the
    timed calls ("zero-copy" | "blob" | "grouping"): BENCH_r05's 0.22×
    "regression" was the grouping fallback silently running where the
    native tiers should have (an unreported build failure — ``reason``
    now carries it)."""
    import pandas as pd

    from advanced_scrapper_tpu.cpu import exactdedup as _ed
    from advanced_scrapper_tpu.cpu import hostbatch as _hb
    from advanced_scrapper_tpu.pipeline.dedup import ExactDedup

    def make_urls(seed: int) -> list[str]:
        r = np.random.RandomState(seed)
        base = [
            f"https://news.example/{r.randint(1 << 30)}/article-{i}.html"
            for i in range(int(n_urls * 0.8))
        ]
        urls = base + [base[r.randint(len(base))] for _ in range(n_urls - len(base))]
        r.shuffle(urls)
        return urls

    dedup = ExactDedup()
    dedup.keep_indices(make_urls(1))  # warm every compiled shape
    urls = make_urls(2)
    best = best_pandas = float("inf")
    kept = expected = None
    for _ in range(5):
        t0 = time.perf_counter()
        kept = dedup.keep_indices(urls)
        best = min(best, time.perf_counter() - t0)
        # frame construction stays inside the timing: the reference path
        # being replaced starts from the python list too (:174), and r1-r4
        # measured it that way — changing the boundary would shift the
        # ratio for a non-performance reason
        t0 = time.perf_counter()
        expected = (
            pd.DataFrame({"url": urls})
            .drop_duplicates(subset=["url"])
            .index.tolist()
        )
        best_pandas = min(best_pandas, time.perf_counter() - t0)
    assert kept == expected, "exact dedup must stay byte-identical to pandas"
    backend = dedup.last_path
    reason = ""
    if backend == "grouping":  # neither native tier served — say why
        reason = (
            _ed.backend_reason() or _hb.backend_reason() or "unknown"
        )
    return (
        n_urls / best, best_pandas / best, best * 1e3, best_pandas * 1e3,
        backend, reason,
    )


def _matcher_workload(n_articles: int):
    """``(EntityIndex, articles DataFrame)`` — the matcher regime's fixed
    synthetic workload, shared with ``tools/profile_hostpath.py --device``
    so the per-tile timeline decomposes EXACTLY this benchmark's
    pipeline."""
    import pandas as pd

    from advanced_scrapper_tpu.pipeline.matcher import (
        EntityIndex,
        process_json_data,
    )

    entities = [
        {
            "id_label": f"Company{i} Corp.",
            "ticker": f"TK{i:02d}",
            "country": ["United States"],
            "industry": ["technology"],
            "aliases": [f"TK{i:02d}", f"Company{i}"],
            "products": [f"Gadget{i} Pro"],
            "subsidiaries": [],
            "owned_entities": [],
            "ceos": [f"Ceo Person{i} (Start: 2011-08-24T00:00:00Z)"],
            "board_members": [],
        }
        for i in range(64)
    ]
    index = EntityIndex(process_json_data(entities))

    rng = np.random.RandomState(13)
    vocab = [
        "".join(chr(97 + c) for c in rng.randint(0, 26, size=rng.randint(3, 10)))
        for _ in range(2000)
    ]

    def article(i: int) -> str:
        words = [vocab[w] for w in rng.randint(0, len(vocab), size=300)]
        if i % 4 == 0:  # 25% of articles mention entities (screen must pass)
            e = int(rng.randint(64))
            words[10:10] = [f"Company{e}", "Corp.", "said", f"Ceo", f"Person{e}"]
        return " ".join(words)

    df = pd.DataFrame(
        {
            "article": [article(i) for i in range(n_articles)],
            "title": ["market wrap" for _ in range(n_articles)],
            "datetime": ["2020-01-02 10:00:00" for _ in range(n_articles)],
        }
    )
    return index, df


def _bench_matcher(n_articles: int) -> tuple[float, float, dict]:
    """``(warmup_rate, steady_rate, device_counter_deltas)`` through the
    second north-star workload: device q-gram screen + pooled host
    exact-verify over a fixed synthetic entity set (the
    ``match_keywords.py:159-180`` reroute).  Like the ragged dedup
    regime, the first full chunk (which compiles the screen tile-shape
    set — with the persistent compile cache those become cache loads) is
    timed separately from the steady best-of-3, and the always-on device
    counters window ONLY the steady passes — the per-tile 1-put/1-dispatch
    contract is a reported number, not prose."""
    from advanced_scrapper_tpu.obs import devprof, stages
    from advanced_scrapper_tpu.pipeline.matcher import (
        make_verify_pool,
        match_chunk,
    )

    index, df = _matcher_workload(n_articles)
    pool = make_verify_pool(index)  # None on single-core hosts
    dt = float("inf")
    try:
        t0 = time.perf_counter()
        match_chunk(df, index, pool=pool)  # warm compile, full shape set
        warm_rate = n_articles / (time.perf_counter() - t0)
        dc0 = stages.device_counters()
        jc0 = devprof.jit_compiles_total()
        for _ in range(3):  # best-of-N: single-shot swung 38% r3→r4
            t0 = time.perf_counter()
            out = match_chunk(df, index, pool=pool)
            dt = min(dt, time.perf_counter() - t0)
        dc1 = stages.device_counters()
    finally:
        if pool is not None:
            pool.shutdown()
    assert len(out) >= n_articles // 8, "planted mentions must match"
    deltas = {k: int(dc1[k] - dc0[k]) for k in dc0}
    deltas["jit_compiles"] = int(devprof.jit_compiles_total() - jc0)
    return warm_rate, n_articles / dt, deltas


def _bench_fleet(n_docs: int, nb: int = 17) -> dict:
    """The sharded index fleet (``index/fleet.py``): the SAME
    check_and_add workload as the ``index`` regime, but through a 2-shard
    × 2-replica fleet of in-process ``IndexShardServer``s over real TCP —
    so the figure pays consistent-hash partitioning, RPC framing, the
    synchronous replica write, and the parallel fan-out.  Read next to
    ``index_insert_rows_per_sec`` it IS the fleet tax (or win, once
    shards live on separate hosts)."""
    import shutil
    import tempfile

    from advanced_scrapper_tpu.index.fleet import ShardedIndexClient
    from advanced_scrapper_tpu.index.remote import IndexShardServer

    rng = np.random.RandomState(13)
    B = 2048
    n_batches = max(1, n_docs // B)
    base = tempfile.mkdtemp(prefix="astpu-bench-fleet-")
    servers = []
    client = None
    try:
        cut = max(1 << 14, (n_docs * nb) // 10)
        parts = []
        for s in range(2):
            nodes = []
            for r in range(2):
                srv = IndexShardServer(
                    os.path.join(base, f"s{s}n{r}"),
                    spaces=("bands",),
                    cut_postings=cut,
                    compact_segments=6,
                    compact_inline=True,
                    name=f"s{s}n{r}",
                ).start()
                servers.append(srv)
                nodes.append(f"127.0.0.1:{srv.port}")
            parts.append("|".join(nodes))
        client = ShardedIndexClient(
            ";".join(parts),
            space="bands",
            spill_dir=os.path.join(base, "spill"),
            timeout=30.0,
        )
        t_ins = 0.0
        probe_keys = []
        kept_rows: list[np.ndarray] = []
        for _ in range(n_batches):
            keys = rng.randint(0, 1 << 62, size=(B, nb)).astype(np.uint64)
            if kept_rows:
                src = kept_rows[rng.randint(len(kept_rows))]
                n_dup = B // 5
                keys[:n_dup] = src[rng.randint(0, src.shape[0], size=n_dup)]
            ids = client.allocate_doc_ids(B)
            t0 = time.perf_counter()
            attr = client.check_and_add_batch(keys, ids)
            t_ins += time.perf_counter() - t0
            kept_rows.append(keys[np.asarray(attr) < 0])
            probe_keys.append(keys)
        t0 = time.perf_counter()
        for keys in probe_keys:
            client.probe_batch(keys)
        t_probe = time.perf_counter() - t0
        total = B * n_batches
        return {
            "fleet_insert_rows_per_sec": round(total / t_ins, 1),
            "fleet_probe_rows_per_sec": round(total / t_probe, 1),
            "fleet_shards": 2,
            "fleet_replicas": 2,
        }
    finally:
        if client is not None:
            client.close()
        for srv in servers:
            srv.stop()
        shutil.rmtree(base, ignore_errors=True)


def _bench_index(n_docs: int, nb: int = 17) -> dict:
    """The persistent corpus index (``index/`` subsystem): probe+insert
    throughput through ``check_and_add_batch`` (WAL append + memtable +
    Bloom-guarded segment probes, 20% planted dup rows), then COLD reopen
    latency — manifest load, segment open (Blooms into RAM, postings
    memmap'd), WAL replay — plus a post-reopen probe pass over history.

    Everything is wall-clock against a real on-disk index in a temp dir;
    segment cuts and compaction happen at the production cadence logic, so
    the insert figure pays the real durability cost.
    """
    import shutil
    import tempfile

    from advanced_scrapper_tpu.index import PersistentIndex

    rng = np.random.RandomState(11)
    B = 2048
    n_batches = max(1, n_docs // B)
    base = tempfile.mkdtemp(prefix="astpu-bench-index-")
    try:
        # cadence sized so the run cuts ~10 segments and triggers at least
        # one compaction — the insert figure must pay the full lifecycle
        cut = max(1 << 14, (n_docs * nb) // 10)
        idx = PersistentIndex(
            os.path.join(base, "bands"),
            cut_postings=cut,
            compact_segments=6,
            compact_inline=True,  # pay compaction inside the timed region
        )
        t_ins = 0.0
        probe_keys = []
        kept_rows: list[np.ndarray] = []
        for _ in range(n_batches):
            keys = rng.randint(0, 1 << 62, size=(B, nb)).astype(np.uint64)
            if kept_rows:
                src = kept_rows[rng.randint(len(kept_rows))]
                n_dup = B // 5
                keys[:n_dup] = src[rng.randint(0, src.shape[0], size=n_dup)]
            ids = idx.allocate_doc_ids(B)
            t0 = time.perf_counter()
            attr = idx.check_and_add_batch(keys, ids)
            t_ins += time.perf_counter() - t0
            kept_rows.append(keys[np.asarray(attr) < 0])
            probe_keys.append(keys)
        idx.checkpoint()
        st = idx.stats()
        # pure-probe pass over the full history (hits + misses mixed)
        t0 = time.perf_counter()
        for keys in probe_keys:
            idx.probe_batch(keys)
        t_probe = time.perf_counter() - t0
        idx.close()
        # cold reopen: fresh process state (fresh object, same files)
        t0 = time.perf_counter()
        idx2 = PersistentIndex(os.path.join(base, "bands"), cut_postings=cut)
        reopen_s = time.perf_counter() - t0
        hit = idx2.probe_batch(probe_keys[0])
        assert (np.asarray(hit) >= 0).any(), "reopened index lost postings"
        idx2.close()
        total = B * n_batches
        return {
            "index_insert_rows_per_sec": round(total / t_ins, 1),
            "index_probe_rows_per_sec": round(total / t_probe, 1),
            "index_reopen_ms": round(reopen_s * 1e3, 2),
            "index_segments": st["segments"],
            "index_segment_bytes": st["segment_bytes"],
            "index_resident_bytes": st["resident_bytes"],
            "index_observed_bloom_fp": round(st["observed_bloom_fp"], 6),
        }
    finally:
        shutil.rmtree(base, ignore_errors=True)


#: Per-chip peaks keyed by ``jax.devices()[0].device_kind``.  Published
#: figures: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB HBM at 819 GB/s).  ``vpu_ops`` is derived, not
#: published: the TensorCore clock from the bf16 peak (197e12 =
#: 2·128·128 per MXU · 4 MXUs · clock → 1.5 GHz) times the nominal
#: 32-bit VPU rate (8 sublanes × 128 lanes × 4 ALUs); DESIGN.md
#: "Roofline".  A device kind missing here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "vpu_ops": 8 * 128 * 4 * 1.5e9,
    },
}


def device_peaks(device_kind: str) -> dict:
    """The :data:`PEAKS` row of ``device_kind``; raises for a kind with
    no published peaks (a roofline share against another chip's peak is
    a wrong number, not an approximate one)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table for device_kind {device_kind!r}; add its "
            "published peaks, with their source, to bench.PEAKS"
        ) from None


def _vpu_roofline(
    articles_per_s: float, block: int, params, platform: str, device_kind: str
) -> dict:
    """MFU-style utilisation of the headline kernel vs the chip's VPU.

    Ops counted per (shingle, permutation): multiply + add + min = 3
    32-bit lane ops for the ``a·h+b``/min update — the irreducible dense
    work; the k-byte shingle hash adds ~2k ops per shingle (noise).  This
    is the NOMINAL utilisation: TPU int32 multiplies decompose into
    multiple VPU passes (~6-8 16-bit partials), so the hardware-cycle
    utilisation is several times higher — both readings in DESIGN.md.
    A CPU run has no device utilisation (null); any other platform must
    have its ``device_kind`` in :data:`PEAKS`.
    """
    if platform == "cpu":
        return {"vpu_util_nominal": None}
    peak = device_peaks(device_kind)["vpu_ops"]
    shingles = block - params.shingle_k + 1
    ops_per_article = shingles * params.num_perm * 3 + shingles * 2 * params.shingle_k
    achieved = articles_per_s * ops_per_article
    return {
        "vpu_ops_per_article": ops_per_article,
        "vpu_achieved_ops_per_sec": round(achieved, 1),
        "vpu_util_nominal": round(achieved / peak, 4),
    }


def _platform_fingerprint(jax, platform: str) -> dict:
    """The top-level platform stamp every result JSON carries: backend,
    device kind/count and the git sha — so a number can never be
    compared against the wrong platform without the JSON itself saying
    so (``obs/perfdb.py`` partitions its trajectories on exactly this).
    An explicit ``JAX_PLATFORMS=cpu`` run is a labelled ``cpu``
    rehearsal, never a device measurement."""
    from advanced_scrapper_tpu.obs import perfdb

    devs = jax.devices()
    fp = {
        "backend": platform,
        "device_kind": getattr(devs[0], "device_kind", "unknown"),
        "device_count": len(devs),
        "git_sha": perfdb.git_sha(os.path.dirname(os.path.abspath(__file__))),
    }
    return fp


def _bench_slo_engine():
    """The bench's declared SLO set (``obs/slo.py``), evaluated over the
    live registry at regime start and end so the result JSON carries a
    machine-readable verdict, not just rates: per-stage p99 ceilings
    (generous on cpu — the ceilings are the on-chip contract chip runs
    will tighten) and the RPC error-ratio budget the fleet regime
    exercises."""
    from advanced_scrapper_tpu.obs.slo import SloEngine

    objectives = [
        {
            "name": f"stage_{s}_p99",
            "kind": "p99_latency_max",
            "metric": "astpu_stage_seconds",
            "labels": {"stage": s},
            "threshold": 1.0,  # seconds per batch, p99
        }
        for s in ("encode", "h2d", "kernel", "resolve")
    ]
    objectives.append(
        {
            "name": "rpc_error_ratio",
            "kind": "ratio_max",
            "metric": "astpu_rpc_server_errors_total",
            "denominator": "astpu_rpc_server_calls_total",
            "threshold": 0.01,
        }
    )
    objectives.append(
        {
            # the sharded plane's declared balance objective: the packed
            # mesh regime labels every put per shard, and a healthy plane
            # is EXACTLY balanced (tiles + 1 per shard) — any skew is a
            # violated SLO, not a prose claim.  The gauge only exists
            # once a sharded regime ran (record_sharded_put_skew), so
            # non-sharded runs skip it instead of vacuously passing.
            "name": "sharded_put_skew",
            "kind": "gauge_max",
            "metric": "astpu_sharded_put_skew",
            "threshold": 0.0,
        }
    )
    objectives.append(
        {
            # the precision tier's declared launch budget: a settled
            # corpus costs EXACTLY tiles + 1 puts (settle tiles + fold
            # init) and tiles + 1 dispatches (settle tiles + finalize)
            # on the "rerank" plane — any surplus launch is a violated
            # SLO.  The gauge only exists once a rerank regime ran
            # (_bench_rerank), so non-rerank runs skip it instead of
            # vacuously passing.
            "name": "rerank_launch_budget",
            "kind": "gauge_max",
            "metric": "astpu_rerank_launch_excess",
            "threshold": 0.0,
        }
    )
    objectives.append(
        {
            # the declared reject-ratio objective of the overload plane:
            # a bench run is UNLOADED relative to its own capacity, so
            # any admission activity it does produce must stay almost
            # entirely admitted — sheds belong to storms, not benches
            "name": "admission_reject_ratio",
            "kind": "ratio_max",
            "metric": "astpu_admission_rejected_total",
            "denominator": "astpu_admission_requests_total",
            "threshold": 0.05,
        }
    )
    return SloEngine(objectives)


def _admission_counters() -> dict:
    """Always-on overload-plane totals (admitted/rejected/degraded-step)
    — snapshotted per regime like the device counters, so every result
    JSON states what the admission plane did during that regime."""
    from advanced_scrapper_tpu.obs import telemetry
    from advanced_scrapper_tpu.obs.slo import SloEngine

    def total(name, **labels):
        return sum(
            m.value
            for m in telemetry.REGISTRY.find(name)
            if all(m.labels.get(k) == v for k, v in labels.items())
        )

    step = 0.0
    for name, _labels, v in SloEngine.registry_samples():
        if name == "astpu_degraded_step":
            step = max(step, v)
    return {
        "admitted": total("astpu_admission_requests_total", outcome="admitted"),
        "rejected": total("astpu_admission_requests_total", outcome="rejected"),
        "degraded_step": step,
    }


def _telemetry_ledger(slo_engine) -> dict:
    """End-of-run aggregated series for the result JSON: EVERY live
    series (always-on device counters, stage histograms with
    percentiles, event counters — plus the full registry when
    ASTPU_TELEMETRY is on) and the final SLO verdict.  BENCH_*.json
    carries a complete ledger, not just headline rates."""
    from advanced_scrapper_tpu.obs import telemetry

    verdict = slo_engine.evaluate() if slo_engine is not None else None
    series = telemetry.REGISTRY.status()["metrics"]
    return {"series": series, "slo": verdict}


REGIMES = (
    "uniform", "ragged", "stream", "sharded", "rerank", "recall", "exact",
    "matcher", "index", "fleet",
)


def _parse_args(argv=None):
    import argparse

    p = argparse.ArgumentParser(
        description="MinHash+LSH dedup throughput benchmark (one JSON line)"
    )
    p.add_argument(
        "--regime",
        default="all",
        choices=("all",) + REGIMES,
        help="run one regime instead of the full battery (the JSON line "
        "then carries only that regime's keys)",
    )
    return p.parse_args(argv)


def main(argv=None) -> None:
    args = _parse_args(argv)
    want = set(REGIMES) if args.regime == "all" else {args.regime}

    import jax

    # whatever backend JAX gives: the fingerprint labels it, and a chip
    # that does not answer is an error, not a reason to measure the CPU
    platform = jax.devices()[0].platform

    from advanced_scrapper_tpu.core.hashing import make_params
    from advanced_scrapper_tpu.core.mesh import build_mesh
    from advanced_scrapper_tpu.obs import telemetry, trace
    from advanced_scrapper_tpu.obs.profiler import xla_trace

    params = make_params()
    # scan is the measured-fastest backend on v5e (oph: sort-bound, ~16×
    # slower; pallas: relayout-bound — see ops/oph.py, ops/pallas_minhash.py)
    backend = os.environ.get("ASTPU_BENCH_BACKEND", "scan")
    quick = bool(os.environ.get("ASTPU_BENCH_QUICK"))

    # 65536: ~15% over 32768 on v5e (2026-07); ASTPU_BENCH_BATCH sweeps it
    batch = int(os.environ.get("ASTPU_BENCH_BATCH", 4096 if quick else 65536))
    block = 1024   # bytes/article (typical short news article body)

    def note(msg: str) -> None:
        # stderr breadcrumbs: if a regime dies mid-run, the driver's tail
        # names the stage instead of showing an unattributed traceback
        print(f"bench: {msg}", file=sys.stderr, flush=True)

    # persistent XLA compilation cache (JAX_COMPILATION_CACHE_DIR, else
    # the checkout's .jax_cache) — steady-state rounds stop paying
    # first-corpus recompiles across processes (the warmup-vs-steady
    # split in the JSON shows the effect)
    from advanced_scrapper_tpu.core.mesh import maybe_enable_compile_cache

    cache_dir = maybe_enable_compile_cache()
    if cache_dir:
        note(f"compile cache: {cache_dir}")

    # live observability for the run: /metrics + /status while regimes
    # execute (tools/obs_top.py points here), flight-recorder sidecar on
    # an uncaught death
    if telemetry.enabled():
        metrics_srv = telemetry.StatusServer(
            port=int(os.environ.get("ASTPU_METRICS_PORT") or 0)
        ).start()
        note(
            "telemetry: GET /metrics and /status live on "
            f"http://{metrics_srv.host}:{metrics_srv.port}"
        )
        trace.install_excepthook()

    out: dict = {
        "metric": "minhash_lsh_dedup_articles_per_sec",
        "platform": platform,
        "unit": "articles/s",
    }
    if args.regime != "all":
        out["regime"] = args.regime

    # declared SLOs: baseline evaluation here, final after the regimes —
    # rate/ratio/windowed-p99 objectives need the two points
    slo_engine = _bench_slo_engine()
    slo_engine.evaluate()

    from advanced_scrapper_tpu.obs import stages

    mesh = build_mesh(len(jax.devices()), 1)
    out["platform_fingerprint"] = _platform_fingerprint(jax, platform)
    note(f"platform={platform} devices={len(jax.devices())} batch={batch}")
    with xla_trace(os.environ.get("ASTPU_TRACE_DIR") or None):
        uniform = None
        # overload-plane ledger per regime: admitted/rejected/
        # degraded-step deltas ride every regime's result keys (the
        # declared reject-ratio SLO is evaluated in the end-of-run
        # verdict under out["telemetry"]["slo"])
        _adm_last = _admission_counters()

        def _adm_delta(prefix: str) -> dict:
            nonlocal _adm_last
            now = _admission_counters()
            out = {
                f"{prefix}_admitted": now["admitted"] - _adm_last["admitted"],
                f"{prefix}_rejected": now["rejected"] - _adm_last["rejected"],
                f"{prefix}_degraded_step": now["degraded_step"],
            }
            _adm_last = now
            return out

        # decision-provenance ledger per regime: the tier×verdict mix
        # (obs/decisions.py always-on counters) snapshotted like the
        # device counters — `<regime>_decision_mix` says WHICH tier
        # settled that regime's verdicts (a rerank regime whose mix is
        # all "band" means the precision tier never fired)
        from advanced_scrapper_tpu.obs import decisions as _decisions

        _dm_last = _decisions.decision_mix_snapshot()

        def _dm_delta(prefix: str) -> dict:
            nonlocal _dm_last
            now = _decisions.decision_mix_snapshot()
            mix = _decisions.decision_mix_delta(_dm_last, now)
            _dm_last = now
            return {f"{prefix}_decision_mix": mix} if mix else {}
        if "uniform" in want:
            uniform = _bench_uniform(jax, mesh, params, backend, batch, block)
            note(f"uniform done: {uniform:.0f}/s")
            out["value"] = round(uniform, 1)
            out["vs_baseline"] = round(uniform / 50000.0, 4)
            out.update(_adm_delta("uniform"))
            out.update(_dm_delta("uniform"))
        # stage_ms: per-stage wall attribution over the two host-path
        # regimes (ragged + stream; obs/stages.py on what the numbers
        # mean), so the next PR can see where the remaining time goes
        stages.reset()
        # windowed always-on device-traffic counters (obs/stages.py):
        # dispatch-count wins are gated numerically per regime, not
        # asserted in prose — `<regime>_device_puts/_dispatches/
        # _h2d_bytes` below are the deltas each regime produced
        def _dev_delta(before: dict, prefix: str) -> dict:
            after = stages.device_counters()
            return {
                f"{prefix}_device_puts": int(
                    after["device_puts"] - before["device_puts"]
                ),
                f"{prefix}_device_dispatches": int(
                    after["device_dispatches"]
                    - before["device_dispatches"]
                ),
                f"{prefix}_h2d_bytes": int(
                    after["h2d_bytes"] - before["h2d_bytes"]
                ),
            }


        if "ragged" in want:
            ragged_warm, ragged, ragged_dc = _bench_ragged(
                1024 if quick else 8192
            )
            note(
                f"ragged done: {ragged:.0f}/s steady "
                f"(warmup corpus {ragged_warm:.0f}/s)"
            )
            out["ragged_articles_per_sec"] = round(ragged, 1)
            out["ragged_warmup_articles_per_sec"] = round(ragged_warm, 1)
            out["ragged_vs_baseline"] = round(ragged / 50000.0, 4)
            # steady-state corpora only — the warmup corpus's traffic
            # is excluded, matching the warmup-vs-steady rate split
            out.update(
                {f"ragged_{k}": v for k, v in ragged_dc.items()}
            )
            out.update(_adm_delta("ragged"))
            out.update(_dm_delta("ragged"))
        if "stream" in want:
            dc = stages.device_counters()
            stream = _bench_stream(
                jax, mesh, params, backend, batch, block, 2 if quick else 4
            )
            warm_s = getattr(_bench_stream, "last_warmup_s", 0.0)
            note(
                f"stream done: {stream:.0f}/s steady "
                f"(warmup compile {warm_s:.2f}s)"
            )
            out["stream_articles_per_sec"] = round(stream, 1)
            out["stream_warmup_s"] = round(warm_s, 3)
            out["stream_vs_baseline"] = round(stream / 50000.0, 4)
            out.update(_dev_delta(dc, "stream"))
            out.update(_adm_delta("stream"))
            out.update(_dm_delta("stream"))
        if "sharded" in want:
            (
                sharded_warm, sharded, sharded_dc, sharded_ps,
                sharded_mesh,
            ) = _bench_sharded(jax, 1024 if quick else 8192)
            note(
                f"sharded done: {sharded:.0f}/s steady over "
                f"{sharded_mesh['shards']} shards "
                f"({sharded_mesh['data']}x{sharded_mesh['seq']} mesh; "
                f"warmup corpus {sharded_warm:.0f}/s)"
            )
            out["sharded_articles_per_sec"] = round(sharded, 1)
            out["sharded_warmup_articles_per_sec"] = round(sharded_warm, 1)
            out["sharded_vs_baseline"] = round(sharded / 50000.0, 4)
            out["sharded_mesh"] = sharded_mesh
            # steady-window totals + the per-shard ledger (the
            # 1-put/1-dispatch-per-tile-per-shard contract as data)
            out.update({f"sharded_{k}": v for k, v in sharded_dc.items()})
            out["sharded_per_shard"] = sharded_ps
            out.update(_adm_delta("sharded"))
            out.update(_dm_delta("sharded"))
        if "rerank" in want:
            rerank_warm, rerank_rate, rerank_dc = _bench_rerank(
                512 if quick else 4096
            )
            note(
                f"rerank done: {rerank_rate:.0f}/s steady "
                f"(warmup corpus {rerank_warm:.0f}/s; "
                f"{rerank_dc['tiles']} settle tiles over "
                f"{rerank_dc['pairs']} pairs, "
                f"{rerank_dc['device_puts']} puts / "
                f"{rerank_dc['device_dispatches']} dispatches steady)"
            )
            out["rerank_articles_per_sec"] = round(rerank_rate, 1)
            out["rerank_warmup_articles_per_sec"] = round(rerank_warm, 1)
            # steady window on the tier's own regime ledger; the
            # tiles+1 launch budget is gated by the declared
            # rerank_launch_budget SLO, not prose
            out.update({f"rerank_{k}": v for k, v in rerank_dc.items()})
            out.update(_adm_delta("rerank"))
            out.update(_dm_delta("rerank"))
        stage_ms = {k: 0.0 for k in ("encode", "h2d", "kernel", "resolve")}
        stage_ms.update(stages.snapshot_ms())
        if "recall" in want:
            recall, recall_pairs, precision, precision_oracle, unchained = (
                _bench_recall(64 if quick else 512)
            )
            note(
                f"recall done: {recall:.4f} over {recall_pairs} pairs "
                f"(precision {precision:.4f} vs oracle {precision_oracle:.4f}, "
                f"unchained {unchained})"
            )
            out["recall_vs_oracle"] = round(recall, 4)
            out["recall_pairs"] = recall_pairs
            out["precision_vs_oracle"] = round(precision, 4)
            out["precision_oracle"] = round(precision_oracle, 4)
            out["unchained_merges"] = unchained
            out.update(_adm_delta("recall"))
            out.update(_dm_delta("recall"))
        if "exact" in want:
            (
                exact, exact_vs_pandas, exact_ms, pandas_ms,
                exact_backend, exact_reason,
            ) = _bench_exact(16384 if quick else 262144)
            note(
                f"exact done: {exact:.0f}/s ({exact_vs_pandas:.2f}x pandas; "
                f"{exact_ms:.1f}ms vs {pandas_ms:.1f}ms; "
                f"path={exact_backend}"
                + (f", reason={exact_reason}" if exact_reason else "")
                + ")"
            )
            out["exact_urls_per_sec"] = round(exact, 1)
            out["exact_vs_pandas"] = round(exact_vs_pandas, 3)
            out["exact_ms"] = round(exact_ms, 2)
            out["pandas_ms"] = round(pandas_ms, 2)
            # which tier served (BENCH_r05's 0.22× was the grouping
            # fallback running unreported); non-empty reason = the
            # native tiers were unavailable and this says why
            out["exact_backend"] = exact_backend
            if exact_reason:
                out["exact_backend_reason"] = exact_reason
            out.update(_adm_delta("exact"))
            out.update(_dm_delta("exact"))
        if "matcher" in want:
            stages.reset()
            matcher_warm, matcher, matcher_dc = _bench_matcher(
                256 if quick else 1024
            )
            m_stage = stages.snapshot_ms()
            for k in ("matcher_build", "matcher_screen", "matcher_verify"):
                stage_ms[k] = m_stage.get(k, 0.0)
            note(
                f"matcher done: {matcher:.0f}/s steady "
                f"(warmup chunk {matcher_warm:.0f}/s; "
                f"{matcher_dc['device_puts']} puts / "
                f"{matcher_dc['device_dispatches']} dispatches steady)"
            )
            out["matcher_articles_per_sec"] = round(matcher, 1)
            out["matcher_warmup_articles_per_sec"] = round(matcher_warm, 1)
            # steady-state window only, matching the rate split
            out.update({f"matcher_{k}": v for k, v in matcher_dc.items()})
            out.update(_adm_delta("matcher"))
            out.update(_dm_delta("matcher"))
        if "index" in want:
            idx = _bench_index(8192 if quick else 65536)
            note(
                f"index done: insert {idx['index_insert_rows_per_sec']:.0f}"
                f"/s probe {idx['index_probe_rows_per_sec']:.0f}/s "
                f"reopen {idx['index_reopen_ms']:.1f}ms"
            )
            out.update(idx)
            out.update(_adm_delta("index"))
            out.update(_dm_delta("index"))
        if "fleet" in want:
            flt = _bench_fleet(8192 if quick else 32768)
            note(
                f"fleet done: insert {flt['fleet_insert_rows_per_sec']:.0f}"
                f"/s probe {flt['fleet_probe_rows_per_sec']:.0f}/s "
                f"(2 shards × 2 replicas over loopback RPC)"
            )
            out.update(flt)
            out.update(_adm_delta("fleet"))
            out.update(_dm_delta("fleet"))

    out["stage_ms"] = stage_ms
    out["telemetry"] = _telemetry_ledger(slo_engine)

    # bench-history fold (obs/perfdb.py): judge this run against the
    # checked-in rounds + the optional ledger, SAME platform only — a
    # CPU rehearsal is never held against an on-chip round.  The
    # verdict rides the SLO block as an objective-shaped entry but does
    # NOT flip the run's top-level ok: per-regime SLOs gate THIS run,
    # the history verdict is cross-run archaeology (the report tool is
    # where it escalates).  ASTPU_PERF_LEDGER=path additionally appends
    # this run as a row, so every bench run grows the trajectory.
    from advanced_scrapper_tpu.obs import perfdb

    here = os.path.dirname(os.path.abspath(__file__))
    ledger_path = os.environ.get("ASTPU_PERF_LEDGER") or None
    try:
        hist = perfdb.bench_history_verdict(
            out, repo_dir=here, ledger_path=ledger_path
        )
    except Exception as e:  # archaeology must never kill a bench record
        hist = {"error": f"{type(e).__name__}: {e}"}
    out["perf_history"] = hist
    slo_v = (out.get("telemetry") or {}).get("slo")
    if isinstance(slo_v, dict) and "regressions" in hist:
        slo_v.setdefault("objectives", []).append(
            {
                "name": "perf_history_regressions",
                "kind": "gauge_max",
                "metric": "perf_history.regressions",
                "threshold": 0,
                "value": hist["regressions"],
                "ok": hist["regressions"] == 0,
                "advisory": True,
                "platform": hist.get("platform"),
                "compared_against": hist.get("compared_against"),
            }
        )
    if ledger_path:
        try:
            perfdb.PerfLedger(ledger_path).ingest_result(
                out, source=f"bench-{time.strftime('%Y%m%d-%H%M%S')}"
            )
        except OSError as e:
            note(f"perf ledger append failed: {e}")
    if uniform is not None:
        out.update(
            _vpu_roofline(
                uniform, block, params, platform,
                out["platform_fingerprint"]["device_kind"],
            )
        )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
