"""The time-domain performance plane (ISSUE 15): dispatch latency ledger,
recompile sentinel, continuous host profiler, and the platform-aware
bench-history engine.

The acceptance shape: the recompile sentinel is always-on and asserts
ZERO steady-state compiles across the packed dedup, matcher and sharded
dispatch planes (per-kernel counters AND the global backend-compile
histogram); the stack sampler's measured overhead stays under the 1%
gate on a real ragged dedup; ``/profile`` round-trips from a live 2×2
fleet into one merged FleetCollector view; and the perf ledger's
regression verdicts only ever compare same-platform rows.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from advanced_scrapper_tpu.config import DedupConfig
from advanced_scrapper_tpu.obs import devprof, perfdb, profiler, stages, telemetry
from advanced_scrapper_tpu.obs.collector import FleetCollector
from advanced_scrapper_tpu.pipeline.dedup import NearDupEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def clean_telemetry():
    telemetry.REGISTRY.reset()
    telemetry.set_enabled(True)
    yield
    profiler.stop_global()
    telemetry.REGISTRY.reset()
    telemetry.set_enabled(None)


def _uniform_corpus(seed: int, n: int = 192, length: int = 900) -> list[bytes]:
    """Fixed-length docs → a stable tile-shape set across corpora (the
    steady-state contract under test is about SHAPES; a random ragged
    corpus can legitimately draw a width bucket its warmup didn't)."""
    r = np.random.RandomState(seed)
    return [
        r.randint(32, 127, size=length, dtype=np.uint8).tobytes()
        for _ in range(n)
    ]


def _engine(**kw) -> NearDupEngine:
    return NearDupEngine(DedupConfig(batch_size=256, **kw))


def _sentinel_delta(fn):
    """Run ``fn`` and return (per-kernel compile deltas, global backend
    compile delta)."""
    base = devprof.jit_compiles_by_kernel()
    gb = devprof.compile_seconds_count()[0]
    fn()
    after = devprof.jit_compiles_by_kernel()
    return (
        {
            k: after.get(k, 0.0) - base.get(k, 0.0)
            for k in set(base) | set(after)
        },
        devprof.compile_seconds_count()[0] - gb,
    )


# -- recompile sentinel -------------------------------------------------------


def test_recompile_sentinel_zero_steady_state_packed_dedup():
    """The headline gate: after the warmup corpus, further same-profile
    corpora through the packed single-dispatch plane compile NOTHING —
    per-kernel sentinel counters flat AND the global backend-compile
    histogram flat (which also covers the fused epilogues and any helper
    jit no seam wraps)."""
    eng = _engine()
    np.asarray(eng.dedup_reps_async(_uniform_corpus(1)))  # warmup compiles
    warm = devprof.jit_compiles_by_kernel()
    assert warm.get("dedup_fused_tile", 0) > 0, (
        "the warmup corpus must land counted compiles — an always-zero "
        "sentinel is a broken sentinel, not a healthy steady state"
    )

    def steady():
        for seed in (2, 3):
            np.asarray(eng.dedup_reps_async(_uniform_corpus(seed)))

    deltas, global_delta = _sentinel_delta(steady)
    assert all(v == 0 for v in deltas.values()), deltas
    assert global_delta == 0


def test_recompile_sentinel_zero_steady_state_matcher():
    import bench
    from advanced_scrapper_tpu.pipeline.matcher import match_chunk

    index, df = bench._matcher_workload(64)
    match_chunk(df, index)  # warmup: compiles the screen shape set
    assert devprof.jit_compiles_by_kernel().get("matcher_screen_step", 0) > 0

    deltas, global_delta = _sentinel_delta(lambda: match_chunk(df, index))
    assert all(v == 0 for v in deltas.values()), deltas
    assert global_delta == 0


def test_recompile_sentinel_zero_steady_state_sharded(devices8):
    from advanced_scrapper_tpu.core.mesh import build_mesh

    mesh = build_mesh(2, 1, devices=devices8[:2])
    eng = _engine()
    eng.dedup_reps_sharded(_uniform_corpus(1), mesh)  # warmup
    assert devprof.jit_compiles_by_kernel().get("sharded_fused_tile", 0) > 0

    deltas, global_delta = _sentinel_delta(
        lambda: eng.dedup_reps_sharded(_uniform_corpus(2), mesh)
    )
    assert all(v == 0 for v in deltas.values()), deltas
    assert global_delta == 0


def test_recompile_sentinel_counts_a_new_shape():
    """The sentinel must MOVE when a genuinely new shape arrives — an
    article-count bucket the warmup never drew recompiles the fused step,
    and that compile is a counted event (the 44-second stall that used
    to be invisible)."""
    eng = _engine()
    np.asarray(eng.dedup_reps_async(_uniform_corpus(1, n=192)))
    deltas, _g = _sentinel_delta(
        # 640 articles buckets to a different num_articles static arg
        lambda: np.asarray(eng.dedup_reps_async(_uniform_corpus(2, n=640)))
    )
    assert deltas.get("dedup_fused_tile", 0) > 0, deltas


def test_instrument_jit_passthrough_and_counting():
    import jax

    f = devprof.instrument_jit(jax.jit(lambda x: x * 2), "test_kernel")
    assert hasattr(f, "_cache_size")  # the prewarm-gate tests rely on this
    before = f._cache_size()
    f(np.ones((4,), np.float32))
    assert f._cache_size() == before + 1
    assert devprof.jit_compiles_by_kernel().get("test_kernel") == 1
    f(np.ones((4,), np.float32))  # cache hit: no count
    assert devprof.jit_compiles_by_kernel().get("test_kernel") == 1
    # non-jit callables pass through unwrapped (sentinel degrades, never errors)
    plain = lambda x: x  # noqa: E731
    assert devprof.instrument_jit(plain, "nope") is plain


# -- dispatch latency ledger --------------------------------------------------


def test_dispatch_latency_ledger_and_queue_lag():
    """Every packed tile dispatch lands one observation on the
    kernel/shape-labeled latency histogram, and every staged pop lands
    the h2d→dispatch gap on the queue-lag series."""
    eng = _engine(put_workers=2)
    np.asarray(eng.dedup_reps_async(_uniform_corpus(1)))
    lat = telemetry.REGISTRY.find(devprof.DISPATCH_HISTOGRAM)
    tile = [h for h in lat if h.labels.get("kernel") == "dedup_fused_tile"]
    assert tile, [h.labels for h in lat]
    assert sum(h.count for h in tile) > 0
    for h in tile:
        shape = h.labels["shape"]
        rows, _x, width = shape.partition("x")
        assert rows.isdigit() and width.isdigit(), shape
    lag = telemetry.REGISTRY.find(devprof.QUEUE_LAG_HISTOGRAM)
    lag = [h for h in lag if h.labels.get("graph") == "dedup.h2d"]
    assert lag and lag[0].count > 0


def test_dispatch_span_skips_failed_dispatches():
    with pytest.raises(RuntimeError):
        with devprof.dispatch_span("boom_kernel", rows=64, width=64):
            raise RuntimeError("injected")
    lat = telemetry.REGISTRY.find(devprof.DISPATCH_HISTOGRAM)
    assert not [h for h in lat if h.labels.get("kernel") == "boom_kernel"]


# -- continuous host profiler -------------------------------------------------


def _burn_marker_function(until: float) -> int:
    """A busy loop with a recognizable name for the folded stacks."""
    acc = 0
    while time.monotonic() < until:
        acc += sum(range(200))
    return acc


def test_stack_sampler_folds_named_function():
    s = profiler.StackSampler(hz=200).start()
    try:
        _burn_marker_function(time.monotonic() + 0.3)
    finally:
        s.stop()
    assert s.samples > 10
    folded = s.folded()
    assert "_burn_marker_function" in folded
    # folded lines are "stack count" with root→leaf ; separators
    top_line = folded.splitlines()[0]
    stack, _sep, count = top_line.rpartition(" ")
    assert int(count) >= 1 and ";" in stack or ":" in stack


def test_sampler_overhead_gate_on_ragged_regime():
    """The <1% promise is MEASURED: the sampler accounts its own pass
    time, and a real packed dedup under the default rate must keep the
    busy fraction under the gate."""
    s = profiler.StackSampler(hz=profiler.DEFAULT_HZ).start()
    try:
        eng = _engine()
        for seed in (1, 2):
            np.asarray(eng.dedup_reps_async(_uniform_corpus(seed)))
        time.sleep(0.2)  # a few more beats so the ratio is settled
        ratio = s.overhead_ratio()
    finally:
        s.stop()
    assert s.samples > 0
    assert ratio < 0.01, f"sampler overhead {ratio:.4%} ≥ the 1% gate"


def test_profile_endpoint_round_trip():
    profiler.ensure_global(hz=100)
    srv = telemetry.StatusServer().start()
    try:
        time.sleep(0.15)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/profile", timeout=5
        ) as r:
            text = r.read().decode()
    finally:
        srv.stop()
    assert text.startswith("# astpu-profile hz=100")
    assert "samples=" in text and "overhead=" in text


def test_profile_endpoint_disabled_is_a_comment_not_an_error(monkeypatch):
    monkeypatch.delenv("ASTPU_PROFILE", raising=False)
    profiler.stop_global()
    srv = telemetry.StatusServer().start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/profile", timeout=5
        ) as r:
            assert r.status == 200
            text = r.read().decode()
    finally:
        srv.stop()
    assert "disabled" in text and "ASTPU_PROFILE" in text


def test_profile_env_knob_resolution(monkeypatch):
    monkeypatch.delenv("ASTPU_PROFILE", raising=False)
    assert profiler.resolve_profile_hz() == 0.0
    monkeypatch.setenv("ASTPU_PROFILE", "1")
    assert profiler.resolve_profile_hz() == profiler.DEFAULT_HZ
    monkeypatch.setenv("ASTPU_PROFILE", "47.5")
    assert profiler.resolve_profile_hz() == 47.5
    monkeypatch.setenv("ASTPU_PROFILE", "nope")
    assert profiler.resolve_profile_hz() == 0.0


def test_profile_fleet_merge_2x2(tmp_path):
    """The acceptance round-trip: a live 2×2 fleet (4 real shard
    subprocesses under ASTPU_PROFILE) has every /profile harvested into
    ONE merged FleetCollector view with instance-prefixed stacks."""
    procs = []
    endpoints = []
    try:
        for s in range(2):
            for r in range(2):
                name = f"s{s}n{r}"
                mf = tmp_path / f"{name}.mport"
                p = subprocess.Popen(
                    [
                        sys.executable, "-m",
                        "advanced_scrapper_tpu.index.remote",
                        "--dir", str(tmp_path / name),
                        "--port", "0",
                        "--port-file", str(tmp_path / f"{name}.port"),
                        "--spaces", "bands",
                        "--metrics-port", "0",
                        "--metrics-port-file", str(mf),
                        "--name", name,
                    ],
                    env=dict(
                        os.environ,
                        JAX_PLATFORMS="cpu",
                        ASTPU_PROFILE="97",
                        ASTPU_TELEMETRY="1",
                    ),
                    cwd=REPO,
                    stdout=subprocess.DEVNULL,
                    stderr=subprocess.DEVNULL,
                )
                procs.append((name, p, mf))
        for name, p, mf in procs:
            deadline = time.monotonic() + 30
            while not mf.exists():
                assert p.poll() is None, f"shard {name} died at start"
                assert time.monotonic() < deadline, f"{name} port never bound"
                time.sleep(0.02)
            endpoints.append((name, f"http://127.0.0.1:{mf.read_text().strip()}"))
        time.sleep(0.3)  # a few 97 Hz beats so every shard has samples
        fc = FleetCollector(endpoints, profiles=True)
        fc.scrape_once()  # harvests profiles too (profiles=True)
        merged = fc.merged_profile()
        for name, _url in endpoints:
            assert f"# instance={name} " in merged
            assert f"\n{name};" in "\n" + merged, (
                f"no folded stacks from {name} in the merged view"
            )
        # the merged metrics side carries the sampler's own series per shard
        samples, _types = fc.merged_samples()
        prof_insts = {
            l.get("instance")
            for n, l, v in samples
            if n == "astpu_prof_samples_total" and v > 0
        }
        assert prof_insts == {name for name, _u in endpoints}
    finally:
        for _name, p, _mf in procs:
            p.terminate()
        for _name, p, _mf in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


# -- perf ledger (bench-history engine) ---------------------------------------


def _row(platform, source, order, **metrics):
    return {
        "schema": perfdb.SCHEMA,
        "kind": "bench_round",
        "source": source,
        "order": order,
        "ts": 0.0,
        "platform": platform,
        "fingerprint": None,
        "git_sha": "",
        "metrics": metrics,
    }


def test_ledger_verdicts_same_platform_direction_aware():
    rows = [
        _row("tpu", "BENCH_r01.json", 1, ragged_articles_per_sec=1000.0,
             stream_warmup_s=40.0),
        _row("tpu", "BENCH_r02.json", 2, ragged_articles_per_sec=700.0,
             stream_warmup_s=2.0),
    ]
    verdicts = {v["metric"]: v for v in perfdb.compute_verdicts(rows)}
    assert verdicts["ragged_articles_per_sec"]["verdict"] == "regression"
    assert verdicts["stream_warmup_s"]["verdict"] == "improvement"  # lower=better


def test_ledger_cross_platform_rows_never_compared():
    """A structural rule: a CPU round and an on-chip round of the same
    metric produce NO verdict."""
    rows = [
        _row("tpu", "BENCH_r01.json", 1, ragged_articles_per_sec=50000.0),
        _row("cpu", "BENCH_r02.json", 2,
             ragged_articles_per_sec=800.0),
    ]
    assert perfdb.compute_verdicts(rows) == []
    traj = perfdb.trajectories(rows)
    assert set(traj) == {"tpu", "cpu"}  # partitioned, both kept


def test_ledger_stable_band_and_unknown_direction():
    rows = [
        _row("cpu", "a_r01.json", 1, ragged_articles_per_sec=1000.0,
             mystery_metric=5.0),
        _row("cpu", "a_r02.json", 2, ragged_articles_per_sec=1050.0,
             mystery_metric=50.0),
    ]
    verdicts = perfdb.compute_verdicts(rows)
    assert [v["metric"] for v in verdicts] == ["ragged_articles_per_sec"]
    assert verdicts[0]["verdict"] == "stable"  # +5% inside the ±10% band


def test_round_records_report_acceptance(rounds_dir):
    """The report over BENCH + MULTICHIP round records is a non-empty
    platform-partitioned trajectory with at least one
    regression/improvement verdict."""
    rows = perfdb.scan_repo_artifacts(rounds_dir)
    assert len(rows) == 5
    report = perfdb.build_report(rows)
    assert len(report["platforms"]) >= 3  # tpu, cpu, multichip
    assert "cpu" in report["trajectories"]
    assert any(
        p.startswith("multichip") for p in report["trajectories"]
    ), "the MULTICHIP dryruns must partition apart from bench rounds"
    moved = [v for v in report["verdicts"] if v["verdict"] != "stable"]
    assert moved, "r02→r03 movement must produce at least one verdict"
    # every verdict's two sources live on the SAME platform partition
    for v in report["verdicts"]:
        assert v["platform"] in report["trajectories"]
    md = perfdb.report_markdown(report)
    assert "# Performance trajectory report" in md
    assert "cpu" in md


def test_ledger_canary_sli_rows_direction_aware(tmp_path):
    """Canary SLI rows join the trajectory engine with the right
    directions: a recall slide is a regression (higher-is-better family
    via the stripped prefix), a latency drop an improvement (_seconds
    suffix), and the shape counters draw no verdict."""
    led = perfdb.PerfLedger(str(tmp_path / "ledger.jsonl"))
    sli = {
        "round": 0, "recall": 0.96, "precision": 0.9,
        "latency_seconds": 2.0, "oracle_pairs": 27, "wiped": 3072,
    }
    row = led.ingest_canary_sli(sli, platform="cpu", ts=1.0)
    assert row["kind"] == "canary"
    assert row["metrics"]["recall"] == 0.96  # canary_ prefix stripped
    assert "canary_latency_seconds" in row["metrics"]
    led.ingest_canary_sli(
        {**sli, "recall": 0.80, "latency_seconds": 0.5},
        platform="cpu", ts=2.0, source="canary2",
    )
    verdicts = {v["metric"]: v for v in perfdb.compute_verdicts(led.rows())}
    assert verdicts["recall"]["verdict"] == "regression"
    assert verdicts["canary_latency_seconds"]["verdict"] == "improvement"
    assert "canary_oracle_pairs" not in verdicts  # unknown direction
    assert "canary_wiped" not in verdicts


def test_ledger_file_roundtrip_and_torn_tail(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    led = perfdb.PerfLedger(path)
    led.append(_row("cpu", "one", 1, value=1.0))
    led.ingest_result({"platform": "cpu", "value": 2.0}, source="two")
    with open(path, "a") as fh:
        fh.write('{"torn": ')  # a crash mid-append
    rows = led.rows()
    assert [r["source"] for r in rows] == ["one", "two"]
    # re-ingest dedupes by source
    n = led.ingest_artifacts([])
    assert n == 0 and led.sources() == {"one", "two"}


def test_platform_key_prefers_fingerprint():
    assert perfdb.platform_key({"platform": "cpu"}) == "cpu"
    assert perfdb.platform_key({}) == "unlabeled"
    fp = {
        "platform": "tpu",
        "platform_fingerprint": {
            "backend": "tpu", "device_kind": "TPU v5e", "device_count": 8,
        },
    }
    assert perfdb.platform_key(fp) == "tpu/TPU-v5ex8"


def test_bench_history_verdict_same_platform_only(rounds_dir):
    # a fresh platform has no comparator — no fabricated verdict
    none = perfdb.bench_history_verdict(
        {"platform": "never-seen-backend", "value": 1.0}, repo_dir=rounds_dir
    )
    assert none["compared_against"] is None and none["verdicts"] == []
    # a cpu run IS judged against the last cpu round
    hist = perfdb.bench_history_verdict(
        {"platform": "cpu", "ragged_articles_per_sec": 100.0},
        repo_dir=rounds_dir,
    )
    assert hist["compared_against"] == "BENCH_r03.json"
    regressed = {
        v["metric"] for v in hist["verdicts"] if v["verdict"] == "regression"
    }
    assert "ragged_articles_per_sec" in regressed


def test_flatten_metrics_skips_structure():
    out = perfdb.flatten_metrics(
        {
            "value": 1.0,
            "ok": True,
            "platform": "cpu",
            "stage_ms": {"encode": 5.0},
            "telemetry": {"series": [1, 2, 3]},
            "name": "x",
        }
    )
    assert out == {"value": 1.0, "stage_ms.encode": 5.0}


def test_recompile_storm_is_slo_alertable():
    """The sentinel's declared alarm shape: a ``rate_max`` objective at
    threshold 0 over ``astpu_jit_compiles_total`` — any steady-state
    compile between evaluations violates, quiet periods recover."""
    from advanced_scrapper_tpu.obs.slo import SloEngine

    eng = SloEngine(
        [
            {
                "name": "recompile_storm",
                "kind": "rate_max",
                "metric": "astpu_jit_compiles_total",
                "threshold": 0.0,
            }
        ],
        export=False,
    )
    devprof._compiles("storm_kernel")  # the series must exist to evaluate
    eng.evaluate(now=0.0)  # first sight: no rate yet
    v = eng.evaluate(now=1.0)["objectives"][0]
    assert v["ok"] is True and v["value"] == 0.0
    devprof._compiles("storm_kernel").inc(3)  # a steady-state compile burst
    v = eng.evaluate(now=2.0)["objectives"][0]
    assert v["ok"] is False and v["value"] == 3.0
    v = eng.evaluate(now=3.0)["objectives"][0]  # storm over → recovered
    assert v["ok"] is True


def test_queue_lag_excludes_put_time():
    """The staged-pop stamp is taken AFTER the put returns: a slow H2D
    with an eager consumer must read near-zero lag (stamping before the
    put would fold the whole transfer into 'lag' and invert the
    bottleneck diagnostic)."""
    from advanced_scrapper_tpu.pipeline.dispatch import PipelinedDispatcher

    def slow_put(item):
        time.sleep(0.05)
        return item

    pipe = PipelinedDispatcher(
        iter(range(4)), pack=lambda x: x, put=slow_put,
        name="lagtest.h2d",
    )
    try:
        assert list(pipe) == [0, 1, 2, 3]
    finally:
        pipe.close()
    lag = [
        h
        for h in telemetry.REGISTRY.find(devprof.QUEUE_LAG_HISTOGRAM)
        if h.labels.get("graph") == "lagtest.h2d"
    ]
    assert lag and lag[0].count == 4
    assert lag[0].sum < 0.05, (
        f"lag sum {lag[0].sum:.3f}s ≈ put time — the stamp is on the "
        "wrong side of the transfer"
    )


def test_sampler_survives_registry_reset():
    """A live global sampler re-instruments after REGISTRY.reset() — its
    series must not silently vanish from /metrics for the rest of the
    process (the orphaned-handle test-ordering trap)."""
    s = profiler.StackSampler(hz=100).start()
    try:
        s.sample_once()
        telemetry.REGISTRY.reset()  # runs the sampler's re-instrument hook
        s.sample_once()
        counters = telemetry.REGISTRY.find("astpu_prof_samples_total")
        assert counters and counters[0].value >= 1
        txt = telemetry.REGISTRY.prometheus_text()
        assert "astpu_prof_hz" in txt and "astpu_prof_overhead_ratio" in txt
    finally:
        s.stop()


def test_metric_direction_inherits_parent_unit():
    assert perfdb.metric_direction("stage_ms.encode") == -1
    assert perfdb.metric_direction("ragged_articles_per_sec") == 1
    assert perfdb.metric_direction("stream_warmup_s") == -1
    assert perfdb.metric_direction("mystery") == 0


def test_ledger_rows_are_strict_json():
    """Every row shape the ledger can hold must survive a strict JSON
    round trip — json.dumps(inf) emits the non-standard ``Infinity``
    token that breaks non-Python readers of the documented format."""
    import json as _json

    row = perfdb.row_from_result(
        {"platform": "cpu", "value": 1.0}, source="bench-20260804-1200"
    )
    line = _json.dumps(row, sort_keys=True)
    assert "Infinity" not in line
    assert _json.loads(line)["order"] is None


# -- per-platform knob-profile store (perf-ledger dispatch defaults) ---------


def _sweep_row(platform, source, rate, order=1):
    return {
        "schema": perfdb.SCHEMA,
        "kind": "sweep",
        "source": source,
        "order": order,
        "ts": 0.0,
        "platform": platform,
        "fingerprint": None,
        "git_sha": "",
        "metrics": {"ragged_articles_per_sec": rate},
    }


def test_parse_source_knobs_round_trip():
    src = "sweep/onchip:rerank:n=4096,put_workers=3,window=6,tile_rows=512"
    assert perfdb.parse_source_knobs(src) == {
        "put_workers": 3,
        "dispatch_window": 6,
        "rerank_tile_rows": 512,
    }
    # unknown keys and malformed values are skipped, never fatal
    assert perfdb.parse_source_knobs("sweep:ragged:n=8192,foo=1,window=oops") == {}
    assert perfdb.parse_source_knobs("no knobs here") == {}


def test_best_knob_profile_max_rate_same_platform_sweeps_only(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    led = perfdb.PerfLedger(path)
    led.append(_sweep_row(
        "cpu/swept-x4",
        "sweep/onchip:ragged:n=4096,put_workers=1,window=2,tile_rows=256",
        500.0,
    ))
    led.append(_sweep_row(
        "cpu/swept-x4",
        "sweep/onchip:ragged:n=4096,put_workers=3,window=6,tile_rows=512",
        900.0,
        order=2,
    ))
    # other platform partitions never leak across
    led.append(_sweep_row(
        "tpu/TPU-v5ex8",
        "sweep/onchip:ragged:n=4096,put_workers=8,window=12,tile_rows=2048",
        5000.0,
        order=3,
    ))
    # bench rounds are not sweeps: no knob tags, excluded by kind
    led.append(_row("cpu", "BENCH_r01.json", 4, ragged_articles_per_sec=9999.0))
    assert perfdb.best_knob_profile(path, "cpu") == {
        "put_workers": 3,
        "dispatch_window": 6,
        "rerank_tile_rows": 512,
    }
    assert perfdb.best_knob_profile(path, "tpu") == {
        "put_workers": 8,
        "dispatch_window": 12,
        "rerank_tile_rows": 2048,
    }
    assert perfdb.best_knob_profile(path, "gpu") == {}


def test_engine_knob_profile_resolution_order(tmp_path, monkeypatch):
    """env > caller-pinned > ledger best row > dataclass default — per
    knob, not per profile."""
    from advanced_scrapper_tpu.config import DedupConfig
    from advanced_scrapper_tpu.pipeline.dedup import _resolve_knob_profile

    path = str(tmp_path / "perf.jsonl")
    led = perfdb.PerfLedger(path)
    led.append(_sweep_row(
        "cpu/swept-x4",
        "sweep/onchip:ragged:n=4096,put_workers=3,window=6,tile_rows=512",
        900.0,
    ))
    monkeypatch.setenv("ASTPU_PERF_LEDGER", path)
    monkeypatch.delenv("ASTPU_DEDUP_PUT_WORKERS", raising=False)
    monkeypatch.delenv("ASTPU_DEDUP_DISPATCH_WINDOW", raising=False)
    monkeypatch.delenv("ASTPU_DEDUP_RERANK_TILE_ROWS", raising=False)

    # 3) the ledger's best same-platform row fills still-default knobs
    cfg = _resolve_knob_profile(DedupConfig())
    assert (cfg.put_workers, cfg.dispatch_window, cfg.rerank_tile_rows) == (
        3, 6, 512,
    )
    # 2) a caller-pinned field is an explicit choice the ledger respects
    #    — while the OTHER knobs still resolve from the row
    cfg = _resolve_knob_profile(DedupConfig(put_workers=2))
    assert cfg.put_workers == 2
    assert (cfg.dispatch_window, cfg.rerank_tile_rows) == (6, 512)
    # 1) explicit env beats both the pin and the ledger
    monkeypatch.setenv("ASTPU_DEDUP_PUT_WORKERS", "5")
    cfg = _resolve_knob_profile(DedupConfig(put_workers=2))
    assert cfg.put_workers == 5
    assert cfg.dispatch_window == 6
    monkeypatch.delenv("ASTPU_DEDUP_PUT_WORKERS")

    # 4) no ledger → untouched construction
    monkeypatch.setenv("ASTPU_PERF_LEDGER", str(tmp_path / "missing.jsonl"))
    assert _resolve_knob_profile(DedupConfig()) == DedupConfig()
    # a torn/foreign ledger must never fail engine init
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"torn": ')
    monkeypatch.setenv("ASTPU_PERF_LEDGER", str(bad))
    assert _resolve_knob_profile(DedupConfig()) == DedupConfig()
