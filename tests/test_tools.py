"""Smoke tests for the measurement tools in ``tools/`` — tiny shapes,
in-process, so the profilers can't silently rot as the paths they
decompose evolve (they reuse bench's corpus/config helpers by design)."""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (REPO, os.path.join(REPO, "tools")):
    if p not in sys.path:
        sys.path.insert(0, p)


def test_profile_host_composition_smoke(capsys):
    import profile_host_composition as t

    t.main(batch=256, block=64, n_batches=2)
    out = capsys.readouterr().out
    assert "host-only composition:" in out and "articles/s" in out


def test_profile_stream_smoke(devices8, capsys):
    import profile_stream as t

    t.main(batch=256, block=64, n_batches=2)
    out = capsys.readouterr().out
    assert "stream" in out and "dispatch=" in out and "final_sync=" in out


def test_profile_ragged_smoke(capsys):
    import profile_ragged as t

    t.main(n_articles=64)
    out = capsys.readouterr().out
    assert "ragged 64 articles" in out and "articles/s one-shot" in out


def test_sweep_onchip_snippets_and_failed_probe_abort(tmp_path, monkeypatch, capsys):
    """The on-chip sweep driver: config snippets must stay importable/
    formattable as the APIs they drive evolve, and a probe subprocess
    that fails must abort the sweep with a recorded probe row."""
    import sweep_onchip as t

    # snippets format cleanly and reference real symbols
    s = t.STREAM_SNIPPET.format(here=t.HERE, batch=64, block=64, n_batches=1, workers=1)
    r = t.RAGGED_SNIPPET.format(here=t.HERE, put_workers=1, n_articles=8)
    sh = t.SHARDED_SNIPPET.format(
        here=t.HERE, n_articles=8, dp=2, sp=1, put_workers=1
    )
    compile(s, "<stream>", "exec")
    compile(r, "<ragged>", "exec")
    compile(sh, "<sharded>", "exec")
    assert "make_sharded_dedup" in s and "dedup_reps_async" in r
    assert "dedup_reps_sharded" in sh and "prewarm_sharded" in sh

    # the local DxS parser is a grammar twin of core.mesh.parse_mesh_shape
    # (the parent process must never import jax, hence the duplicate)
    from advanced_scrapper_tpu.core.mesh import parse_mesh_shape

    for spec in ("2x4", "8X1", " 1x8 "):
        assert t.parse_mesh_shape(spec) == parse_mesh_shape(spec)
    for bad in ("axb", "8", "0x4", "2x4x1"):
        for parser in (t.parse_mesh_shape, parse_mesh_shape):
            try:
                parser(bad)
                raise AssertionError(f"{parser} accepted {bad!r}")
            except ValueError as e:
                assert "mesh shape" in str(e)
    assert t._mesh_shapes("auto", 8) == [(8, 1), (4, 2)]
    assert t._mesh_shapes("1x8,2x4,4x4", 8) == [(1, 8), (2, 4)]

    # the probe subprocess fails -> sweep aborts, row recorded
    out = tmp_path / "sweep.jsonl"
    monkeypatch.setattr(
        t, "PROBE_SNIPPET", "import sys; sys.exit(3)"
    )
    monkeypatch.setattr(sys, "argv", ["sweep_onchip.py", "--out", str(out)])
    try:
        t.main()
        raise AssertionError("expected SystemExit on a failed probe")
    except SystemExit as e:
        assert e.code == 1
    import json

    rows = [json.loads(line) for line in out.read_text().splitlines()]
    assert rows and rows[0]["config"] == "probe" and rows[0]["status"] == "error"


def test_bench_ragged_engine_honors_put_workers_knob(monkeypatch):
    """ASTPU_DEDUP_PUT_WORKERS must reach the ragged engine: bench once
    built NearDupEngine() from raw defaults, silently ignoring the knob it
    documents (and the sweep would have measured one config four times)."""
    import bench

    monkeypatch.setenv("ASTPU_DEDUP_PUT_WORKERS", "3")
    assert bench._ragged_engine().cfg.put_workers == 3


def test_transport_default_worker_resolution(monkeypatch):
    """H2D put-worker counts resolve IN THE PRODUCT LAYER (0/None = one
    put thread), so production defaults and bench defaults cannot
    diverge; env knobs and explicit values always win."""
    import bench
    from advanced_scrapper_tpu.config import DedupConfig
    from advanced_scrapper_tpu.pipeline.dedup import resolve_put_workers

    monkeypatch.delenv("ASTPU_BENCH_FEED_WORKERS", raising=False)
    monkeypatch.delenv("ASTPU_DEDUP_PUT_WORKERS", raising=False)
    assert bench._feed_workers() is None       # defer to the product layer
    cfg = bench._ragged_engine().cfg
    assert cfg.put_workers == 0 and resolve_put_workers(cfg) == 1
    assert resolve_put_workers(DedupConfig(put_workers=3)) == 3  # explicit wins

    monkeypatch.setenv("ASTPU_BENCH_FEED_WORKERS", "2")
    monkeypatch.setenv("ASTPU_DEDUP_PUT_WORKERS", "7")
    assert bench._feed_workers() == 2
    assert resolve_put_workers(bench._ragged_engine().cfg) == 7


def test_profile_hostpath_smoke(capsys):
    import profile_hostpath as t

    t.main(n_articles=64)
    out = capsys.readouterr().out
    assert "hostpath ragged 64 articles" in out
    assert "encode=" in out and "kernel=" in out and "articles/s warm" in out


def test_profile_hostpath_device_view_smoke(capsys):
    """--device renders the per-tile put/dispatch timeline plus the
    always-on device-counter deltas for the warm corpus — and the
    matcher tile plane's timeline (the other half of the launch-count
    ledger)."""
    import profile_hostpath as t

    t.main(n_articles=64, device=True)
    out = capsys.readouterr().out
    assert "device view (warm corpus):" in out
    assert "puts=" in out and "dispatches=" in out and "h2d_bytes=" in out
    # at least one per-tile timeline row with both phases attributed
    assert "put=" in out and "dispatch=" in out and "tile " in out
    # matcher section: counter deltas + its own per-tile rows
    assert "matcher device view (warm chunk):" in out
    m_tail = out.split("matcher device view")[1]
    assert "puts=" in m_tail and "tiles=" in m_tail and "tile " in m_tail


def test_obs_top_once_smoke(capsys):
    """obs_top --once against a live StatusServer: one full frame with the
    stage table, gauges and counters rendered."""
    import obs_top

    from advanced_scrapper_tpu.obs import stages, telemetry

    telemetry.REGISTRY.reset()
    stages._clear_for_tests()
    telemetry.set_enabled(True)
    srv = None
    try:
        stages.add("encode", 0.05)
        stages.add("kernel", 0.02)
        telemetry.event_counter(
            "astpu_quarantine_total", kind="csv_torn_tail"
        ).inc()
        srv = telemetry.StatusServer(port=0).start()
        rc = obs_top.main(["--url", f"http://127.0.0.1:{srv.port}", "--once"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "obs_top @" in out
        assert "encode" in out and "kernel" in out and "p95_ms" in out
        assert "astpu_quarantine_total{kind=csv_torn_tail}" in out
        assert "astpu_process_max_rss_bytes" in out
    finally:
        if srv is not None:
            srv.stop()
        telemetry.REGISTRY.reset()
        stages._clear_for_tests()
        telemetry.set_enabled(None)


def test_obs_top_graph_once_smoke(capsys):
    """obs_top --graph --once against a live StatusServer while a runtime
    stage graph holds items: the frame must show each edge's depth/
    capacity, items in/out and put/get stall times, and each stage's
    throughput — the whole-graph view of the scheduler's own gauges."""
    import threading
    import time

    import obs_top

    from advanced_scrapper_tpu.obs import telemetry
    from advanced_scrapper_tpu.runtime import DONE, StageGraph

    telemetry.REGISTRY.reset()
    telemetry.set_enabled(True)
    srv = None
    g = None
    gate = threading.Event()
    try:
        g = StageGraph("obstop")
        mid = g.edge("mid", capacity=4)
        it = iter(range(8))
        lock = threading.Lock()

        def src():
            with lock:
                return next(it, DONE)

        g.stage("gen", source=src, out_edge=mid)
        g.stage("hold", fn=lambda x: (gate.wait(10), x)[1], in_edge=mid)
        g.start()
        time.sleep(0.3)  # let the edge fill behind the held stage
        srv = telemetry.StatusServer(port=0).start()
        rc = obs_top.main(
            ["--url", f"http://127.0.0.1:{srv.port}", "--once", "--graph"]
        )
        out = capsys.readouterr().out
        assert rc == 0
        assert "obs_top --graph @" in out
        assert "graph obstop" in out
        assert "edge mid" in out and "depth" in out and "stall put" in out
        assert "stage gen" in out and "stage hold" in out and "busy" in out
    finally:
        gate.set()
        if g is not None:
            g.stop()
            g.join(timeout=10, raise_error=False)
        if srv is not None:
            srv.stop()
        telemetry.REGISTRY.reset()
        telemetry.set_enabled(None)


def test_obs_top_quality_once_smoke(capsys):
    """obs_top --quality --once against a live StatusServer: decision-mix
    table, canary SLIs and the canary SLO verdicts in one frame."""
    import obs_top

    from advanced_scrapper_tpu.obs import telemetry
    from advanced_scrapper_tpu.obs.decisions import DecisionRecorder

    telemetry.REGISTRY.reset()
    telemetry.set_enabled(True)
    srv = None
    try:
        rec = DecisionRecorder(None)
        rec.count("rerank", "dup", 5)
        rec.count("band", "unique", 20)
        telemetry.REGISTRY.gauge(
            "astpu_canary_recall", "t", always=True
        ).set(0.95)
        telemetry.REGISTRY.gauge(
            "astpu_canary_precision", "t", always=True
        ).set(0.875)
        telemetry.REGISTRY.counter(
            "astpu_canary_rounds_total", "t", always=True
        ).inc(3)
        telemetry.REGISTRY.gauge(
            "astpu_slo_compliant", "t", objective="canary_recall"
        ).set(0.0)
        srv = telemetry.StatusServer(port=0).start()
        rc = obs_top.main(
            ["--url", f"http://127.0.0.1:{srv.port}", "--once", "--quality"]
        )
        out = capsys.readouterr().out
        assert rc == 0
        assert "obs_top --quality @" in out
        assert "decision mix" in out
        assert "rerank" in out and "band" in out and "unique" in out
        assert "recall 0.950" in out and "precision 0.875" in out
        assert "rounds 3" in out
        assert "canary slo:" in out
        assert "canary_recall" in out and "VIOLATED" in out
    finally:
        if srv is not None:
            srv.stop()
        telemetry.REGISTRY.reset()
        telemetry.set_enabled(None)


def test_obs_top_once_unreachable_exits_nonzero(capsys):
    import obs_top

    rc = obs_top.main(["--url", "http://127.0.0.1:1", "--once"])
    assert rc == 1
    assert "cannot reach" in capsys.readouterr().err


def test_bench_regime_selection_args():
    """`bench.py --regime ragged` (the acceptance invocation) must parse,
    and only known regimes are accepted."""
    import bench

    assert bench._parse_args([]).regime == "all"
    assert bench._parse_args(["--regime", "ragged"]).regime == "ragged"
    assert set(bench.REGIMES) == {
        "uniform", "ragged", "stream", "sharded", "rerank", "recall",
        "exact", "matcher", "index", "fleet",
    }
    try:
        bench._parse_args(["--regime", "nope"])
        raise AssertionError("unknown regime must be rejected")
    except SystemExit:
        pass


def test_bench_index_regime_reports_throughput_and_reopen():
    """``bench.py --regime index`` acceptance: the JSON carries probe +
    insert throughput and the cold reopen latency, measured against a real
    on-disk index (segments cut, at least the resident/disk split real)."""
    import bench

    out = bench._bench_index(2048, nb=9)
    assert out["index_insert_rows_per_sec"] > 0
    assert out["index_probe_rows_per_sec"] > 0
    assert out["index_reopen_ms"] >= 0
    assert out["index_segments"] >= 1
    assert out["index_resident_bytes"] < out["index_segment_bytes"]


def test_bench_fleet_regime_reports_throughput():
    """``bench.py --regime fleet``: the same check_and_add workload as
    the index regime, through a real 2×2 loopback fleet."""
    import bench

    out = bench._bench_fleet(2048, nb=9)
    assert out["fleet_insert_rows_per_sec"] > 0
    assert out["fleet_probe_rows_per_sec"] > 0
    assert out["fleet_shards"] == 2 and out["fleet_replicas"] == 2


def test_bench_sharded_regime_reports_per_shard_ledger():
    """``bench.py --regime sharded``: the pod-shape regime must report
    mesh shape, steady throughput, and a per-shard put/dispatch ledger
    that is exactly balanced (the gauge the declared SLO gates at 0)."""
    import jax

    import bench
    from advanced_scrapper_tpu.obs import telemetry

    warm, steady, totals, per_shard, mesh_shape = bench._bench_sharded(
        jax, 192, n_corpora=1
    )
    assert warm > 0 and steady > 0
    assert mesh_shape["shards"] == len(jax.devices())
    assert len(per_shard) == mesh_shape["shards"]
    puts = {d["device_puts"] for d in per_shard.values()}
    disp = {d["device_dispatches"] for d in per_shard.values()}
    assert len(puts) == 1 and len(disp) == 1, per_shard
    assert totals["device_puts"] >= mesh_shape["shards"]
    skew = [
        g for g in telemetry.REGISTRY.find("astpu_sharded_put_skew")
    ]
    assert skew and skew[0].value == 0.0


def test_lint_imports_clean_tree():
    """Tier-1 layering gate: core/ops/utils must not import pipeline/net/
    obs, index/ must not import pipeline or net (net.rpc excepted — the
    fleet's transport), net/ must not import pipeline — over the REAL
    tree."""
    import lint_imports

    problems = lint_imports.lint()
    assert problems == [], "\n".join(problems)


def test_lint_imports_catches_violations(tmp_path):
    """The linter must see absolute imports at any depth — module level,
    from-imports, and the lazy function-local imports the hot paths use."""
    import lint_imports

    pkg = tmp_path / "advanced_scrapper_tpu"
    (pkg / "core").mkdir(parents=True)
    (pkg / "index").mkdir()
    (pkg / "core" / "bad.py").write_text(
        "from advanced_scrapper_tpu.obs import telemetry\n"
        "def f():\n"
        "    import advanced_scrapper_tpu.pipeline.dedup\n"
    )
    # the pack op / fused tile step are pure kernels: the scheduler may
    # never leak below the pipeline layer (ops ↛ runtime)
    (pkg / "ops").mkdir()
    (pkg / "ops" / "bad.py").write_text(
        "def f():\n"
        "    from advanced_scrapper_tpu.runtime import StageGraph\n"
    )
    # the matcher-side shape of the same inversion: the fused screen step
    # (ops/match.py) must never reach for the executor it rides — the
    # pipeline layer drives ops, never the reverse
    (pkg / "ops" / "bad_match.py").write_text(
        "def screen():\n"
        "    from advanced_scrapper_tpu.pipeline.dispatch import (\n"
        "        PipelinedDispatcher,\n"
        "    )\n"
        "    import advanced_scrapper_tpu.runtime.graph\n"
    )
    (pkg / "index" / "bad.py").write_text(
        "def g():\n"
        "    from advanced_scrapper_tpu.pipeline.scraper import run_scraper\n"
    )
    (pkg / "index" / "ok.py").write_text(
        "from advanced_scrapper_tpu.obs import telemetry\n"  # allowed here
        # the ONE transport exemption: the fleet may ride net/rpc...
        "import advanced_scrapper_tpu.net.rpc as rpc\n"
    )
    (pkg / "index" / "bad_net.py").write_text(
        # ...but no other net/ module (protocol, not transport)
        "from advanced_scrapper_tpu.net.lease import LeaseServer\n"
    )
    (pkg / "net").mkdir()
    (pkg / "net" / "bad.py").write_text(
        "def h():\n"
        "    from advanced_scrapper_tpu.pipeline.scraper import SUCCESS_FIELDS\n"
    )
    # the mesh planes are device math: the sharded packed step must never
    # reach for the executor (pipeline) or scheduler (runtime) that drive
    # it — pipeline→parallel is strictly one-way
    (pkg / "parallel").mkdir()
    (pkg / "parallel" / "bad.py").write_text(
        "def f():\n"
        "    from advanced_scrapper_tpu.pipeline.dispatch import (\n"
        "        PipelinedDispatcher,\n"
        "    )\n"
        "    import advanced_scrapper_tpu.runtime.graph\n"
        "    from advanced_scrapper_tpu.index.fleet import ShardedIndexClient\n"
    )
    (pkg / "parallel" / "ok.py").write_text(
        "from advanced_scrapper_tpu.core.mesh import build_mesh\n"
        "from advanced_scrapper_tpu.ops.pack import unpack_tile\n"
    )
    # the runtime is workload-blind: no pipeline/extractors/net/index —
    # but obs (telemetry taps, the flight recorder) is its one dependency
    (pkg / "runtime").mkdir()
    (pkg / "runtime" / "bad.py").write_text(
        "from advanced_scrapper_tpu.pipeline.feed import DeviceFeed\n"
        "def f():\n"
        "    from advanced_scrapper_tpu.extractors.tpu_batch import (\n"
        "        TpuBatchBackend,\n"
        "    )\n"
        "    import advanced_scrapper_tpu.net.rpc\n"
        "    import advanced_scrapper_tpu.index.store\n"
    )
    (pkg / "runtime" / "ok.py").write_text(
        "from advanced_scrapper_tpu.obs import telemetry, trace\n"
    )
    # per-module rules: the cutover plan/ledger loses even the net.rpc
    # exemption the rest of index/ rides, and the autoscaler (a pure
    # policy head) may not reach for durable state
    (pkg / "index" / "reshard.py").write_text(
        "import advanced_scrapper_tpu.net.rpc as rpc\n"
    )
    (pkg / "runtime" / "autoscaler.py").write_text(
        "def f():\n"
        "    from advanced_scrapper_tpu.storage.fsio import atomic_replace\n"
    )
    # ...and the rerank settle math may not reach for the durable index
    # its re-probe consults (the handle is injected by pipeline/rerank.py)
    (pkg / "ops" / "rerank.py").write_text(
        "def reprobe():\n"
        "    from advanced_scrapper_tpu.index.store import PersistentIndex\n"
    )
    # the decision/canary plane observes from OUTSIDE: hook-injected, no
    # pipeline/index reach-in (the obs LAYER itself carries no ban — the
    # collector legitimately reads siblings — so ok.py stays clean)
    (pkg / "obs").mkdir()
    (pkg / "obs" / "decisions.py").write_text(
        "def emit():\n"
        "    from advanced_scrapper_tpu.pipeline.dedup import DedupEngine\n"
        "    import advanced_scrapper_tpu.index.store\n"
    )
    (pkg / "obs" / "canary.py").write_text(
        "from advanced_scrapper_tpu.index.fleet import ShardedIndexClient\n"
    )
    (pkg / "obs" / "ok.py").write_text(
        "import advanced_scrapper_tpu.index.store\n"  # layer-wide: allowed
    )
    # the front door routes and meters — it may ride net/index/runtime/
    # obs but never the dedup math itself; and its tenancy module is pure
    # declarations (no transport even though the layer allows it)
    (pkg / "service").mkdir()
    (pkg / "service" / "bad.py").write_text(
        "def serve():\n"
        "    from advanced_scrapper_tpu.pipeline.dedup import DedupEngine\n"
    )
    (pkg / "service" / "tenancy.py").write_text(
        "import advanced_scrapper_tpu.net.rpc as rpc\n"
    )
    (pkg / "service" / "ok.py").write_text(
        "from advanced_scrapper_tpu.index.fleet import ShardedIndexClient\n"
        "import advanced_scrapper_tpu.net.rpc\n"
        "from advanced_scrapper_tpu.obs import telemetry\n"
    )
    problems = lint_imports.lint(str(tmp_path))
    assert len(problems) == 23, problems
    assert any("parallel/ must not import pipeline/" in p for p in problems)
    assert any("parallel/ must not import runtime/" in p for p in problems)
    assert any("parallel/ must not import index/" in p for p in problems)
    assert any("core/ must not import obs/" in p for p in problems)
    assert any("core/ must not import pipeline/" in p for p in problems)
    assert any("ops/ must not import runtime/" in p for p in problems)
    assert any(
        "bad_match.py" in p and "ops/ must not import pipeline/" in p
        for p in problems
    )
    assert any(
        "bad_match.py" in p and "ops/ must not import runtime/" in p
        for p in problems
    )
    assert any("index/ must not import pipeline/" in p for p in problems)
    assert any("index/ must not import net/" in p for p in problems)
    assert any("net/ must not import pipeline/" in p for p in problems)
    assert any("runtime/ must not import pipeline/" in p for p in problems)
    assert any("runtime/ must not import extractors/" in p for p in problems)
    assert any("runtime/ must not import net/" in p for p in problems)
    assert any("runtime/ must not import index/" in p for p in problems)
    assert any(
        "reshard.py" in p and "must not import net/" in p for p in problems
    ), "module rule: reshard.py loses the net.rpc exemption"
    assert any(
        "autoscaler.py" in p and "must not import storage/" in p
        for p in problems
    ), "module rule: the autoscaler may not reach for durable state"
    assert any(
        "rerank.py" in p and "must not import index/" in p
        for p in problems
    ), "module rule: the rerank settle math may not import the index"
    assert any(
        "decisions.py" in p and "must not import pipeline/" in p
        for p in problems
    ), "module rule: the decision plane may not reach into pipeline/"
    assert any(
        "decisions.py" in p and "must not import index/" in p
        for p in problems
    ), "module rule: the decision plane may not reach into index/"
    assert any(
        os.path.join("obs", "canary.py") in p and "must not import index/" in p
        for p in problems
    ), "module rule: the canary prober's index hooks are injected"
    assert any(
        "service/ must not import pipeline/" in p for p in problems
    ), "layer rule: the front door never holds the dedup math"
    assert any(
        "tenancy.py" in p and "must not import net/" in p for p in problems
    ), "module rule: tenant declarations stay transport-free"
    assert not any("ok.py" in p for p in problems), (
        "net.rpc is exempt for index/, runtime/ may use obs/, the obs "
        "layer itself carries no layer-wide ban, and service/ may ride "
        "net/index/obs"
    )


def test_lint_metrics_clean_tree():
    """Tier-1 series-naming gate over the REAL tree: astpu_ prefix, unit
    suffixes (_total for counters, _seconds/_bytes for histograms), one
    registering module per series outside the shared event families."""
    import lint_metrics

    problems = lint_metrics.lint()
    assert problems == [], "\n".join(problems)


def test_lint_metrics_catches_violations(tmp_path):
    """Prefix, suffix, duplicate-owner and kind-conflict findings — at
    any nesting depth, through telemetry.* and REGISTRY.* spellings."""
    import lint_metrics

    pkg = tmp_path / "advanced_scrapper_tpu"
    (pkg / "alpha").mkdir(parents=True)
    (pkg / "beta").mkdir()
    (pkg / "alpha" / "bad.py").write_text(
        "from advanced_scrapper_tpu.obs import telemetry\n"
        "def f():\n"
        "    telemetry.counter('my_counter', 'no prefix')\n"
        "    telemetry.counter('astpu_alpha_things', 'counter sans _total')\n"
        "    telemetry.histogram('astpu_alpha_latency', 'no unit suffix')\n"
        "    telemetry.gauge('astpu_alpha_done_total', 'gauge w/ _total')\n"
        "    telemetry.gauge_fn('astpu_alpha_heap_bytes_used', lambda: 0)\n"
        "    telemetry.REGISTRY.counter('astpu_shared_ops_total', 'ok')\n"
    )
    (pkg / "beta" / "bad.py").write_text(
        "from advanced_scrapper_tpu.obs import telemetry\n"
        "def g():\n"
        "    telemetry.counter('astpu_shared_ops_total', 'dup owner')\n"
        "    telemetry.gauge('astpu_alpha_things', 'kind conflict')\n"
    )
    problems = lint_metrics.lint(str(tmp_path))
    assert any("'my_counter'" in p and "astpu_" in p for p in problems)
    assert any(
        "'astpu_alpha_things'" in p and "_total" in p for p in problems
    )
    assert any(
        "'astpu_alpha_latency'" in p and "_seconds" in p for p in problems
    )
    assert any(
        "'astpu_alpha_done_total'" in p and "not monotone" in p
        for p in problems
    )
    assert any(
        "'astpu_alpha_heap_bytes_used'" in p and "_bytes" in p
        for p in problems
    )
    assert any(
        "'astpu_shared_ops_total'" in p and "2 modules" in p for p in problems
    )
    assert any(
        "'astpu_alpha_things'" in p and "conflicting kinds" in p
        for p in problems
    )


def test_obs_fleet_once_smoke(capsys):
    """obs_fleet --once against two live exporters: endpoint table, merged
    series count, and an SLO verdict when --slo is given."""
    import json as _json

    import obs_fleet

    from advanced_scrapper_tpu.obs import telemetry

    telemetry.REGISTRY.reset()
    telemetry.set_enabled(True)
    s1 = s2 = None
    try:
        telemetry.REGISTRY.counter("astpu_obsft_tool_total", "t").inc(4)
        s1 = telemetry.StatusServer(name="a").start()
        s2 = telemetry.StatusServer(name="b").start()
        import tempfile

        with tempfile.NamedTemporaryFile(
            "w", suffix=".json", delete=False
        ) as fh:
            _json.dump(
                [
                    {
                        "name": "endpoints_up", "kind": "gauge_min",
                        "metric": "astpu_collector_endpoint_up",
                        "threshold": 2, "agg": "sum",
                    }
                ],
                fh,
            )
            slo_path = fh.name
        rc = obs_fleet.main(
            [
                "--endpoints",
                f"a=http://127.0.0.1:{s1.port},b=http://127.0.0.1:{s2.port}",
                "--slo", slo_path, "--once",
            ]
        )
        out = capsys.readouterr().out
        assert rc == 0
        assert "obs_fleet @" in out
        assert "up" in out and "merged series:" in out
        assert "slo ok=True" in out
        assert "endpoints_up" in out
        os.unlink(slo_path)
    finally:
        for s in (s1, s2):
            if s is not None:
                s.stop()
        telemetry.REGISTRY.reset()
        telemetry.set_enabled(None)


def test_obs_top_fleet_once_smoke(capsys):
    """obs_top --fleet --once against a serving collector: per-endpoint
    health lines and the SLO block from the merged view."""
    import obs_top

    from advanced_scrapper_tpu.obs import telemetry
    from advanced_scrapper_tpu.obs.collector import FleetCollector
    from advanced_scrapper_tpu.obs.slo import SloEngine

    telemetry.REGISTRY.reset()
    telemetry.set_enabled(True)
    srv = fc = None
    try:
        telemetry.REGISTRY.counter(
            "astpu_rpc_server_calls_total", "t", server="s"
        ).inc(7)
        srv = telemetry.StatusServer(name="node0").start()
        eng = SloEngine(
            [
                {
                    "name": "calls_floor", "kind": "gauge_min",
                    "metric": "astpu_rpc_server_calls_total", "threshold": 1,
                }
            ]
        )
        eng.evaluate()
        fc = FleetCollector([("node0", f"http://127.0.0.1:{srv.port}")])
        fc.serve(interval=0.2)
        rc = obs_top.main(
            ["--url", f"http://{fc.host}:{fc.port}", "--fleet", "--once"]
        )
        out = capsys.readouterr().out
        assert rc == 0
        assert "obs_top --fleet @" in out
        assert "node0" in out and "up" in out
        assert "slo:" in out and "calls_floor" in out and "OK" in out
    finally:
        if fc is not None:
            fc.stop()
        if srv is not None:
            srv.stop()
        telemetry.REGISTRY.reset()
        telemetry.set_enabled(None)


def test_loadgen_smoke_storm_verdict():
    """The self-contained 10× storm: zero transport failures, counted
    rejects, retry-after honored, declared SLO green — the machine
    verdict the crashsweep overload workload builds on."""
    import loadgen

    report = loadgen.run_smoke(rate_multiple=10.0, duration=0.8, workers=4)
    assert report["ok_verdict"], report["problems"]
    assert report["ok"] > 0
    assert report["admission"]["rejected"] > 0
    assert report["transport_failures"] == 0
    assert report["retry_after_honored_s"] > 0
    assert report["slo"]["ok"]


def test_loadgen_cli_smoke(tmp_path, capsys):
    import loadgen

    out = tmp_path / "storm.json"
    rc = loadgen.main(
        ["--smoke", "--duration", "0.5", "--workers", "3", "--out", str(out)]
    )
    assert rc == 0
    import json

    report = json.loads(out.read_text())
    assert report["ok_verdict"] and "admission" in report


def test_lint_metrics_covers_admission_series():
    """The naming linter actually sees the new overload-plane series
    (registration sites in runtime/admission.py, net/rpc.py) and they
    conform — one owner each, suffix rules green."""
    import lint_metrics

    seen: dict[str, set] = {}
    pkg = os.path.join(REPO, "advanced_scrapper_tpu")
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in files:
            if fn.endswith(".py"):
                _problems, regs = lint_metrics.check_file(
                    os.path.join(dirpath, fn)
                )
                for name, _kind, _ln in regs:
                    seen.setdefault(name, set()).add(fn)
    for name, owner in (
        ("astpu_admission_requests_total", "admission.py"),
        ("astpu_admission_rejected_total", "admission.py"),
        ("astpu_admission_retry_after_seconds", "admission.py"),
        ("astpu_degraded_step", "admission.py"),
        ("astpu_degraded_transitions_total", "admission.py"),
        ("astpu_degraded_effects_total", "admission.py"),
        ("astpu_rpc_overload_rejects_total", "rpc.py"),
        ("astpu_rpc_overload_backoff_seconds_total", "rpc.py"),
        ("astpu_fleet_overload_backoff_total", "fleet.py"),
        ("astpu_lease_shed_grants_total", "lease.py"),
    ):
        assert name in seen, f"{name} never registered"
        assert seen[name] == {owner}, (name, seen[name])
    assert not lint_metrics.lint(), "naming lint must stay clean"


def test_crashsweep_overload_workload_registered():
    """The overload storm is a first-class crashsweep workload: child +
    verifier registered, and the default battery actually schedules it
    (grep the orchestrator for the sweep call — the battery is code,
    not config)."""
    import crashsweep

    assert "overload" in crashsweep.CHILDREN
    assert "overload" in crashsweep.VERIFIERS
    import inspect

    battery = inspect.getsource(crashsweep.main)
    assert "sweep_overload(" in battery


def test_fsck_index_clean_then_corrupt(tmp_path, capsys):
    """The offline verifier: a healthy index directory reports clean
    (exit 0); one silently flipped bit anywhere turns into a nonzero
    exit with a per-file problem naming the segment."""
    import numpy as np

    import fsck_index
    from advanced_scrapper_tpu.index import PersistentIndex

    d = str(tmp_path / "ix")
    idx = PersistentIndex(d, cut_postings=24, compact_segments=0)
    for i in range(3):
        idx.insert_batch(
            np.arange(i * 40, i * 40 + 16, dtype=np.uint64),
            np.full(16, i, np.uint64),
        )
    idx.close()

    assert fsck_index.main([d]) == 0
    out = capsys.readouterr().out
    assert "clean" in out

    # rot one bit of one segment; fsck must name the file, exit nonzero
    report = fsck_index.fsck([d])
    assert report["ok"]
    seg = next(
        n for n in sorted(os.listdir(d)) if n.endswith(".seg")
    )
    path = os.path.join(d, seg)
    with open(path, "r+b") as fh:
        fh.seek(os.path.getsize(path) // 2)
        b = fh.read(1)[0]
        fh.seek(os.path.getsize(path) // 2)
        fh.write(bytes([b ^ 0x04]))
    assert fsck_index.main([d]) == 1
    out = capsys.readouterr().out
    assert "CORRUPT" in out and seg in out
    report = fsck_index.fsck([d])
    assert not report["ok"]
    assert any(seg in p for p in report["problems"])
    # read-only by construction: fsck never quarantined or repaired
    assert os.path.exists(path) and not os.path.exists(path + ".quarantine")


def test_fsck_index_walks_ancestors_and_notes_torn_wal(tmp_path, capsys):
    """A DIR argument may be an ancestor: every manifest.json below is
    checked; a torn WAL tail is a NOTE (normal crash artifact), never a
    problem."""
    import numpy as np

    import fsck_index
    from advanced_scrapper_tpu.index import PersistentIndex

    for sub in ("a", "b"):
        idx = PersistentIndex(str(tmp_path / "fleet" / sub), cut_postings=8)
        idx.insert_batch(
            np.arange(8, dtype=np.uint64), np.zeros(8, np.uint64)
        )
        idx.insert_batch(
            np.arange(20, 24, dtype=np.uint64), np.ones(4, np.uint64)
        )
        idx.close()
    # tear the live WAL tail of one index (crash artifact)
    wal = next(
        n for n in os.listdir(tmp_path / "fleet" / "a")
        if n.startswith("wal-")
    )
    with open(tmp_path / "fleet" / "a" / wal, "ab") as fh:
        fh.write(b"torn-garbage")
    report = fsck_index.fsck([str(tmp_path / "fleet")])
    assert report["ok"], report["problems"]
    assert len(report["dirs"]) == 2
    notes = [n for r in report["dirs"] for n in r["notes"]]
    assert any("torn tail" in n for n in notes)


def test_fleet_snapshot_verify_cli_refuses_uncommitted(tmp_path, capsys):
    """A snapshot directory without its MANIFEST.json commit mark is
    garbage by definition — verify must say so, nonzero."""
    import fleet_snapshot

    snap = tmp_path / "snap"
    snap.mkdir()
    assert fleet_snapshot.main(["verify", "--snapshot", str(snap)]) == 1
    err = capsys.readouterr().err
    assert "never committed" in err


def test_lint_metrics_covers_selfhealing_series():
    """The naming linter sees every new scrub/repair/resync series and
    they conform — one owner each, suffix rules green."""
    import lint_metrics

    seen: dict[str, set] = {}
    pkg = os.path.join(REPO, "advanced_scrapper_tpu")
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in files:
            if fn.endswith(".py"):
                _problems, regs = lint_metrics.check_file(
                    os.path.join(dirpath, fn)
                )
                for name, _kind, _ln in regs:
                    seen.setdefault(name, set()).add(fn)
    for name, owner in (
        ("astpu_scrub_runs_total", "store.py"),
        ("astpu_scrub_seconds", "store.py"),
        ("astpu_scrub_corrupt_segments_total", "store.py"),
        ("astpu_fleet_resync_total", "fleet.py"),
        ("astpu_fleet_resync_postings_total", "fleet.py"),
        ("astpu_repair_rounds_total", "fleet.py"),
        ("astpu_repair_ranges_total", "fleet.py"),
        ("astpu_repair_postings_total", "fleet.py"),
    ):
        assert name in seen, f"{name} never registered"
        assert seen[name] == {owner}, (name, seen[name])
    assert not lint_metrics.lint(), "naming lint must stay clean"


def test_crashsweep_bitrot_workload_registered():
    """Bitrot is a first-class crashsweep workload: child + verifier
    registered, and the default battery actually schedules it."""
    import inspect

    import crashsweep

    assert "bitrot" in crashsweep.CHILDREN
    assert "bitrot" in crashsweep.VERIFIERS
    battery = inspect.getsource(crashsweep.main)
    assert "sweep_bitrot(" in battery


def test_lint_metrics_covers_perf_obs_series():
    """ISSUE 15's time-domain series: the naming linter sees each one,
    each has exactly ONE owning module, and the tree stays clean."""
    import lint_metrics

    seen: dict[str, set] = {}
    pkg = os.path.join(REPO, "advanced_scrapper_tpu")
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in files:
            if fn.endswith(".py"):
                _problems, regs = lint_metrics.check_file(
                    os.path.join(dirpath, fn)
                )
                for name, _kind, _ln in regs:
                    seen.setdefault(name, set()).add(fn)
    for name, owner in (
        ("astpu_dispatch_latency_seconds", "devprof.py"),
        ("astpu_dispatch_queue_lag_seconds", "devprof.py"),
        ("astpu_jit_compiles_total", "devprof.py"),
        ("astpu_jit_compile_seconds", "devprof.py"),
        ("astpu_prof_samples_total", "profiler.py"),
        ("astpu_prof_sample_seconds", "profiler.py"),
        ("astpu_prof_stacks", "profiler.py"),
        ("astpu_prof_overhead_ratio", "profiler.py"),
        ("astpu_prof_hz", "profiler.py"),
    ):
        assert name in seen, f"{name} never registered"
        assert seen[name] == {owner}, (name, seen[name])
    assert not lint_metrics.lint(), "naming lint must stay clean"


def test_perf_ledger_report_smoke(rounds_dir, capsys):
    """``perf_ledger.py report`` over a directory of round records:
    non-empty platform-partitioned trajectory with at least one moved
    verdict (rc 2 = regressions present)."""
    import perf_ledger

    rc = perf_ledger.main(["--repo", rounds_dir, "report"])
    out = capsys.readouterr().out
    assert rc == 2
    assert "# Performance trajectory report" in out
    assert "cpu" in out and "multichip-8dev" in out
    assert "**regression**" in out or "**improvement**" in out


def test_perf_ledger_ingest_then_json_report(rounds_dir, tmp_path, capsys):
    import json as _json

    import perf_ledger

    ledger = str(tmp_path / "led.jsonl")
    rc = perf_ledger.main(["--repo", rounds_dir, "--ledger", ledger, "ingest", "--scan"])
    assert rc == 0
    assert os.path.exists(ledger)
    capsys.readouterr()
    rc = perf_ledger.main(
        ["--repo", rounds_dir, "--ledger", ledger, "report", "--format", "json",
         "--quiet-regressions"]
    )
    assert rc == 0
    report = _json.loads(capsys.readouterr().out)
    assert report["platforms"] and report["verdicts"]
    # ingesting again is a no-op (deduped by source)
    rc = perf_ledger.main(["--repo", rounds_dir, "--ledger", ledger, "ingest", "--scan"])
    assert rc == 0
    assert "0 new row(s)" in capsys.readouterr().out


def test_obs_top_prof_once_smoke(capsys):
    """obs_top --prof --once against a live StatusServer with the global
    sampler running: hottest-stack frame with shares."""
    import time as _time

    import obs_top

    from advanced_scrapper_tpu.obs import profiler, telemetry

    telemetry.REGISTRY.reset()
    telemetry.set_enabled(True)
    srv = None
    try:
        profiler.ensure_global(hz=150)
        srv = telemetry.StatusServer(port=0).start()
        _time.sleep(0.2)
        rc = obs_top.main(
            ["--url", f"http://127.0.0.1:{srv.port}", "--prof", "--once"]
        )
        out = capsys.readouterr().out
        assert rc == 0
        assert "obs_top --prof @" in out
        assert "# astpu-profile hz=150" in out
        assert "hottest stacks" in out
    finally:
        profiler.stop_global()
        if srv is not None:
            srv.stop()
        telemetry.REGISTRY.reset()
        telemetry.set_enabled(None)


def test_sweep_onchip_ledger_and_trace_plumb():
    """The sweep's satellite contract, asserted structurally (a full
    sweep is an on-chip tool): every measurement snippet honors
    ASTPU_TRACE_DIR through xla_trace, and main appends sweep points to
    the perf ledger + re-runs each regime's best point under a trace."""
    import inspect

    import sweep_onchip

    for snip in (
        sweep_onchip.STREAM_SNIPPET,
        sweep_onchip.RAGGED_SNIPPET,
        sweep_onchip.SHARDED_SNIPPET,
    ):
        assert "xla_trace" in snip and "ASTPU_TRACE_DIR" in snip
    src = inspect.getsource(sweep_onchip.main)
    assert "PerfLedger" in src
    assert "ASTPU_TRACE_DIR=" in src or "ASTPU_TRACE_DIR" in src
    assert "traced_best_of" in src
    # the traced re-run pays profiler overhead and must NOT land in the
    # ledger as the newest same-platform row (a spurious regression)
    assert 'endswith(":trace")' in src


def test_lint_metrics_covers_elastic_reshard_series():
    """The naming linter sees every elastic-fleet series — migration
    counters/histograms owned by reshard.py, autoscaler decisions by
    autoscaler.py — and the tree stays clean."""
    import lint_metrics

    seen: dict[str, set] = {}
    pkg = os.path.join(REPO, "advanced_scrapper_tpu")
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in files:
            if fn.endswith(".py"):
                _problems, regs = lint_metrics.check_file(
                    os.path.join(dirpath, fn)
                )
                for name, _kind, _ln in regs:
                    seen.setdefault(name, set()).add(fn)
    for name, owner in (
        ("astpu_reshard_pages_total", "reshard.py"),
        ("astpu_reshard_postings_moved_total", "reshard.py"),
        ("astpu_reshard_flips_total", "reshard.py"),
        ("astpu_reshard_voids_total", "reshard.py"),
        ("astpu_reshard_dual_writes_total", "reshard.py"),
        ("astpu_reshard_digest_retries_total", "reshard.py"),
        ("astpu_reshard_page_seconds", "reshard.py"),
        ("astpu_reshard_page_bytes", "reshard.py"),
        ("astpu_reshard_range_state", "reshard.py"),
        ("astpu_reshard_ranges_pending", "reshard.py"),
        ("astpu_autoscale_transitions_total", "autoscaler.py"),
        ("astpu_autoscale_blocked_total", "autoscaler.py"),
        ("astpu_autoscale_pressure", "autoscaler.py"),
        ("astpu_autoscale_target_shards", "autoscaler.py"),
    ):
        assert name in seen, f"{name} never registered"
        assert seen[name] == {owner}, (name, seen[name])
    assert not lint_metrics.lint(), "naming lint must stay clean"


def test_crashsweep_reshard_workload_registered():
    """The live-cutover crash storm is a first-class crashsweep workload:
    child + safety check + verifier registered, and the default battery
    actually schedules it with the migration WAL as a chaos target."""
    import inspect

    import crashsweep

    assert "reshard" in crashsweep.CHILDREN
    assert "reshard" in crashsweep.SAFETY_CHECKS
    assert "reshard" in crashsweep.VERIFIERS
    battery = inspect.getsource(crashsweep.main)
    assert '"reshard"' in battery
    assert "reshard-wal" in battery


def test_fsck_index_notes_handed_off_and_reshard_mark(tmp_path):
    """Migrated-away state is a handoff, not a loss: a manifest carrying
    ``handed_off`` arcs and a live reshard fence mark fscks CLEAN, with
    both surfaced as notes an operator can read."""
    import numpy as np

    import fsck_index
    from advanced_scrapper_tpu.index import PersistentIndex

    d = str(tmp_path / "ix")
    idx = PersistentIndex(d, cut_postings=8)
    idx.insert_batch(np.arange(16, dtype=np.uint64), np.zeros(16, np.uint64))
    idx.set_reshard_mark("tok-123")
    idx.retire_range(0, 1 << 32)
    idx.checkpoint()
    idx.close()

    report = fsck_index.fsck_dir(d)
    assert report["ok"], report["problems"]
    notes = "\n".join(report["notes"])
    assert "handed off" in notes and "not a loss" in notes
    assert "reshard fence mark" in notes and "tok-123" in notes


def test_fleet_snapshot_refuses_mid_reshard(tmp_path):
    """A shard fenced by a live reshard mark must refuse the snapshot —
    freezing half-migrated ownership is operator error — unless the
    override is explicit."""
    import numpy as np
    import pytest

    import fleet_snapshot
    from advanced_scrapper_tpu.index.remote import IndexShardServer, RemoteIndex

    srv = IndexShardServer(
        str(tmp_path / "node"), spaces=("bands",), cut_postings=24,
        name="snapref",
    ).start()
    try:
        remote = RemoteIndex(("127.0.0.1", srv.port), space="bands")
        try:
            remote.insert_batch(
                np.arange(8, dtype=np.uint64), np.zeros(8, np.uint64)
            )
            remote.checkpoint()
            remote.set_reshard_mark("tok-live")
        finally:
            remote.close()
        fleet = f"127.0.0.1:{srv.port}"
        with pytest.raises(RuntimeError, match="live.*reshard|reshard.*live"):
            fleet_snapshot.snapshot_fleet(
                fleet, str(tmp_path / "snap1"), spaces=("bands",)
            )
        assert not os.path.exists(
            os.path.join(str(tmp_path / "snap1"), fleet_snapshot.SNAP_MANIFEST)
        ), "a refused snapshot must never commit"
        # the explicit override still works (DR under a wedged cutover)
        fleet_snapshot.snapshot_fleet(
            fleet, str(tmp_path / "snap2"), spaces=("bands",),
            allow_reshard=True,
        )
        assert fleet_snapshot.verify_snapshot(str(tmp_path / "snap2")) == []
    finally:
        srv.stop()


def test_loadgen_tenant_smoke_verdict():
    """The self-contained mixed-tenant storm: skewed per-tenant offered
    rates through one gateway over a live loopback fleet — zero transport
    failures, zero wrong answers, zero cross-tenant hits, the noisy
    tenant throttled (quiet tenants never), retry-after honored, and the
    per-tenant SLO verdict green."""
    import loadgen

    report = loadgen.run_tenant_smoke(tenants=3, duration=1.0, base_rate=50.0)
    assert report["ok_verdict"], report["problems"]
    assert report["isolation_violations"] == 0
    for tid, ledger in report["tenants"].items():
        assert ledger["transport_failures"] == 0, tid
        assert ledger["wrong_answers"] == 0, tid
        assert ledger["ok"] > 0, tid
        assert "p50" in ledger["latency_ms"] and "p99" in ledger["latency_ms"]
    noisy = max(report["tenants"])  # last tenant id sorts last (t0, t1, …)
    assert report["quota_rejects"][noisy] > 0, (
        "the noisy tenant must have overrun its bucket"
    )
    assert report["slo"]["ok"]


def test_loadgen_cli_tenant_smoke(tmp_path, capsys):
    import json

    import loadgen

    out = tmp_path / "tenants.json"
    rc = loadgen.main(
        ["--tenants", "2", "--duration", "0.8", "--out", str(out)]
    )
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["ok_verdict"] and "tenants" in report


def test_obs_top_tenants_once_smoke(capsys):
    """obs_top --tenants --once against a live StatusServer carrying the
    gateway's per-tenant ledger: request/reject tables, the per-tenant
    posting/p99/burn row, and the violated-objective banner."""
    import obs_top

    from advanced_scrapper_tpu.obs import telemetry

    telemetry.REGISTRY.reset()
    telemetry.set_enabled(True)
    srv = None
    try:
        telemetry.REGISTRY.counter(
            "astpu_tenant_requests_total", "t", always=True,
            tenant="acme", verb="submit_batch", outcome="ok",
        ).inc(40)
        telemetry.REGISTRY.counter(
            "astpu_tenant_requests_total", "t", always=True,
            tenant="acme", verb="submit_batch", outcome="rejected",
        ).inc(4)
        telemetry.REGISTRY.counter(
            "astpu_tenant_rejected_total", "t", always=True,
            tenant="acme", reason="rate",
        ).inc(4)
        telemetry.REGISTRY.gauge(
            "astpu_tenant_postings", "t", always=True, tenant="acme"
        ).set(1234)
        h = telemetry.REGISTRY.histogram(
            "astpu_tenant_seconds", "t", always=True,
            tenant="acme", verb="submit_batch",
        )
        for _ in range(20):
            h.observe(0.002)
        telemetry.REGISTRY.gauge(
            "astpu_slo_burn_rate", "t",
            objective="tenant_acme_p99", window="fast",
        ).set(2.5)
        telemetry.REGISTRY.gauge(
            "astpu_slo_compliant", "t", objective="tenant_acme_p99"
        ).set(0.0)
        srv = telemetry.StatusServer(port=0).start()
        rc = obs_top.main(
            ["--url", f"http://127.0.0.1:{srv.port}", "--once", "--tenants"]
        )
        out = capsys.readouterr().out
        assert rc == 0
        assert "obs_top --tenants @" in out
        assert "tenants (front-door gateway):" in out
        assert "acme" in out and "submit_batch" in out
        assert "quota rejects" in out and "rate" in out
        assert "1234" in out  # posting count
        assert "2.50" in out  # burn column
        assert "tenant slo VIOLATED: tenant_acme_p99" in out
    finally:
        if srv is not None:
            srv.stop()
        telemetry.REGISTRY.reset()
        telemetry.set_enabled(None)


def test_lint_metrics_covers_tenant_series():
    """The naming linter sees the gateway's per-tenant series — one
    owner each (service/gateway.py), suffix rules green."""
    import lint_metrics

    seen: dict[str, set] = {}
    pkg = os.path.join(REPO, "advanced_scrapper_tpu")
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in files:
            if fn.endswith(".py"):
                _problems, regs = lint_metrics.check_file(
                    os.path.join(dirpath, fn)
                )
                for name, _kind, _ln in regs:
                    seen.setdefault(name, set()).add(fn)
    for name in (
        "astpu_tenant_requests_total",
        "astpu_tenant_rejected_total",
        "astpu_tenant_seconds",
        "astpu_tenant_postings",
    ):
        assert name in seen, f"{name} never registered"
        assert seen[name] == {"gateway.py"}, (name, seen[name])
    assert not lint_metrics.lint(), "naming lint must stay clean"


def test_crashsweep_tenant_workload_registered():
    """Mixed-tenant traffic under shard kills is a first-class crashsweep
    workload: child + verifier registered, and the default battery
    actually schedules it."""
    import inspect

    import crashsweep

    assert "tenant" in crashsweep.CHILDREN
    assert "tenant" in crashsweep.VERIFIERS
    battery = inspect.getsource(crashsweep.main)
    assert "sweep_tenant(" in battery
