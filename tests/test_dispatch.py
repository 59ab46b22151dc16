"""Single-dispatch dedup tiles (ISSUE 9): the pipelined dispatch
executor, packed H2D transfers, the donated fused tile step, and the
always-on device-traffic counters that gate the win numerically.

Certification strategy: output must not depend on the dispatch knobs,
and the estimator-only resolution must equal the plain numpy references
(``cpu.oracle.device_family_signatures`` + ``oracle_lsh_reps``) — across
the one-shot, async, streaming (batch backend) and persistent-index
modes.
"""

from __future__ import annotations

import numpy as np
import pytest

from advanced_scrapper_tpu.config import DedupConfig
from advanced_scrapper_tpu.pipeline.dedup import NearDupEngine
from advanced_scrapper_tpu.pipeline.dispatch import (
    PipelinedDispatcher,
    resolve_dispatch_window,
)


def _corpus(rng: np.random.RandomState, n: int) -> list[bytes]:
    """Adversarial ragged mix: empties, sub-shingle docs, bucket-edge
    lengths, blockwise docs, planted duplicates."""
    docs: list[bytes] = []
    specials = [0, 1, 4, 63, 64, 65, 128, 4096, 4097, 9001]
    for i in range(n):
        if i < len(specials):
            ln = specials[i]
        elif i >= 8 and rng.rand() < 0.25:
            docs.append(docs[rng.randint(0, i)])
            continue
        else:
            ln = int(rng.randint(5, 9000))
        docs.append(rng.randint(32, 127, size=ln, dtype=np.uint8).tobytes())
    return docs


#: the estimator-only resolution (precision tier and exact verify off):
#: the configuration the plain numpy references model
ESTIMATOR_ONLY = DedupConfig(rerank=False, exact_verify_band=0.0)


def _plain_reps(eng: NearDupEngine, docs: list[bytes]) -> np.ndarray:
    """Plain numpy representatives: the host twin of the device hash
    family, resolved by the estimator-only LSH reference."""
    from advanced_scrapper_tpu.cpu.oracle import (
        device_family_signatures,
        oracle_lsh_reps,
    )

    valid = np.fromiter(map(len, docs), np.int64) >= eng.params.shingle_k
    return oracle_lsh_reps(
        device_family_signatures(docs, eng.params),
        eng.params,
        cand_subbands=eng.cfg.cand_subbands,
        threshold=eng.cfg.sim_threshold,
        valid=valid,
    )


def _numpy_fold(tok, lens, owners, params, n_bucket: int) -> np.ndarray:
    """Per-owner ``np.minimum.at`` fold of per-row host signatures into a
    fresh all-``U32_MAX`` ``uint32[n_bucket, num_perm]`` accumulator."""
    from advanced_scrapper_tpu.cpu.oracle import device_family_signatures

    rows = [bytes(tok[i, : lens[i]]) for i in range(tok.shape[0])]
    acc = np.full((n_bucket, params.num_perm), 0xFFFFFFFF, np.uint32)
    np.minimum.at(acc, owners, device_family_signatures(rows, params))
    return acc


# -- the executor itself -----------------------------------------------------


def test_executor_delivers_every_tile_and_window_resolution():
    items = list(range(57))
    pipe = PipelinedDispatcher(
        iter(items),
        pack=lambda x: x * 10,
        put=lambda x: x + 1,
        put_workers=3,
        window=2,
        name="test.h2d",
    )
    try:
        got = sorted(pipe)  # put pool may reorder; the set must be exact
    finally:
        pipe.close()
    assert got == [x * 10 + 1 for x in items]
    assert resolve_dispatch_window(0, 1) == 2  # auto: double buffering
    assert resolve_dispatch_window(0, 4) == 4  # auto: pool-deep
    assert resolve_dispatch_window(7, 4) == 7  # explicit wins


def test_executor_propagates_worker_errors():
    def bad_put(x):
        if x == 3:
            raise ValueError("boom in put")
        return x

    pipe = PipelinedDispatcher(
        iter(range(8)), pack=lambda x: x, put=bad_put, put_workers=2,
        name="test.h2d",
    )
    try:
        with pytest.raises(RuntimeError) as ei:
            list(pipe)
        assert isinstance(ei.value.__cause__, ValueError)
    finally:
        pipe.close()
    pipe.close()  # idempotent


def test_executor_encode_generator_error_fails_graph():
    def gen():
        yield 1
        raise OSError("encode died")

    pipe = PipelinedDispatcher(
        gen(), pack=lambda x: x, put=lambda x: x, name="test.h2d"
    )
    try:
        with pytest.raises(RuntimeError) as ei:
            list(pipe)
        assert isinstance(ei.value.__cause__, OSError)
    finally:
        pipe.close()


# -- per-tile device traffic (the acceptance gate) ---------------------------


def test_per_tile_traffic_one_put_one_dispatch():
    """Exactly 1 put + 1 dispatch per tile (plus the per-corpus
    valid-mask put and the fused resolve epilogue dispatch), and the
    counted bytes are exactly the packed buffers the probe saw —
    asserted via the ALWAYS-ON counters, so the contract is a measured
    number, not prose."""
    from advanced_scrapper_tpu.obs import stages
    from advanced_scrapper_tpu.ops.pack import packed_nbytes

    rng = np.random.RandomState(3)
    docs = _corpus(rng, 128)
    # rerank=False: the traffic ledger here audits the SIGNATURE tile
    # plane; the precision tier's own tiles+1/tiles+1 contract has its
    # dedicated gate in test_rerank_dispatch.py on the "rerank" regime
    eng = NearDupEngine(DedupConfig(rerank=False))
    probe: list[dict] = []
    eng.dispatch_probe = probe.append
    before = stages.device_counters()
    rep = np.asarray(eng.dedup_reps_async(docs))[: len(docs)]
    after = stages.device_counters()
    puts = after["device_puts"] - before["device_puts"]
    disp = after["device_dispatches"] - before["device_dispatches"]
    nbytes = after["h2d_bytes"] - before["h2d_bytes"]
    tiles = eng.last_tiles
    assert tiles > 1 and len(probe) == tiles
    # 1 put/tile + 1 valid-mask put; 1 dispatch/tile + ONE fused resolve
    # epilogue — tiles × 1 + 1
    assert puts == tiles + 1, (puts, tiles)
    assert disp == tiles + 1, (disp, tiles)
    # each tile ships tokens plus the 8 B/row (length, owner) trailer
    assert all(
        t["h2d_bytes"] == packed_nbytes(t["rows"], t["width"]) for t in probe
    )
    n_bucket = 128  # bucket_len(128 docs): the valid mask's bool bytes
    assert nbytes == sum(t["h2d_bytes"] for t in probe) + n_bucket
    assert (rep == _plain_reps(eng, docs)).all()


# -- donation safety ---------------------------------------------------------


def test_fused_step_donates_accumulator():
    """The running accumulator buffer is DONATED to the fused step: after
    a call the old buffer is dead (device reuses it in place) and any
    further use of it is an error — the executor must never touch it
    again, and provably does not (the parity suite passes with donation
    live)."""
    import jax
    import jax.numpy as jnp

    from advanced_scrapper_tpu.core.hashing import make_params
    from advanced_scrapper_tpu.ops.minhash import make_fused_tile_step
    from advanced_scrapper_tpu.ops.pack import pack_tile
    from advanced_scrapper_tpu.ops.shingle import U32_MAX

    params = make_params()
    step = make_fused_tile_step(params, "scan")
    rng = np.random.RandomState(0)
    rows, width, n_bucket = 64, 128, 64
    tok = rng.randint(32, 127, size=(rows, width)).astype(np.uint8)
    lens = np.full((rows,), width, np.int32)
    owners = (np.arange(rows) % n_bucket).astype(np.int32)
    packed = jnp.asarray(pack_tile(tok, lens, owners))

    running = jnp.full((n_bucket, params.num_perm), U32_MAX, jnp.uint32)
    out = step(
        running, packed, rows=rows, width=width, num_articles=n_bucket
    )
    out.block_until_ready()
    if not running.is_deleted():
        pytest.skip("backend does not implement buffer donation")
    # the donated buffer is unusable afterwards — referencing it raises
    with pytest.raises(RuntimeError):
        np.asarray(running)
    # and the fold is bit-exact vs a plain per-owner numpy fold of the
    # host twin of the device hash family
    want = _numpy_fold(tok, lens, owners, params, n_bucket)
    assert (np.asarray(out) == want).all()


# -- output against the plain references --------------------------------------


def test_packed_parity_oneshot_and_async():
    """Signatures equal the host twin of the device hash family bit for
    bit; the estimator-only resolution (one-shot with exact verify off,
    and async) equals the plain numpy LSH resolution."""
    from advanced_scrapper_tpu.cpu.oracle import device_family_signatures

    rng = np.random.RandomState(11)
    docs = _corpus(rng, 96)
    eng = NearDupEngine(ESTIMATOR_ONLY)
    want = _plain_reps(eng, docs)
    assert (eng.dedup_reps(docs) == want).all()
    assert (np.asarray(eng.dedup_reps_async(docs))[: len(docs)] == want).all()
    assert (
        eng.signatures(docs) == device_family_signatures(docs, eng.params)
    ).all()


def test_fused_resolve_matches_two_stage_hook_path():
    """The one-dispatch fused resolve (no rerank hook) and the two-stage
    candidates→resolve split (hooked engines) are the same math — a
    passthrough hook must not change a single representative, with and
    without the fine-margin per-edge bars."""
    rng = np.random.RandomState(31)
    docs = _corpus(rng, 96)
    # rerank=False: the comparison needs a passthrough hook vs NO hook;
    # the default tier rewrites the matrix and is covered elsewhere
    for cfg in (
        DedupConfig(rerank=False),
        DedupConfig(rerank=False, fine_margin=0.05),
    ):
        hooked = NearDupEngine(cfg)
        hooked.rerank_hook = lambda raw, sigs, rb, valid: rb  # passthrough
        a = np.asarray(hooked.dedup_reps_async(docs))
        b = np.asarray(NearDupEngine(cfg).dedup_reps_async(docs))
        assert (a == b).all(), cfg.fine_margin


def test_packed_parity_window_and_worker_knobs():
    """Any (put_workers, dispatch_window) combination is byte-identical —
    the min-combine is order-independent, so out-of-order staging from a
    deep window must never show in the output."""
    rng = np.random.RandomState(13)
    docs = _corpus(rng, 72)
    want = NearDupEngine(DedupConfig()).dedup_reps(docs)
    for pw, win in ((1, 1), (3, 1), (4, 6)):
        cfg = DedupConfig(put_workers=pw, dispatch_window=win)
        got = NearDupEngine(cfg).dedup_reps(docs)
        assert (got == want).all(), (pw, win)


def test_packed_parity_streaming_batch_backend():
    """The stream mode end to end: TpuBatchBackend annotations (exact +
    near-dup attribution) byte-identical across the dispatch knobs (the
    put pool may reorder tiles; the output must not show it)."""
    from advanced_scrapper_tpu.extractors.tpu_batch import TpuBatchBackend

    rng = np.random.RandomState(17)
    docs = _corpus(rng, 64)

    def annotate(cfg):
        backend = TpuBatchBackend(cfg)
        recs = [
            {"url": f"u{i % 48}", "article": d.decode("latin-1")}
            for i, d in enumerate(docs)
        ]
        out = []
        for r in recs:
            out.extend(backend.submit(dict(r)))
        out.extend(backend.flush())
        return [(r["url"], r["dup_of"], r["near_dup_of"]) for r in out]

    base = annotate(DedupConfig())
    assert base == annotate(DedupConfig(put_workers=3, dispatch_window=1))


def test_packed_parity_persist_index_mode(tmp_path):
    """The persist mode: dedup_against_index attributions independent of
    the dispatch knobs (separate index dirs, same corpus stream), and
    every exact copy across the batches attributed."""
    from advanced_scrapper_tpu.index import PersistentIndex

    rng = np.random.RandomState(19)
    half_a = _corpus(rng, 48)
    half_b = _corpus(rng, 48) + half_a[:8]  # cross-batch dups

    def run(cfg, d):
        eng = NearDupEngine(cfg)
        idx = PersistentIndex(str(tmp_path / d))
        try:
            out_a = eng.dedup_against_index(half_a, idx)
            out_b = eng.dedup_against_index(half_b, idx)
        finally:
            idx.close()
        return out_a.tolist(), out_b.tolist()

    got = run(DedupConfig(), "base")
    assert got == run(DedupConfig(put_workers=4, dispatch_window=6), "deep")
    # the tail of half_b repeats half_a[:8]: each eligible copy finds a
    # posting (its own first occurrence or an earlier near-dup of it)
    for k, doc in enumerate(half_a[:8]):
        if len(doc) >= 5:
            assert got[1][48 + k] >= 0, (k, got[1][48 + k])


def test_signatures_and_keys_matches_host_composition():
    """The fused (sigs, keys) epilogue — narrow and wide — equals the
    host composition (host-twin signatures, then band_keys*/
    candidate_keys over them) bit for bit."""
    import jax.numpy as jnp

    from advanced_scrapper_tpu.cpu.oracle import device_family_signatures
    from advanced_scrapper_tpu.ops.lsh import band_keys_wide, candidate_keys

    rng = np.random.RandomState(23)
    docs = _corpus(rng, 80)
    eng = NearDupEngine(DedupConfig())
    sigs_ref = device_family_signatures(docs, eng.params)
    sigs, keys = eng.signatures_and_keys(docs)
    assert (sigs == sigs_ref).all()
    want = np.asarray(
        candidate_keys(
            jnp.asarray(sigs_ref), eng.params.band_salt, eng.cfg.cand_subbands
        )
    )
    assert (keys == want).all()
    sigs_w, keys_w = eng.signatures_and_keys(docs, wide=True)
    assert (sigs_w == sigs_ref).all()
    want_w = np.asarray(
        band_keys_wide(
            jnp.asarray(sigs_ref), jnp.asarray(eng.params.band_salt)
        )
    )
    assert (keys_w == want_w).all()
    # empty corpus: typed empties, no device work
    s0, k0 = eng.signatures_and_keys([])
    assert s0.shape == (0, eng.params.num_perm) and k0.shape[0] == 0


def test_pack_roundtrip_unpack():
    """pack_tile → unpack_tile is the identity on (tokens, lengths,
    owners), including int32 values past one byte."""
    import jax.numpy as jnp

    from advanced_scrapper_tpu.ops.pack import pack_tile, unpack_tile

    rng = np.random.RandomState(29)
    rows, width = 64, 96
    tok = rng.randint(0, 256, size=(rows, width)).astype(np.uint8)
    lens = rng.randint(0, 1 << 22, size=rows).astype(np.int32)
    owners = rng.randint(0, 1 << 20, size=rows).astype(np.int32)
    buf = pack_tile(tok, lens, owners)
    assert buf.dtype == np.uint8 and buf.shape == (rows * (width + 8),)
    t, l, o = unpack_tile(jnp.asarray(buf), rows, width)
    assert (np.asarray(t) == tok).all()
    assert (np.asarray(l) == lens).all()
    assert (np.asarray(o) == owners).all()


def test_nativebuild_falls_back_to_tmp_when_target_unwritable(tmp_path):
    """build_or_find must route around an unwritable beside-source target
    (the BENCH_r05 silent-fallback shape) and report a reason when every
    candidate fails."""
    import os

    from advanced_scrapper_tpu.cpu.nativebuild import (
        build_or_find,
        fallback_lib_path,
        hashed_lib_path,
    )

    src = tmp_path / "mini.cpp"
    src.write_text('extern "C" int forty_two() { return 42; }\n')
    # the beside-source target is unreachable: its parent is a FILE, so
    # neither makedirs nor g++ -o can produce it (robust under root,
    # where chmod-based unwritability is bypassed)
    blocker = tmp_path / "blocker"
    blocker.write_text("")
    target = str(blocker / "libmini-astpu-test.so")
    fb = fallback_lib_path(hashed_lib_path(str(src), target))
    if os.path.exists(fb):
        os.unlink(fb)
    lib, why = build_or_find(str(src), target)
    if lib is None and "g++ not found" in why:
        pytest.skip("no C++ toolchain")
    assert lib == fb and why == ""
    assert os.path.exists(fb)
    os.unlink(fb)
    # total failure names a reason instead of silently degrading
    bad = tmp_path / "bad.cpp"
    bad.write_text("this is not C++\n")
    lib2, why2 = build_or_find(str(bad), str(tmp_path / "libbad.so"))
    assert lib2 is None and why2


def test_nativebuild_keys_libraries_on_source_content(tmp_path):
    """A library loads only under the hash of the source it was built
    from: a stale .so with a fresh mtime (a copied tree) is never found,
    and an edit to the source or a local header names a new library."""
    import os

    from advanced_scrapper_tpu.cpu.nativebuild import build_or_find, find_fresh

    (tmp_path / "h.h").write_text("#define V 1\n")
    src = tmp_path / "k.cpp"
    src.write_text('#include "h.h"\nextern "C" int v() { return V; }\n')
    canonical = str(tmp_path / "libk.so")
    open(canonical, "wb").close()  # a stale library at the plain name
    assert find_fresh(str(src), canonical) is None
    lib, why = build_or_find(str(src), canonical)
    if lib is None and "g++ not found" in why:
        pytest.skip("no C++ toolchain")
    assert lib != canonical and os.path.exists(lib) and why == ""
    assert find_fresh(str(src), canonical) == lib
    (tmp_path / "h.h").write_text("#define V 2\n")
    assert find_fresh(str(src), canonical) is None


# -- device-plane degradation (overload-safe ingest) --------------------------


def test_watchdog_trips_on_hung_put_with_dump_and_teardown(tmp_path, monkeypatch):
    """A wedged tile (hung inside the put stage) trips the watchdog
    inside its budget: counted, flight recorder dumped, whole graph torn
    down, and the consumer raises DispatchTimeout instead of blocking
    forever."""
    import threading

    from advanced_scrapper_tpu.obs import telemetry, trace
    from advanced_scrapper_tpu.pipeline.dispatch import DispatchTimeout

    dump = tmp_path / "flight.jsonl"
    trace.RECORDER.set_active(True)  # the env gate caches on first touch
    trace.RECORDER.set_dump_path(str(dump))
    trace.RECORDER.clear()  # re-arm the once-per-death dump latch
    hang = threading.Event()

    def hung_put(x):
        hang.wait(30.0)  # far beyond the budget
        return x

    before = telemetry.REGISTRY.counter(
        "astpu_dispatch_watchdog_trips_total", always=True
    ).value
    pipe = PipelinedDispatcher(
        iter(range(4)),
        pack=lambda x: x,
        put=hung_put,
        watchdog_s=0.3,
    )
    try:
        with pytest.raises(DispatchTimeout):
            list(pipe)
        after = telemetry.REGISTRY.counter(
            "astpu_dispatch_watchdog_trips_total", always=True
        ).value
        assert after == before + 1
        assert dump.exists(), "watchdog never dumped the flight recorder"
        text = dump.read_text()
        assert "dispatch watchdog" in text
        assert '"dispatch.watchdog"' in text
    finally:
        hang.set()
        pipe.close()
        trace.RECORDER.set_dump_path(None)
        trace.RECORDER.set_active(None)
        trace.RECORDER.clear()


def test_watchdog_trips_on_hung_caller_dispatch():
    """A hang in the CALLER's dispatch (the device step) also goes
    stale — the beat only advances when iteration re-enters — so the
    watchdog still counts and tears down (the consumer itself is stuck,
    but the wedge becomes visible and every worker exits)."""
    import threading
    import time as _time

    from advanced_scrapper_tpu.obs import telemetry
    from advanced_scrapper_tpu.pipeline.dispatch import DispatchTimeout

    release = threading.Event()
    pipe = PipelinedDispatcher(
        iter(range(4)),
        pack=lambda x: x,
        put=lambda x: x,
        watchdog_s=0.25,
    )
    got = []
    err = []

    def consume():
        try:
            for item in pipe:
                got.append(item)
                release.wait(20.0)  # "hung device call" on the first tile
        except DispatchTimeout as e:
            err.append(e)

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    deadline = _time.monotonic() + 5
    while pipe.error is None and _time.monotonic() < deadline:
        _time.sleep(0.02)
    assert isinstance(pipe.error, DispatchTimeout)
    release.set()
    t.join(timeout=5)
    pipe.close()


def test_watchdog_quiet_on_clean_run():
    from advanced_scrapper_tpu.obs import telemetry

    c = telemetry.REGISTRY.counter(
        "astpu_dispatch_watchdog_trips_total", always=True
    )
    before = c.value
    pipe = PipelinedDispatcher(
        iter(range(32)),
        pack=lambda x: x,
        put=lambda x: x,
        watchdog_s=5.0,
    )
    assert sorted(list(pipe)) == list(range(32))
    pipe.close()
    assert c.value == before


def test_oom_backoff_halving_converges_byte_identical(monkeypatch):
    """Injected RESOURCE_EXHAUSTED (chaos env) halves tiles, re-packs,
    retries — and the fold converges byte-identical to the unthrottled
    path, with the extra halved puts visible on the always-on ledger."""
    from advanced_scrapper_tpu.obs import stages, telemetry
    from advanced_scrapper_tpu.pipeline import dispatch as dp

    # uniform ~one-block docs: ONE width bucket whose first tile is a
    # 512-row power-of-two chunk — the injected OOMs land on tiles with
    # real halving headroom (a 64-row floor tile would fail clean, which
    # is the OTHER test)
    rng = np.random.RandomState(11)
    docs = [
        rng.randint(32, 127, size=int(rng.randint(900, 1100)), dtype=np.uint8)
        .tobytes()
        for _ in range(512)
    ]
    eng = NearDupEngine(DedupConfig())
    clean = np.asarray(eng.dedup_reps(docs))

    monkeypatch.setenv("ASTPU_CHAOS_DISPATCH_OOM", "2")
    dp.reset_chaos_oom()
    backoffs = telemetry.REGISTRY.counter(
        "astpu_dispatch_oom_backoff_total", always=True, plane="dedup"
    )
    b0 = backoffs.value
    before = stages.device_counters()
    throttled = np.asarray(eng.dedup_reps(docs))
    after = stages.device_counters()
    monkeypatch.delenv("ASTPU_CHAOS_DISPATCH_OOM")
    dp.reset_chaos_oom()

    assert (throttled == clean).all(), "OOM backoff changed the output"
    assert backoffs.value > b0, "the injection never engaged the ladder"
    # each halving pays 2 extra puts (the re-packed halves)
    extra_puts = int(after["device_puts"] - before["device_puts"])
    assert extra_puts >= eng.last_tiles + 1 + 2, (
        f"halved tiles never re-crossed H2D (puts delta {extra_puts})"
    )


def test_oom_ladder_to_floor_fails_clean(monkeypatch):
    """An injection budget deep enough to out-halve the floor produces a
    clean RESOURCE_EXHAUSTED failure — bounded, attributable, no wedge —
    and the engine is reusable afterwards."""
    from advanced_scrapper_tpu.pipeline import dispatch as dp

    rng = np.random.RandomState(12)
    docs = _corpus(rng, 64)
    eng = NearDupEngine(DedupConfig())
    monkeypatch.setenv("ASTPU_CHAOS_DISPATCH_OOM", "100000")
    dp.reset_chaos_oom()
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        eng.dedup_reps(docs)
    monkeypatch.delenv("ASTPU_CHAOS_DISPATCH_OOM")
    dp.reset_chaos_oom()
    clean = np.asarray(eng.dedup_reps(docs))
    assert clean.shape[0] >= len(docs)


def test_oom_backoff_floor_and_markers():
    from advanced_scrapper_tpu.pipeline.dispatch import (
        OOM_FLOOR_ROWS,
        dispatch_with_oom_backoff,
        is_resource_exhausted,
    )

    assert is_resource_exhausted(RuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert is_resource_exhausted(MemoryError("Resource exhausted: HBM"))
    assert is_resource_exhausted(RuntimeError("ran out of memory on device"))
    assert not is_resource_exhausted(ValueError("shape mismatch"))

    # a non-OOM error propagates untouched, never split
    calls = []
    with pytest.raises(ValueError):
        dispatch_with_oom_backoff(
            lambda c, i: (_ for _ in ()).throw(ValueError("boom")),
            0, (None, 128),
            split=lambda i: calls.append(i) or [],
            rows_of=lambda i: i[1],
        )
    assert not calls

    # at the floor the OOM propagates cleanly instead of splitting
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        dispatch_with_oom_backoff(
            lambda c, i: (_ for _ in ()).throw(
                RuntimeError("RESOURCE_EXHAUSTED: x")
            ),
            0, (None, OOM_FLOOR_ROWS),
            split=lambda i: calls.append(i) or [],
            rows_of=lambda i: i[1],
        )
    assert not calls


def test_oom_backoff_generic_fold_halves_to_success():
    """Pure-python model of the ladder: a fold that OOMs above 128 rows
    converges through recursive halving with the leaf sum intact."""
    from advanced_scrapper_tpu.pipeline.dispatch import (
        dispatch_with_oom_backoff,
    )

    def fn(carry, item):
        lo, hi = item
        if hi - lo > 128:
            raise RuntimeError("RESOURCE_EXHAUSTED: too big")
        return carry + sum(range(lo, hi))

    def split(item):
        lo, hi = item
        mid = lo + (hi - lo) // 2
        return [(lo, mid), (mid, hi)]

    total = dispatch_with_oom_backoff(
        fn, 0, (0, 1024), split=split, rows_of=lambda it: it[1] - it[0],
    )
    assert total == sum(range(1024))
