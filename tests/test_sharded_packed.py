"""Pod-scale packed dedup (ISSUE 13): the fused donated tile step sharded
over a device mesh — per-shard donation, per-shard launch ledger, byte
parity against BOTH oracles (the single-device fused plane and the plain
numpy estimator-only LSH resolution, ``cpu.oracle.oracle_lsh_reps``), the
shared-prewarm jit-cache contract, and the sharded band-key fan-out into
the persistent-index plane.

Certification strategy mirrors PR 9: the packed sharded transport is pure
performance work, so every representative (and every index attribution)
must match the certified paths bit for bit on every mesh shape.
"""

from __future__ import annotations

import numpy as np
import pytest

from advanced_scrapper_tpu.config import DedupConfig
from advanced_scrapper_tpu.core.mesh import build_mesh
from advanced_scrapper_tpu.pipeline.dedup import NearDupEngine


def _corpus(rng: np.random.RandomState, n: int) -> list[bytes]:
    """Adversarial ragged mix: empties, sub-shingle docs, bucket-edge
    lengths, blockwise docs, planted duplicates (the test_dispatch.py
    certification corpus)."""
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


@pytest.fixture(scope="module")
def mesh42(devices8):
    return build_mesh(4, 2)


@pytest.fixture(scope="module")
def mesh81(devices8):
    return build_mesh(8, 1)


# -- byte parity against both oracles -----------------------------------------


def _plain_reps(eng: NearDupEngine, docs: list[bytes]) -> np.ndarray:
    """The plain numpy reference: the host twin of the device hash family
    resolved by the estimator-only LSH reference."""
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


#: the estimator-only configuration the plain reference models
ESTIMATOR_ONLY = DedupConfig(rerank=False, exact_verify_band=0.0)


def test_sharded_packed_matches_both_oracles(mesh42, mesh81):
    """The acceptance triangle: packed-sharded representatives must equal
    the single-device fused path AND the plain numpy LSH resolution, on
    a 4x2 and an 8x1 mesh (a shard is a device, whatever the dp/sp
    factorisation)."""
    rng = np.random.RandomState(3)
    docs = _corpus(rng, 128)
    eng = NearDupEngine(ESTIMATOR_ONLY)
    want = np.asarray(eng.dedup_reps_async(docs))[: len(docs)]
    assert (want == _plain_reps(eng, docs)).all()
    for mesh in (mesh42, mesh81):
        got = eng.dedup_reps_sharded(docs, mesh)
        assert (got == want).all(), mesh.shape


@pytest.mark.parametrize(
    "seed,n_docs,batch_size", [(21, 96, 64), (22, 160, 1024)]
)
def test_sharded_packed_matches_plain_reference(mesh42, seed, n_docs, batch_size):
    """More seeds and tile shapes of the sharded plane against the plain
    numpy LSH resolution: 64-row tiles spread one corpus over many tile
    groups (and an article's blocks over several shards); 1024-row tiles
    leave most shards a zero padding tile."""
    rng = np.random.RandomState(seed)
    docs = _corpus(rng, n_docs)
    eng = NearDupEngine(
        DedupConfig(rerank=False, exact_verify_band=0.0, batch_size=batch_size)
    )
    got = eng.dedup_reps_sharded(docs, mesh42)
    assert (got == _plain_reps(eng, docs)).all()


def test_sharded_packed_parity_fine_margin_and_oph(mesh42):
    """Knob parity: the fine-margin per-edge bars and the OPH backend
    (raw accumulate, densify AFTER the cross-shard pmin) resolve exactly
    like the single-device async engine under the same config."""
    rng = np.random.RandomState(7)
    docs = _corpus(rng, 64)
    for cfg in (
        DedupConfig(fine_margin=0.05),
        DedupConfig(backend="oph"),
    ):
        eng = NearDupEngine(cfg)
        want = np.asarray(eng.dedup_reps_async(docs))[: len(docs)]
        got = eng.dedup_reps_sharded(docs, mesh42)
        assert (got == want).all(), cfg


def test_sharded_packed_window_and_worker_knobs(mesh81):
    """Any (put_workers, dispatch_window) combination is byte-identical —
    out-of-order tile-group staging from the put pool must never show in
    the min-combine."""
    rng = np.random.RandomState(13)
    docs = _corpus(rng, 56)
    want = NearDupEngine(DedupConfig()).dedup_reps_sharded(docs, mesh81)
    for pw, win in ((3, 1), (4, 6)):
        cfg = DedupConfig(put_workers=pw, dispatch_window=win)
        got = NearDupEngine(cfg).dedup_reps_sharded(docs, mesh81)
        assert (got == want).all(), (pw, win)


def test_sharded_packed_empty_and_env_routing(mesh81, monkeypatch):
    """Empty corpus returns a typed empty with no device work; the
    removed ASTPU_DEDUP_PACKED_H2D=0 switch fails loudly instead of
    silently selecting a transport that no longer exists."""
    from advanced_scrapper_tpu.config import from_env

    eng = NearDupEngine(DedupConfig())
    out = eng.dedup_reps_sharded([], mesh81)
    assert out.shape == (0,) and out.dtype == np.int32
    monkeypatch.setenv("ASTPU_DEDUP_PACKED_H2D", "0")
    with pytest.raises(ValueError, match="MIGRATION.md"):
        from_env(DedupConfig, "dedup")
    monkeypatch.setenv("ASTPU_DEDUP_PACKED_H2D", "1")
    assert from_env(DedupConfig, "dedup").packed_h2d is True


# -- per-shard launch ledger (the acceptance gate) -----------------------------


def test_per_tile_traffic_one_put_one_dispatch_per_shard(mesh42):
    """EVERY shard's always-on counter delta is exactly tiles + 1 puts
    and tiles + 1 dispatches per corpus (tiles + the valid-mask put;
    tiles + the combine/resolve epilogue) — the single-device plane's
    ISSUE 9 contract, applied per shard, with equal bytes per shard
    (same-shape tile groups)."""
    from advanced_scrapper_tpu.obs import stages
    from advanced_scrapper_tpu.parallel.sharded_packed import mesh_num_shards

    rng = np.random.RandomState(11)
    docs = _corpus(rng, 128)
    eng = NearDupEngine(DedupConfig())
    before = stages.sharded_device_counters()
    rep = eng.dedup_reps_sharded(docs, mesh42)
    after = stages.sharded_device_counters()
    tiles = eng.last_tiles
    assert tiles > 1 and rep.shape == (len(docs),)
    nsh = mesh_num_shards(mesh42)
    deltas = {
        s: {
            k: after[s][k] - before.get(s, {}).get(k, 0.0)
            for k in after[s]
        }
        for s in after
    }
    assert len(deltas) == nsh, sorted(deltas)
    bytes_seen = set()
    for s, d in deltas.items():
        assert d["device_puts"] == tiles + 1, (s, d, tiles)
        assert d["device_dispatches"] == tiles + 1, (s, d, tiles)
        bytes_seen.add(d["h2d_bytes"])
    # same-shape groups ⇒ every shard ships identical bytes
    assert len(bytes_seen) == 1, deltas
    # and the skew gauge (the bench's SLO hook) reads balanced
    assert stages.record_sharded_put_skew() == 0.0


# -- donation ------------------------------------------------------------------


def test_sharded_fused_step_donates_per_shard(mesh42):
    """The sharded running accumulator is DONATED into the partitioned
    step — pjit rebases the donation per shard, so after a call the old
    global buffer (and every per-shard slice of it) is dead, and the fold
    is bit-exact vs a plain per-owner numpy fold of each shard's tile."""
    import jax

    from advanced_scrapper_tpu.core.hashing import make_params
    from advanced_scrapper_tpu.cpu.oracle import device_family_signatures
    from advanced_scrapper_tpu.ops.pack import pack_tile
    from advanced_scrapper_tpu.parallel.sharded_packed import (
        assemble_packed_tiles,
        local_shard_rows,
        make_sharded_accumulator_init,
        make_sharded_fused_tile_step,
        make_sharded_resolve_epilogue,
        mesh_num_shards,
        shard_row_devices,
    )

    params = make_params()
    step = make_sharded_fused_tile_step(mesh42, params, "scan")
    init = make_sharded_accumulator_init(mesh42, params.num_perm)
    nsh = mesh_num_shards(mesh42)
    devices = shard_row_devices(mesh42)
    assert local_shard_rows(mesh42) == list(range(nsh))  # single host

    rng = np.random.RandomState(0)
    rows, width, n_bucket = 64, 128, 64
    tiles = []
    shards = []
    for s in range(nsh):
        tok = rng.randint(32, 127, size=(rows, width)).astype(np.uint8)
        lens = np.full((rows,), width, np.int32)
        owners = (np.arange(rows) % n_bucket).astype(np.int32)
        tiles.append((tok, lens, owners))
        shards.append(
            jax.device_put(pack_tile(tok, lens, owners)[None], devices[s])
        )
    packed = assemble_packed_tiles(mesh42, shards, shards[0].shape[1])
    running = init(num_articles=n_bucket)
    out = step(running, packed, rows=rows, width=width, num_articles=n_bucket)
    out.block_until_ready()
    if not running.is_deleted():
        pytest.skip("backend does not implement buffer donation")
    with pytest.raises(RuntimeError):
        np.asarray(running)  # the donated buffer is unusable afterwards
    # per-shard fold parity: shard s's accumulator row equals the
    # per-owner np.minimum.at fold of shard s's tile alone
    got = np.asarray(out)
    for s, (tok, lens, owners) in enumerate(tiles):
        want = np.full((n_bucket, params.num_perm), 0xFFFFFFFF, np.uint32)
        rows_s = [bytes(tok[i, : lens[i]]) for i in range(rows)]
        np.minimum.at(want, owners, device_family_signatures(rows_s, params))
        assert (got[s] == want).all(), s
    # and the epilogue's pmin-combine equals the elementwise min of rows
    epi = make_sharded_resolve_epilogue(
        mesh42, params,
        threshold=0.7, fine_margin=0.0,
        fine_salt=np.zeros((0,), np.uint32), backend="scan",
    )
    valid = jax.device_put(np.ones((n_bucket,), bool))
    rep = epi(out, valid, jump_rounds=6)
    assert np.asarray(rep).shape == (n_bucket,)


# -- prewarm: the shape set is shared with the chunker -------------------------


def test_prewarm_sharded_compiles_the_chunker_shape_set(mesh81):
    """prewarm_sharded must compile exactly the (width × rows) variants
    the shared chunker emits — a real corpus afterwards adds ZERO jit
    cache entries (the silently-disjoint-prewarm regression gate), and
    the epilogue for the pinned bucket is covered too."""
    cfg = DedupConfig(block_len=256, batch_size=64)
    eng = NearDupEngine(cfg)
    n_compiled = eng.prewarm_sharded(mesh81, n_articles=90)
    assert n_compiled > 1
    step = eng._get_sharded_fused_step(mesh81)
    epi = eng._get_sharded_epilogue(mesh81)
    if not hasattr(step, "_cache_size"):
        pytest.skip("this jax does not expose jit cache introspection")
    sizes = (step._cache_size(), epi._cache_size())
    rng = np.random.RandomState(17)
    docs = _corpus(rng, 90)
    rep = eng.dedup_reps_sharded(docs, mesh81)
    assert rep.shape == (90,)
    assert (step._cache_size(), epi._cache_size()) == sizes, (
        "a corpus compiled outside the prewarmed set"
    )


# -- band-key fan-out into the index plane -------------------------------------


def test_dedup_against_index_sharded_keys_match_single_device(tmp_path, mesh42):
    """``dedup_against_index(mesh=...)`` computes its wide band keys on
    the sharded packed plane — attributions must be byte-identical to the
    single-device path across a two-batch stream (cross-batch dups land
    on restart-stable doc ids either way)."""
    from advanced_scrapper_tpu.index import PersistentIndex

    rng = np.random.RandomState(19)
    half_a = _corpus(rng, 48)
    half_b = _corpus(rng, 48) + half_a[:8]  # cross-batch dups

    def run(d, mesh):
        eng = NearDupEngine(DedupConfig())
        idx = PersistentIndex(str(tmp_path / d))
        try:
            ids_a = np.arange(0, len(half_a), dtype=np.uint64)
            ids_b = np.arange(1000, 1000 + len(half_b), dtype=np.uint64)
            out_a = eng.dedup_against_index(half_a, idx, ids_a, mesh=mesh)
            out_b = eng.dedup_against_index(half_b, idx, ids_b, mesh=mesh)
        finally:
            idx.close()
        return out_a.tolist(), out_b.tolist()

    assert run("sharded", mesh42) == run("single", None)


def test_dedup_against_index_sharded_through_fleet(tmp_path, mesh81):
    """The full ISSUE 13 merge plane: sharded-device band keys fanned out
    per INDEX shard through a live 2-shard loopback ShardedIndexClient —
    attributions byte-equal to the single-node oracle (the ring fan-out
    and the device-mesh shard count are independent by construction)."""
    from advanced_scrapper_tpu.index import PersistentIndex
    from advanced_scrapper_tpu.index.fleet import ShardedIndexClient
    from advanced_scrapper_tpu.index.remote import IndexShardServer

    rng = np.random.RandomState(23)
    half_a = _corpus(rng, 40)
    half_b = _corpus(rng, 40) + half_a[:6]
    ids_a = np.arange(0, len(half_a), dtype=np.uint64)
    ids_b = np.arange(500, 500 + len(half_b), dtype=np.uint64)

    # single-node oracle, single-device keys
    eng = NearDupEngine(DedupConfig())
    oracle = PersistentIndex(str(tmp_path / "oracle"))
    try:
        want_a = eng.dedup_against_index(half_a, oracle, ids_a)
        want_b = eng.dedup_against_index(half_b, oracle, ids_b)
    finally:
        oracle.close()

    servers = [
        IndexShardServer(
            str(tmp_path / f"s{s}"), spaces=("bands",), name=f"s{s}"
        ).start()
        for s in range(2)
    ]
    client = None
    try:
        client = ShardedIndexClient(
            ";".join(f"127.0.0.1:{srv.port}" for srv in servers),
            space="bands",
            spill_dir=str(tmp_path / "spill"),
            timeout=30.0,
        )
        got_a = eng.dedup_against_index(half_a, client, ids_a, mesh=mesh81)
        got_b = eng.dedup_against_index(half_b, client, ids_b, mesh=mesh81)
    finally:
        if client is not None:
            client.close()
        for srv in servers:
            srv.stop()
    assert got_a.tolist() == want_a.tolist()
    assert got_b.tolist() == want_b.tolist()


# -- step cache ----------------------------------------------------------------


def test_sharded_step_cache_reused_across_corpora(mesh81):
    """Same mesh + same article bucket ⇒ the compiled step/epilogue cache
    gains no new entries on the second corpus (the test_encode_parity
    cache contract, restated for the packed plane)."""
    rng = np.random.RandomState(29)
    docs = _corpus(rng, 80)
    eng = NearDupEngine(DedupConfig())
    eng.dedup_reps_sharded(docs, mesh81)
    n_entries = len(eng._sharded_steps)
    eng.dedup_reps_sharded(docs[::-1], mesh81)
    assert len(eng._sharded_steps) == n_entries
