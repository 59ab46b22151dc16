"""The pod-scale sharded plane against a plain reference of the LSH
resolution it runs (``cpu.oracle.oracle_lsh_reps``): every representative
equal on a 4x1 mesh, on a 1x1 mesh and on the single-device async path;
a shard-local resolve (no cross-chip ``pmin``) differs; the padded-tile
counter, the plane's spans and clocks, and its kernel scopes.

Signatures come from the host twin of the device hash family
(``device_family_signatures``), so the comparison covers the tile step,
the cross-shard combine and the resolution at once.
"""

from __future__ import annotations

import glob
import os

import numpy as np
import pytest

from advanced_scrapper_tpu.config import DedupConfig
from advanced_scrapper_tpu.core.mesh import build_mesh
from advanced_scrapper_tpu.cpu.oracle import (
    build_certification_corpus,
    device_family_signatures,
    oracle_lsh_reps,
)
from advanced_scrapper_tpu.obs import stages, telemetry
from advanced_scrapper_tpu.pipeline.dedup import NearDupEngine

#: the estimator-only configuration the sharded plane runs; 64-row tiles
#: at the 4096-byte width, so a 100 kB article's blocks fill several tiles
#: and the tiles of one article land on different shards
CFG = DedupConfig(rerank=False, exact_verify_band=0.0, batch_size=64)


def _corpus(seed: int, n_bases: int = 128) -> list[bytes]:
    """Ragged articles (100 B to 20 kB, twelve of 100 kB), each base with
    two planted mutants (40% across the Jaccard knee 0.62–0.80) and an
    unrelated article, plus exact copies of a tenth of them, shuffled."""
    rng = np.random.RandomState(seed)
    docs = build_certification_corpus(rng, n_bases)
    docs += [docs[i] for i in rng.randint(0, len(docs), size=len(docs) // 10)]
    return [docs[i] for i in rng.permutation(len(docs))]


def _reference(eng: NearDupEngine, docs: list[bytes]) -> np.ndarray:
    sigs = device_family_signatures(docs, eng.params)
    valid = np.fromiter(map(len, docs), np.int64) >= eng.params.shingle_k
    return oracle_lsh_reps(
        sigs, eng.params, cand_subbands=eng.cfg.cand_subbands,
        threshold=eng.cfg.sim_threshold, valid=valid,
    )


@pytest.fixture(scope="module")
def eng():
    return NearDupEngine(CFG)


@pytest.fixture(scope="module")
def mesh41(devices8):
    return build_mesh(4, 1, devices=devices8[:4])


@pytest.fixture(scope="module")
def mesh11(devices8):
    return build_mesh(1, 1, devices=devices8[:1])


@pytest.fixture(scope="module")
def corpus():
    docs = _corpus(25)
    return docs, _reference(NearDupEngine(CFG), docs)


def test_corpus_spans_shards_and_plants_merges(eng, corpus):
    """The premise of the equality tests: merges to find, and articles
    whose blocks are folded on more than one shard of a 4x1 mesh."""
    docs, want = corpus
    assert len(docs) >= 512
    assert (want != np.arange(len(docs))).sum() > 100
    shards_of: dict[int, set] = {}
    groups = eng._sharded_tile_groups(eng._host_tiles(docs), 4, shards=[])
    for _shape, batch in groups:
        for s, (_t, lens, owners) in enumerate(batch):
            for o in owners[lens > 0].tolist():
                shards_of.setdefault(o, set()).add(s)
    assert max(map(len, shards_of.values())) >= 2


@pytest.mark.parametrize("seed", [25, 2**31 + 3])
def test_sharded_4x1_equals_reference(eng, mesh41, corpus, seed):
    docs, want = corpus if seed == 25 else (_corpus(seed), None)
    if want is None:
        want = _reference(eng, docs)
    got = eng.dedup_reps_sharded(docs, mesh41)
    assert got.shape == want.shape
    assert np.array_equal(got, want), np.flatnonzero(got != want)[:10]


def test_sharded_resolve_stops_early_and_stays_one_dispatch(eng, mesh41, corpus):
    """The replicated resolve stops at its fixpoint: every representative
    still equals the reference, and every shard still costs tiles + 1
    puts and tiles + 1 dispatches, the resolve one of them."""
    docs, want = corpus
    dev0 = stages.sharded_device_counters()
    got = eng.dedup_reps_sharded(docs, mesh41)
    dev1 = stages.sharded_device_counters()
    assert np.array_equal(got, want), np.flatnonzero(got != want)[:10]
    tiles = eng.last_tiles
    for s in range(4):
        d = {k: v - dev0.get(str(s), {}).get(k, 0.0) for k, v in dev1[str(s)].items()}
        assert d["device_puts"] == tiles + 1, (s, d, tiles)
        assert d["device_dispatches"] == tiles + 1, (s, d, tiles)


def test_sharded_1x1_equals_reference(eng, mesh11, corpus):
    docs, want = corpus
    assert np.array_equal(eng.dedup_reps_sharded(docs, mesh11), want)


def test_async_equals_reference(eng, corpus):
    docs, want = corpus
    got = np.asarray(eng.dedup_reps_async(docs))[: len(docs)]
    assert np.array_equal(got, want)


def test_shard_local_resolve_differs_from_reference(eng, mesh41, corpus):
    """Shard 0's partial accumulator resolved with the same epilogue math
    and no cross-chip combine: the reference tells it apart."""
    from advanced_scrapper_tpu.ops.lsh import fused_resolve_epilogue
    from advanced_scrapper_tpu.pipeline.dedup import _jump_rounds

    docs, want = corpus
    running, n_bucket, _ = eng._accumulate_device_sharded(docs, mesh41)
    valid = np.zeros((n_bucket,), bool)
    valid[: len(docs)] = np.fromiter(map(len, docs), np.int64) >= 5
    local = fused_resolve_epilogue(
        np.asarray(running)[0], valid, np.asarray(eng.params.band_salt),
        eng._fine_salt(), CFG.sim_threshold, 0.0, densify_oph=False,
        num_coarse=CFG.num_bands, jump_rounds=_jump_rounds(n_bucket),
        use_fine_margin=False,
    )
    local = np.asarray(local)[: len(docs)]
    assert (local != want).sum() > len(docs) // 10


def test_reference_keeps_invalid_articles_apart_as_the_device_does(eng):
    """Articles shorter than k share the all-``U32_MAX`` signature and
    every key, yet merge with nothing; equal valid signatures merge onto
    the smallest index; one lane short of the bar stays apart."""
    from advanced_scrapper_tpu.ops.lsh import fused_resolve_epilogue

    rng = np.random.RandomState(9)
    base = rng.randint(0, 2**32, size=128, dtype=np.uint64).astype(np.uint32)
    far = base.copy()
    far[:39] ^= np.uint32(2)  # 89 of 128 lanes agree, with base and near: 0.695
    near = base.copy()
    near[:38] ^= np.uint32(1)  # 90 of 128: 0.703
    sigs = np.stack([np.full(128, 2**32 - 1, np.uint32)] * 3 + [base, base, far, near])
    valid = np.array([False] * 3 + [True] * 4)
    want = [0, 1, 2, 3, 3, 5, 3]
    reps = oracle_lsh_reps(sigs, eng.params, cand_subbands=32, threshold=0.7, valid=valid)
    assert reps.tolist() == want
    dev = fused_resolve_epilogue(
        sigs, valid, np.asarray(eng.params.band_salt), eng._fine_salt(), 0.7, 0.0,
        densify_oph=False, num_coarse=16, jump_rounds=3, use_fine_margin=False,
    )
    assert np.asarray(dev).tolist() == want


def test_pad_tile_counter_counts_a_leftover_group(eng, mesh41):
    """A corpus whose tile count is no multiple of 4: each shard counts
    one slot per group, and the shards past a leftover group's real
    tiles count its padding tiles."""
    docs = _corpus(7, n_bases=41)
    shapes: dict[tuple, int] = {}
    for tok, _l, _o in eng._host_tiles(docs):
        shapes[tok.shape] = shapes.get(tok.shape, 0) + 1
    assert sum(shapes.values()) % 4
    groups = sum(-(-c // 4) for c in shapes.values())
    pads = [sum(1 for c in shapes.values() if c % 4 and s >= c % 4) for s in range(4)]
    before = stages.sharded_tile_counters()
    eng.dedup_reps_sharded(docs, mesh41)
    after = stages.sharded_tile_counters()
    for s in range(4):
        d = {k: v - before.get(str(s), {}).get(k, 0.0) for k, v in after[str(s)].items()}
        assert d == {"tile_slots": groups, "pad_tiles": pads[s]}, (s, d)
    assert sum(pads) == 4 * groups - sum(shapes.values())


def test_sharded_spans_on_profiler_trace_and_clocks_once_per_corpus(
    eng, mesh41, tmp_path
):
    import jax
    from jax.profiler import ProfileData

    docs = _corpus(11, n_bases=32)
    eng.dedup_reps_sharded(docs, mesh41)  # compiles outside the trace
    totals = lambda: {h.labels["stage"]: h.count for h in telemetry.stage_histograms()}  # noqa: E731
    before = totals()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.dedup_reps_sharded(docs, mesh41)
    finally:
        jax.profiler.stop_trace()
    after = totals()
    for stage in ("sharded_tiles", "sharded_epilogue"):
        assert after[stage] - before.get(stage, 0) == 1, stage
    (xplane,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    spans: dict[str, list] = {}
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    spans.setdefault(e.name, []).append((e.start_ns, e.start_ns + e.duration_ns))
    (tiles,) = spans["astpu.dedup.sharded.tiles"]
    (epi,) = spans["astpu.dedup.sharded.epilogue"]
    (resolve,) = spans["astpu.dedup.resolve"]
    assert tiles[1] <= epi[0] and resolve[0] <= epi[0] <= epi[1] <= resolve[1]


def _tile_lowering(mesh):
    import jax.numpy as jnp

    from advanced_scrapper_tpu.ops.pack import packed_nbytes
    from advanced_scrapper_tpu.parallel.sharded_packed import make_sharded_fused_tile_step

    step = make_sharded_fused_tile_step(mesh, NearDupEngine(CFG).params, "scan")
    running = jnp.zeros((4, 64, 128), jnp.uint32)
    packed = jnp.zeros((4, packed_nbytes(64, 1024)), jnp.uint8)
    return step.lower(running, packed, rows=64, width=1024, num_articles=64)


def _resolve_lowering(mesh):
    import jax.numpy as jnp

    from advanced_scrapper_tpu.parallel.sharded_packed import make_sharded_resolve_epilogue

    eng = NearDupEngine(CFG)
    epi = make_sharded_resolve_epilogue(
        mesh, eng.params, threshold=0.7, fine_margin=0.0,
        fine_salt=eng._fine_salt(), backend="scan",
    )
    return epi.lower(jnp.zeros((4, 64, 128), jnp.uint32), jnp.ones((64,), bool),
                     jump_rounds=6)


def _keys_lowering(mesh):
    import jax.numpy as jnp

    from advanced_scrapper_tpu.parallel.sharded_packed import make_sharded_keys_epilogue

    keys = make_sharded_keys_epilogue(mesh, NearDupEngine(CFG).params, "scan")
    return keys.lower(jnp.zeros((4, 64, 128), jnp.uint32))


@pytest.mark.parametrize(
    "lower, scope",
    [(_tile_lowering, "sharded.tile"), (_resolve_lowering, "sharded.pmin"),
     (_keys_lowering, "sharded.pmin")],
    ids=["tile", "resolve_pmin", "keys_pmin"],
)
def test_sharded_programs_carry_named_scopes(mesh41, lower, scope):
    assert scope in lower(mesh41).as_text(debug_info=True)
