"""Matcher on the single-dispatch plane (ISSUE 10): packed screen tiles,
the fused screen+Myers-bound step, the pipelined screen executor, and the
always-on device-traffic counters that gate the launch-count win
numerically — mirroring ``test_dispatch.py``'s certification of the dedup
half of the ledger.

Certification strategy: the screen is pure performance work, so matcher
OUTPUT must equal the unscreened exact host scan (``use_screen=False``,
the standing golden) across screen-only, forced refine, overlong-fallback
and pooled/inline verify modes.
"""

from __future__ import annotations

import json

import numpy as np
import pandas as pd
import pytest

from advanced_scrapper_tpu.obs import stages, telemetry
from advanced_scrapper_tpu.pipeline.matcher import (
    EntityIndex,
    _screen_rows_options,
    _screen_tile_rows,
    make_verify_pool,
    match_chunk,
    prewarm_screen,
    process_json_data,
)


def _entities(n: int = 12) -> list[dict]:
    return [
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
        for i in range(n)
    ]


def _index(n: int = 12) -> EntityIndex:
    return EntityIndex(process_json_data(_entities(n)))


def _chunk(n_articles: int, seed: int = 13, pad_every: int = 0) -> pd.DataFrame:
    """Synthetic article frame: filler prose, 25% planted entity mentions,
    mixed lengths (``pad_every`` > 0 inflates every k-th row into a bigger
    width bucket so the chunk spans several compiled tile shapes)."""
    rng = np.random.RandomState(seed)
    vocab = [
        "".join(chr(97 + c) for c in rng.randint(0, 26, size=rng.randint(3, 10)))
        for _ in range(500)
    ]
    rows = []
    for i in range(n_articles):
        words = [vocab[w] for w in rng.randint(0, len(vocab), size=60)]
        if i % 4 == 0:
            e = int(rng.randint(12))
            words[10:10] = [f"Company{e}", "Corp.", "said", "Ceo", f"Person{e}"]
        body = " ".join(words)
        if pad_every and i % pad_every == 0:
            body += " pad" * (500 * (1 + i % 3))
        rows.append(
            {
                "article_text": body,
                "title": "TK01 leads markets" if i % 5 == 0 else "daily wrap",
                "date_time": "2020-06-01T00:00:00Z",
                "url": f"https://x/{i}.html",
                "source": "s",
                "source_url": "su",
            }
        )
    return pd.DataFrame(rows)


def _norm(res):
    return sorted(
        (t, json.dumps(m, sort_keys=True), r["url"]) for t, m, r in res
    )


# -- the launch-count gate (the acceptance criterion) ------------------------


def test_per_tile_traffic_one_put_one_dispatch_vs_legacy():
    """Exactly 1 put + 1 dispatch per screen tile, nothing else per
    chunk — asserted via the ALWAYS-ON counters, so the contract is a
    measured number, not prose; the unscreened host scan moves no device
    traffic at all."""
    idx = _index()
    df = _chunk(96, pad_every=7)

    probe: list[dict] = []
    idx.dispatch_probe = probe.append
    d0 = stages.device_counters()
    packed = match_chunk(df, idx)
    d1 = stages.device_counters()
    idx.dispatch_probe = None
    unscreened = match_chunk(df, idx, use_screen=False)
    d2 = stages.device_counters()

    tiles = len(probe)
    assert tiles > 1  # pad_every spans several width buckets
    puts_p = d1["device_puts"] - d0["device_puts"]
    disp_p = d1["device_dispatches"] - d0["device_dispatches"]
    bytes_p = d1["h2d_bytes"] - d0["h2d_bytes"]
    # the contract: tiles × (1 + 1), and the counted bytes are exactly
    # the packed buffers the probe saw
    assert puts_p == tiles, (puts_p, tiles)
    assert disp_p == tiles, (disp_p, tiles)
    assert bytes_p == sum(t["h2d_bytes"] for t in probe)
    assert d2["device_puts"] == d1["device_puts"]
    assert d2["device_dispatches"] == d1["device_dispatches"]
    # and the outputs are byte-identical
    assert _norm(packed) == _norm(unscreened)
    assert len(packed) >= len(df) // 8


def test_probe_reports_tile_geometry():
    idx = _index()
    probe: list[dict] = []
    idx.dispatch_probe = probe.append
    match_chunk(_chunk(40), idx)
    idx.dispatch_probe = None
    assert probe
    for t in probe:
        assert t["rows"] >= 16 and t["width"] >= 1024
        assert t["h2d_bytes"] == t["rows"] * (t["width"] + 20)  # 5 planes
        assert "put_ms" in t and "dispatch_ms" in t


# -- byte-identical output across modes --------------------------------------


def test_packed_parity_screen_only_and_unscreened():
    idx = _index()
    df = _chunk(64, pad_every=9)
    want = _norm(match_chunk(df, idx, use_screen=False))
    assert _norm(match_chunk(df, idx)) == want
    assert _norm(match_chunk(df, idx, use_refine=False)) == want
    assert len(want) >= 8


def test_packed_parity_forced_refine():
    """Forced refine: the fused screen+bound step must produce the
    unscreened scan's matches — its prune set is sound, so it may not
    change a decision."""
    idx = _index()
    df = _chunk(48, seed=7)
    want = _norm(match_chunk(df, idx, use_screen=False))
    got_p = _norm(match_chunk(df, idx, use_refine=True))
    got_s = _norm(match_chunk(df, idx, use_refine=False))
    assert got_p == got_s == want


def test_packed_parity_pooled_verify():
    idx = _index()
    df = _chunk(48, seed=29, pad_every=11)
    pool = make_verify_pool(idx, workers=2)
    if pool is None:
        pytest.skip("host refuses worker processes")
    try:
        got_p = _norm(match_chunk(df, idx, pool=pool))
        got_u = _norm(match_chunk(df, idx, use_screen=False, pool=pool))
    finally:
        pool.shutdown()
    assert got_p == got_u == _norm(match_chunk(df, idx, use_screen=False))


def test_packed_parity_window_and_put_worker_knobs():
    """Any (put_workers, dispatch_window) combination is byte-identical —
    tiles carry their row owners, so out-of-order staging from a deep
    window must never show in the output."""
    idx = _index()
    df = _chunk(72, seed=3, pad_every=5)
    want = _norm(match_chunk(df, idx, use_screen=False))
    for pw, win in ((1, 1), (3, 1), (4, 6)):
        got = match_chunk(
            df, idx, screen_put_workers=pw, dispatch_window=win
        )
        assert _norm(got) == want, (pw, win)


# -- overlong-article fallback (previously untested) --------------------------


def _overlong_frame() -> pd.DataFrame:
    long_body = (
        "Company3 Corp. said Ceo Person3 will expand. " + "filler words " * 400
    )
    assert len(long_body) > 4096
    rows = [
        {  # overlong: must fall back to the full host scan
            "article_text": long_body,
            "title": "TK03 overlong",
            "date_time": "2020-06-01T00:00:00Z",
            "url": "https://x/long.html",
            "source": "s",
            "source_url": "su",
        },
        {  # normal screened row rides a tile in the same chunk
            "article_text": "Company1 Corp. said Ceo Person1 spoke today.",
            "title": "daily wrap",
            "date_time": "2020-06-01T00:00:00Z",
            "url": "https://x/short.html",
            "source": "s",
            "source_url": "su",
        },
        {  # overlong WITHOUT any entity mention: screen may not invent one
            "article_text": "nothing relevant here " * 300,
            "title": "daily wrap",
            "date_time": "2020-06-01T00:00:00Z",
            "url": "https://x/noise.html",
            "source": "s",
            "source_url": "su",
        },
    ]
    return pd.DataFrame(rows)


@pytest.mark.parametrize("use_refine", [False, True])
def test_overlong_fallback_parity_both_transports(use_refine):
    """Rows above ``screen_block`` must fall back to the full host scan —
    decisions identical to the unscreened reference — in both screen
    modes, and must never ship an overlong row's bytes to the device."""
    idx = _index()
    df = _overlong_frame()
    block = 4096
    want = _norm(match_chunk(df, idx, use_screen=False))
    assert any("long.html" in u for _, _, u in want)  # overlong row matches

    probe: list[dict] = []
    idx.dispatch_probe = probe.append
    got_p = match_chunk(
        df, idx, screen_block=block, use_refine=use_refine
    )
    idx.dispatch_probe = None
    assert _norm(got_p) == want
    # only the one short row entered a tile: 16 bucketed rows, 1024 wide
    assert sum(t["rows"] for t in probe) == 16
    assert all(t["width"] == 1024 for t in probe)


def test_overlong_counter_counts_on_both_transports():
    idx = _index()
    df = _overlong_frame()

    def overlong_total() -> float:
        return sum(
            c.value
            for c in telemetry.REGISTRY.find("astpu_matcher_overlong_total")
        )

    base = overlong_total()
    match_chunk(df, idx, screen_block=4096)
    after_screen = overlong_total()
    assert after_screen - base == 2  # the two >4096 rows
    match_chunk(df, idx, screen_block=4096, use_refine=True)
    assert overlong_total() - after_screen == 2
    # the unscreened scan routes nothing: it never counts an overlong row
    match_chunk(df, idx, use_screen=False, screen_block=4096)
    assert overlong_total() - after_screen == 2


# -- the fused kernel's parts -------------------------------------------------


def test_semiglobal_shared_matches_pairwise_kernel():
    """``semiglobal_dist_shared`` (the fused step's all-pairs bound, no
    B×K text materialisation) must equal the per-pair kernel column for
    column — including empty text and tlen-truncated rows."""
    import jax.numpy as jnp

    from advanced_scrapper_tpu.ops.editdist import (
        build_pattern_masks,
        semiglobal_dist,
        semiglobal_dist_shared,
    )

    rng = np.random.RandomState(5)
    pats = [
        bytes(rng.randint(97, 123, size=rng.randint(1, 33), dtype=np.uint8))
        for _ in range(9)
    ]
    masks, lens, _ok = build_pattern_masks(pats)
    B, L = 6, 700
    text = rng.randint(97, 123, size=(B, L)).astype(np.uint8)
    tlens = np.array([0, 1, 31, 500, 699, 700], np.int32)
    got = np.asarray(
        semiglobal_dist_shared(
            jnp.asarray(masks), jnp.asarray(lens), jnp.asarray(text),
            jnp.asarray(tlens),
        )
    )
    assert got.shape == (B, len(pats))
    for k in range(len(pats)):
        want = np.asarray(
            semiglobal_dist(
                jnp.asarray(np.repeat(masks[k][None], B, axis=0)),
                jnp.asarray(np.full((B,), lens[k], np.int32)),
                jnp.asarray(text),
                jnp.asarray(tlens),
            )
        )
        assert (got[:, k] == want).all(), k


def test_pack_tile_planes_roundtrip():
    """pack_tile_planes → unpack_tile_planes is the identity on (tokens,
    *planes) at the matcher's 5-plane layout, including negative owners
    (tail padding) and values past one byte."""
    import jax.numpy as jnp

    from advanced_scrapper_tpu.ops.pack import (
        pack_tile_planes,
        packed_nbytes,
        unpack_tile_planes,
    )

    rng = np.random.RandomState(29)
    rows, width = 32, 96
    tok = rng.randint(0, 256, size=(rows, width)).astype(np.uint8)
    planes = [
        rng.randint(-(1 << 20), 1 << 22, size=rows).astype(np.int32)
        for _ in range(5)
    ]
    buf = pack_tile_planes(tok, *planes)
    assert buf.dtype == np.uint8
    assert buf.shape == (packed_nbytes(rows, width, 5),) == (rows * (width + 20),)
    t, got = unpack_tile_planes(jnp.asarray(buf), rows, width, 5)
    assert (np.asarray(t) == tok).all()
    for want, have in zip(planes, got):
        assert (np.asarray(have) == want).all()


def test_fused_mode_aliases_screen_only_without_candidates():
    """An index with no refine-eligible names must not compile a second,
    identical kernel for the fused mode — the True step IS the False
    step (and prewarm counts its shapes once)."""
    from advanced_scrapper_tpu.pipeline.matcher import _screen_steps

    idx = EntityIndex(
        {"T0": {"aliases": {"IBM": (None, None), "HPQ": (None, None)}}}
    )
    assert all(e.is_exact_upper for e in idx.entries)
    assert _screen_steps(idx, True) is _screen_steps(idx, False)
    n_both = prewarm_screen(
        idx, use_refine=None, screen_block=1024, tile_bytes=1 << 14
    )
    assert n_both == len(_screen_rows_options(16))  # one mode's shapes only


def test_many_tiles_bounded_readback_parity():
    """A chunk spanning many more tiles than the in-flight lag (tiny tile
    budget, shallow window) must drain trailing masks mid-loop and still
    scatter every row correctly."""
    idx = _index()
    df = _chunk(96, seed=17)
    probe: list[dict] = []
    idx.dispatch_probe = probe.append
    got = match_chunk(
        df,
        idx,
        screen_tile_bytes=1 << 14,  # 16-row tiles at width 1024
        dispatch_window=1,
        screen_put_workers=1,
    )
    idx.dispatch_probe = None
    assert len(probe) >= 6  # well past lag = window + workers + 1 = 3
    assert _norm(got) == _norm(match_chunk(df, idx, use_screen=False))


# -- prewarm: the shape set is shared with the chunker ------------------------


def test_screen_tile_rows_shared_derivation():
    assert _screen_tile_rows(1 << 21, 1024) == 2048
    assert _screen_tile_rows(1 << 21, 1 << 16) == 32
    assert _screen_tile_rows(1 << 10, 1 << 16) == 16      # floor
    assert _screen_tile_rows(1 << 30, 64) == 4096          # ceiling
    assert _screen_rows_options(128) == [16, 32, 64, 128]
    assert _screen_rows_options(16) == [16]


def test_prewarm_compiles_the_chunker_shape_set():
    """prewarm_screen must compile exactly the (width × rows) variants
    the tile chunker can emit — then a real chunk adds no new shapes
    (observed through the jit cache of the screen step)."""
    idx = _index(4)
    block, tile_bytes = 2048, 1 << 15
    n = prewarm_screen(
        idx, use_refine=False, screen_block=block, tile_bytes=tile_bytes
    )
    # widths {1024, 2048} × rows options of bs=32/16 → {16,32} / {16}
    assert n == len(_screen_rows_options(32)) + len(_screen_rows_options(16))
    step = idx._packed_steps[False]
    if not hasattr(step, "_cache_size"):
        pytest.skip("jit cache introspection unavailable on this jax")
    sizes = step._cache_size()
    df = _chunk(40, pad_every=6)
    out = match_chunk(
        df, idx, screen_block=block, screen_tile_bytes=tile_bytes
    )
    assert step._cache_size() == sizes, "chunk compiled outside the prewarmed set"
    assert _norm(out) == _norm(match_chunk(df, idx, use_screen=False))
