"""Entity→article matcher tests: reference parsing semantics, match rules,
and the screened-vs-unscreened byte-identical golden."""

import json
import os

import numpy as np
import pandas as pd
import pytest

from advanced_scrapper_tpu.config import MatchConfig
from advanced_scrapper_tpu.cpu import native
from advanced_scrapper_tpu.pipeline.matcher import (
    EntityIndex,
    extract_time_periods,
    is_within_period,
    make_verify_pool,
    match_article,
    match_chunk,
    match_chunk_async,
    process_json_data,
    read_info_dir,
    run_matcher,
)
from dateutil import parser as dateparser


def _entity(ticker="AAPL", **over):
    base = {
        "id_label": "Apple Inc.",
        "ticker": ticker,
        "country": ["United States"],
        "industry": ["technology"],
        "aliases": ["AAPL", "Apple"],
        "products": ["iPhone", "iPad Pro"],
        "subsidiaries": ["Beats Electronics (Start: 2014-08-01T00:00:00Z)"],
        "owned_entities": [],
        "ceos": [
            "Tim Cook (Start: 2011-08-24T00:00:00Z)",
            "Steve Jobs (Start: 1997-09-16T00:00:00Z) (End: 2011-08-24T00:00:00Z)",
        ],
        "board_members": [],
    }
    base.update(over)
    return base


def test_extract_time_periods_parsing():
    p = extract_time_periods(
        ["Tim Cook (Start: 2011-08-24T00:00:00Z)",
         "Steve Jobs (Start: 1997-09-16T00:00:00Z) (End: 2011-08-24T00:00:00Z)",
         "No Dates Co"]
    )
    assert p["Tim Cook"][0].year == 2011 and p["Tim Cook"][1] is None
    assert p["Steve Jobs"][1].year == 2011
    assert p["No Dates Co"] == (None, None)
    # string input treated as single name (ref :42-43)
    assert "Apple Inc." in extract_time_periods("Apple Inc.")


def test_is_within_period_rules():
    d = dateparser.parse("2015-01-01T00:00:00Z")
    s = dateparser.parse("2011-08-24")  # naive → promoted to UTC
    e = dateparser.parse("2020-01-01")
    assert is_within_period(d, s, e)
    assert is_within_period(d, s, None)
    assert not is_within_period(d, None, dateparser.parse("2012-01-01"))
    assert is_within_period(d, None, None)
    assert not is_within_period(None, None, None)  # dateless article


def test_process_json_data_us_filter():
    us, de = _entity(), _entity(ticker="SAP", country=["Germany"])
    # two companies: only US kept
    assert set(process_json_data([us, de])) == {"AAPL"}
    # single company: kept regardless of country
    assert set(process_json_data([de])) == {"SAP"}


def test_entity_index_name_classification():
    idx = EntityIndex(process_json_data([_entity()]))
    names = {(e.name, e.is_exact_upper) for e in idx.entries}
    assert ("AAPL", True) in names            # ALL-CAPS → exact path
    assert ("Tim Cook", False) in names       # mixed case → fuzzy path
    assert ("iPhone", False) in names         # not pure-lower-alpha (capital P)
    # pure lowercase alphabetic names are dropped (ref :174)
    idx2 = EntityIndex(
        process_json_data([_entity(products=["technology stuff", "iphone"])])
    )
    kept = {e.name for e in idx2.entries}
    assert "technology stuff" not in kept and "iphone" not in kept


ARTICLE = (
    "Apple Inc. announced today that Tim Cook will present the new iPhone. "
    "Shares of AAPL rose 3%. Beats Electronics was mentioned too."
)
TITLE = "AAPL leads markets as Tim Cook speaks"


def _index():
    return EntityIndex(process_json_data([_entity()]))


def test_match_article_exact_and_fuzzy_paths():
    adate = dateparser.parse("2020-06-01T00:00:00Z")
    m = match_article(ARTICLE, TITLE, adate, _index())
    assert "AAPL" in m
    text_m, title_m = m["AAPL"]["text"], m["AAPL"]["title"]
    # exact word-boundary positions
    assert text_m["AAPL"] == [ARTICLE.index("AAPL")]
    assert title_m["AAPL"] == [0]
    # fuzzy names present with positions
    assert text_m["Tim Cook"] == [ARTICLE.index("Tim Cook")]
    assert "iPhone" in text_m
    # period gating: Steve Jobs ended 2011 → absent in a 2020 article
    assert "Steve Jobs" not in text_m


def test_match_article_period_gate_allows_former_ceo_in_window():
    adate = dateparser.parse("2005-06-01T00:00:00Z")
    m = match_article("Steve Jobs unveiled something.", "", adate, _index())
    assert "Steve Jobs" in m["AAPL"]["text"]
    assert "Tim Cook" not in m["AAPL"]["text"]  # started 2011


def test_match_article_dateless_article_matches_nothing():
    # ref :18-20: article_date None → is_within_period False for EVERY name,
    # so dateless articles can never match anything
    assert match_article(ARTICLE, TITLE, None, _index()) == {}


def test_screened_equals_unscreened_golden():
    """The TPU screen must never change match output (no false negatives)."""
    rng = np.random.RandomState(0)
    fillers = [
        "Markets were mixed today as investors weighed inflation data.",
        "The quarterly report highlighted strong services growth.",
        "Nothing related to any entity appears in this filler text.",
    ]
    rows = []
    for i in range(40):
        body = fillers[i % 3]
        if i % 5 == 0:
            body += " " + ARTICLE
        if i % 7 == 0:
            body += " Beats Electronics expansion continues."
        rows.append(
            {
                "article_text": body,
                "title": TITLE if i % 4 == 0 else "daily wrap",
                "date_time": "2020-06-01T00:00:00Z",
                "url": f"https://x/{i}.html",
                "source": "s",
                "source_url": "su",
            }
        )
    df = pd.DataFrame(rows)
    idx = _index()
    screened = match_chunk(df, idx, use_screen=True, screen_tile_bytes=1 << 14)
    unscreened = match_chunk(df, idx, use_screen=False)

    def norm(res):
        return sorted(
            (t, json.dumps(m, sort_keys=True), r["url"]) for t, m, r in res
        )

    assert norm(screened) == norm(unscreened)
    assert len(screened) >= 8  # planted matches found


def test_run_matcher_end_to_end(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    os.makedirs("info_dir")
    with open("info_dir/AAPL_info.json", "w") as f:
        json.dump([_entity()], f)
    rows = [
        {
            "article_text": ARTICLE,
            "title": TITLE,
            "date_time": "2020-06-01T12:00:00Z",
            "url": "https://x/1.html",
            "source": "yahoo",
            "source_url": "https://y",
        },
        {
            "article_text": "Unrelated piece about weather.",
            "title": "weather",
            "date_time": "2020-06-02T12:00:00Z",
            "url": "https://x/2.html",
            "source": "yahoo",
            "source_url": "https://y",
        },
        {   # earlier article, to verify final time sort
            "article_text": "AAPL had a strong day.",
            "title": "markets",
            "date_time": "2019-01-01T00:00:00Z",
            "url": "https://x/0.html",
            "source": "yahoo",
            "source_url": "https://y",
        },
    ]
    pd.DataFrame(rows).to_csv("articles.csv", index=False)
    cfg = MatchConfig(source_name="yahoo", info_dir="info_dir", chunk_size=2)
    rc = run_matcher(cfg, articles_csv="articles.csv")
    assert rc == 0
    out = pd.read_csv("yahoo_ticker_matched_articles/AAPL_match.csv")
    assert len(out) == 2
    # sorted ascending by time_unix (the 2019 article first)
    assert out["url"].tolist() == ["https://x/0.html", "https://x/1.html"]
    matches = json.loads(out.iloc[1]["text_matches"])
    assert "AAPL" in matches and "Tim Cook" in matches


def test_gbk_encoding_fallback(tmp_path):
    payload = [_entity(ticker="GBK1", id_label="中文公司")]
    raw = json.dumps(payload, ensure_ascii=False).encode("gbk")
    with open(tmp_path / "gbk_info.json", "wb") as f:
        f.write(raw)
    data = read_info_dir(str(tmp_path))
    assert "GBK1" in data


def test_native_backend_loaded():
    native.partial_ratio("warm", "up")
    assert native.BACKEND in ("native", "python")
    assert native.partial_ratio("Tim Cook", ARTICLE) > 95
    assert native.partial_ratio("Timothy Cook", "completely unrelated") < 60


def _mk_index(entities):
    return EntityIndex(process_json_data(entities))


def test_screen_sound_for_short_title_vs_long_name():
    """partial_ratio slides the SHORTER side: a short title inside a long
    name must survive the screen (the unsound bound pruned this)."""
    long_name = "International Business Machines Corporation"
    idx = _mk_index([_entity(ticker="IBM", aliases=[long_name], ceos=[], products=[],
                             subsidiaries=[], id_label="X1")])
    rows = [{
        "article_text": "totally unrelated body text about the weather today",
        "title": "International Business",  # shorter than the name, ratio 100
        "date_time": "2020-06-01T00:00:00Z",
        "url": "https://x/t.html", "source": "s", "source_url": "su",
    }]
    df = pd.DataFrame(rows)
    screened = match_chunk(df, idx, use_screen=True)
    unscreened = match_chunk(df, idx, use_screen=False)
    assert len(unscreened) == 1  # reference records the title match
    assert len(screened) == len(unscreened)


def test_screen_sound_for_truncated_long_fuzzy_name():
    """Names with more grams than max_grams must keep edit tolerance."""
    long_name = "Abcdefgh Ijklmnop Qrstuvwx " * 6 + "Yz Holdings"  # ~170 bytes
    assert not long_name.isupper()
    idx = _mk_index([_entity(ticker="LONG", aliases=[long_name], ceos=[],
                             products=[], subsidiaries=[], id_label="X2")])
    body = "intro text. " + long_name[:80] + "Q" + long_name[81:] + " outro."
    rows = [{
        "article_text": body, "title": "wrap",
        "date_time": "2020-06-01T00:00:00Z",
        "url": "https://x/l.html", "source": "s", "source_url": "su",
    }]
    df = pd.DataFrame(rows)
    screened = match_chunk(df, idx, use_screen=True)
    unscreened = match_chunk(df, idx, use_screen=False)
    assert len(screened) == len(unscreened)


def test_screen_sound_for_nondefault_threshold():
    """Screen bounds must follow the configured threshold, not a fixed 95."""
    name = "Consolidated Widget Partners"
    idx = _mk_index([_entity(ticker="CWP", aliases=[name], ceos=[], products=[],
                             subsidiaries=[], id_label="X3")])
    # heavily edited mention: ratio ~80 — matches at threshold 70, not 95
    mention = "Consodated Wdget Parters"
    rows = [{
        "article_text": f"news about {mention} expanding operations",
        "title": "wrap", "date_time": "2020-06-01T00:00:00Z",
        "url": "https://x/nt.html", "source": "s", "source_url": "su",
    }]
    df = pd.DataFrame(rows)
    screened = match_chunk(df, idx, use_screen=True, threshold=70.0)
    unscreened = match_chunk(df, idx, use_screen=False, threshold=70.0)
    assert len(unscreened) == 1
    assert len(screened) == len(unscreened)


def test_screen_exact_path_prunes_impossible_substrings():
    """ALL-CAPS names longer than both parts can never match → pruned,
    through the packed single-dispatch screen step."""
    import jax.numpy as jnp

    from advanced_scrapper_tpu.ops.match import (
        MASK_SCREEN_KEEP,
        make_screen_step,
        prepare_names,
    )
    from advanced_scrapper_tpu.ops.pack import pack_tile_planes
    from advanced_scrapper_tpu.core.tokenizer import encode_batch
    import numpy as np

    tables = prepare_names(
        [b"VERYLONGTICKERNAME", b"body"], fuzzy=np.array([False, False])
    )
    doc = b"short\nbody"
    tok, ln = encode_batch([doc], block_len=64)
    one = lambda v: np.array([v], np.int32)  # noqa: E731
    packed = pack_tile_planes(tok, ln, one(4), one(5), one(0), one(0))
    step = make_screen_step(tables)
    mask, owners = step(jnp.asarray(packed), 95.0, rows=1, width=64)
    keep = np.asarray(mask) & MASK_SCREEN_KEEP
    assert not keep[0, 0]
    # a name that fits a part and whose grams are all present survives
    assert keep[0, 1]
    assert int(np.asarray(owners)[0]) == 0


def test_verify_pool_output_identical_to_serial():
    """The process fan-out (ref match_keywords.py:231-238) must not change
    output content or order."""
    from advanced_scrapper_tpu.pipeline.matcher import make_verify_pool

    rows = []
    for i in range(25):
        body = "filler text about markets. "
        if i % 3 == 0:
            body += ARTICLE
        rows.append({
            "article_text": body, "title": TITLE if i % 4 == 0 else "wrap",
            "date_time": "2020-06-01T00:00:00Z", "url": f"https://x/{i}.html",
            "source": "s", "source_url": "su",
        })
    df = pd.DataFrame(rows)
    idx = _index()
    serial = match_chunk(df, idx, use_screen=True)
    pool = make_verify_pool(idx, workers=3)
    assert pool is not None
    try:
        pooled = match_chunk(df, idx, use_screen=True, pool=pool)
    finally:
        pool.shutdown()
    as_cmp = lambda res: [
        (t, json.dumps(m, sort_keys=True), r["url"]) for t, m, r in res
    ]
    assert as_cmp(pooled) == as_cmp(serial)
    assert len(serial) >= 8


def test_verify_pool_single_worker_is_none():
    from advanced_scrapper_tpu.pipeline.matcher import make_verify_pool

    assert make_verify_pool(_index(), workers=1) is None


def test_match_chunk_rejects_refine_without_screen():
    df = pd.DataFrame([{
        "article_text": "x", "title": "t",
        "date_time": "2020-06-01T00:00:00Z", "url": "u",
        "source": "s", "source_url": "su",
    }])
    with pytest.raises(ValueError, match="use_refine requires use_screen"):
        match_chunk(df, _index(), use_screen=False, use_refine=True)


def test_match_chunk_async_equals_sync_and_overlaps(tmp_path):
    """match_chunk_async's collect() must return exactly match_chunk's
    result (pool and serial), and with a pool the verify futures must be
    IN FLIGHT before collect() is called — that overlap is the point."""
    entities = [_entity()]
    index = EntityIndex(process_json_data(entities))
    rows = []
    for i in range(24):
        rows.append(
            {
                "article_text": ARTICLE if i % 3 == 0 else "nothing relevant here",
                "title": TITLE if i % 5 == 0 else "wrap",
                "date_time": "2020-06-01T12:00:00Z",
                "url": f"https://x/{i}.html",
            }
        )
    df = pd.DataFrame(rows)

    def norm(res):
        return [(t, json.dumps(m, sort_keys=True), r["url"]) for t, m, r in res]

    sync = match_chunk(df, index)
    assert norm(match_chunk_async(df, index)()) == norm(sync)

    pool = make_verify_pool(index, workers=2)
    if pool is not None:
        try:
            collect = match_chunk_async(df, index, pool=pool)
            # verify slices were submitted during the async call itself
            from concurrent.futures import Future

            futures = collect.futures
            assert futures and all(isinstance(f, Future) for f in futures)
            assert norm(collect()) == norm(sync)
        finally:
            pool.shutdown()


def test_refine_auto_mode_semantics(monkeypatch):
    """Default "auto" (r5): without a RefineController measurement the
    bound kernel never dispatches (measured-safe default; the r4
    pair-count gate guessed wrong both ways); with a controller verdict
    it follows the measurement.  Output is identical to both forced
    modes either way, and invalid values fail loudly."""
    import pandas as pd
    import pytest

    import advanced_scrapper_tpu.ops.editdist as ED
    from advanced_scrapper_tpu.pipeline import matcher as M

    entities = [
        {
            "id_label": "Apple Inc.",
            "ticker": "AAPL",
            "country": ["United States"],
            "industry": [],
            "aliases": ["Tim Cook", "Apple Inc."],
            "products": ["iPhone"],
            "subsidiaries": [],
            "owned_entities": [],
            "ceos": [],
            "board_members": [],
        }
    ]
    idx = M.EntityIndex(M.process_json_data(entities))
    rows = [
        {
            "article_text": "Tim Cook spoke about the new iPhone lineup.",
            "title": "daily wrap",
            "date_time": "2020-06-01T00:00:00Z",
            "url": f"https://x/{i}.html",
            "source": "s",
            "source_url": "su",
        }
        for i in range(8)
    ]
    df = pd.DataFrame(rows)

    # the bound's engagement is counted on the screen-step mode: refine
    # IS the fused screen+bound step (no separate kernel call exists);
    # a direct prune_mask_tables call would land in the same counter
    calls = {"n": 0}
    real = ED.prune_mask_tables

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(ED, "prune_mask_tables", counting)

    real_packed = M._packed_screen

    def counting_packed(rows, index, *, use_refine, **kw):
        if use_refine:
            calls["n"] += 1
        return real_packed(rows, index, use_refine=use_refine, **kw)

    monkeypatch.setattr(M, "_packed_screen", counting_packed)

    # uncalibrated auto must not dispatch the bound at all
    out_auto = M.match_chunk(df, idx)  # default is "auto"
    assert calls["n"] == 0, "uncalibrated auto must skip the bound"

    # a controller that measured refine winning flips auto on
    ctrl = M.RefineController()
    ctrl.record(False, 1.0)
    ctrl.record(True, 0.5)
    assert ctrl.verdict() is True
    idx.refine_controller = ctrl
    calls["n"] = 0
    out_auto_on = M.match_chunk(df, idx)
    assert calls["n"] > 0, "calibrated auto must follow the measurement"
    del idx.refine_controller

    calls["n"] = 0
    out_forced = M.match_chunk(df, idx, use_refine=True)
    assert calls["n"] > 0, "forced mode must dispatch regardless of count"
    out_off = M.match_chunk(df, idx, use_refine=False)
    assert sorted(t for t, _, _ in out_auto_on) == sorted(
        t for t, _, _ in out_forced
    )

    def key(res):
        return sorted((t, json_dumps(m)) for t, m, _ in res)

    import json as _json

    def json_dumps(m):
        return _json.dumps(m, sort_keys=True)

    assert key(out_auto) == key(out_forced) == key(out_off)

    with pytest.raises(ValueError, match="auto"):
        M.match_chunk(df, idx, use_refine="always")
    # explicit always-on without the screen is a conflict; auto is not
    with pytest.raises(ValueError, match="use_screen"):
        M.match_chunk(df, idx, use_screen=False, use_refine=True)
    out_noscreen = M.match_chunk(df, idx, use_screen=False)  # auto: fine
    assert key(out_noscreen) == key(out_auto)


def test_refine_controller_race():
    """The controller probes each mode once, exploits the measured winner
    with 5% hysteresis, re-probes the loser periodically, and keeps the
    MIN per-mode cost (queue inflation only ever adds time)."""
    from advanced_scrapper_tpu.pipeline.matcher import RefineController

    c = RefineController()
    assert c.next_mode() is False  # probe screen-only first
    c.record(False, 1.0)
    assert c.next_mode() is True  # then probe refine
    c.record(True, 0.99)  # faster, but within the 5% hysteresis band
    assert c.verdict() is False  # ties go to the simpler mode
    c.record(True, 0.5)
    assert c.verdict() is True
    # exploitation follows the verdict, with a periodic loser re-probe
    assert c.next_mode() is True
    modes = []
    for _ in range(RefineController.PROBE_EVERY + 2):
        m = c.next_mode()
        modes.append(m)
        c.record(m, 0.5 if m else 1.0)  # costs stay mode-true
    assert False in modes, "the losing mode must be re-probed"
    assert modes.count(False) <= 2, "re-probes are periodic, not constant"
    assert c.verdict() is True
    # a noisy (queue-inflated) later sample must not overwrite the best
    c.record(False, 50.0)
    assert c.verdict() is True
    assert c._best[False] == 1.0


def test_upper_automaton_positions_match_regex_fuzz():
    """The multi-pattern automaton path must be output-identical to the
    per-name ``\\b re.escape(name) \\b`` finditer loop it replaces —
    fuzzed over names with regex-special characters, word/non-word edge
    characters, overlapping and nested names, and repeated occurrences
    (the finditer non-overlap rule)."""
    import re as _re

    import numpy as np

    from advanced_scrapper_tpu.pipeline.matcher import (
        EntityIndex,
        _upper_positions,
        match_article,
    )

    names = [
        "AB", "ABC", "BC", "A+", "C.D", "X Y", "-AB-", "A A", "Q_Q",
        "HE", "SHE", "HERS", "IBM", "AT&T", "(A)", "ZZZZ",
    ]
    processed = {
        f"T{i}": {"aliases": {nm: (None, None)}} for i, nm in enumerate(names)
    }
    index = EntityIndex(processed)
    assert all(e.is_exact_upper for e in index.entries)
    mp, mid_of = index.upper_matcher()
    if mp is None:
        import pytest

        pytest.skip("no native multi-pattern core")

    from dateutil import parser as dateparser

    non_trivial = [0]
    rng = np.random.RandomState(17)
    frags = names + ["ab", "x", " ", ".", "+", "_", "&", "he", "AAB", "BCD",
                     "A A A", "ABAB", "SHERS", "usher", "(", ")", "-"]
    for trial in range(200):
        text = "".join(
            frags[rng.randint(len(frags))] for _ in range(rng.randint(0, 30))
        )
        got = _upper_positions(index, text)
        assert got is not None
        for nm in names:
            want = [
                m.start()
                for m in _re.finditer(r"\b" + _re.escape(nm) + r"\b", text)
            ]
            assert got.get(nm, []) == want, (trial, nm, text)

    # end-to-end: match_article with the automaton vs with it disabled
    for trial in range(40):
        text = "".join(
            frags[rng.randint(len(frags))] for _ in range(rng.randint(0, 40))
        )
        title = "".join(
            frags[rng.randint(len(frags))] for _ in range(rng.randint(0, 8))
        )
        adate = dateparser.parse("2020-01-02 10:00:00")
        with_auto = match_article(text, title, adate, index, None)
        saved = index._upper_matcher
        index._upper_matcher = (None, {})  # force the regex route
        try:
            without = match_article(text, title, adate, index, None)
        finally:
            index._upper_matcher = saved
        assert with_auto == without, (trial, text, title)
        if any(nm in text or nm in title for nm in names):
            non_trivial[0] += 1
    assert non_trivial[0] > 10  # the fuzz must exercise real matches


def test_upper_automaton_non_ascii_text_falls_back():
    """Non-ASCII articles must route to the regex path (byte offsets would
    diverge from char offsets) and still produce identical decisions."""
    from advanced_scrapper_tpu.pipeline.matcher import (
        EntityIndex,
        _upper_positions,
        match_article,
    )

    index = EntityIndex({"T0": {"aliases": {"IBM": (None, None)}}})
    from dateutil import parser as dateparser

    text = "résumé — IBM gains; naïve IBM"
    assert _upper_positions(index, text) is None  # fallback signalled
    out = match_article(
        text, "IBM", dateparser.parse("2020-01-02"), index, None
    )
    assert out["T0"]["text"]["IBM"] == [9, 26]
    assert out["T0"]["title"]["IBM"] == [0]
