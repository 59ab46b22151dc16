"""A rehearsal run with the timed path broken underneath must come out
``correct: false``: once for each fault a cell can have.  The look for a
chip is skipped (``--rehearse``); everything else is the run as it is."""

import io
import json

import numpy as np
import pytest

from benchmark import run as harness


def rehearse(cell: str, seed: int = 20261015) -> dict:
    out = io.StringIO()
    rc = harness.main(["--workload", cell, "--seed", str(seed), "--seconds", "0.5",
                       "--trace", "0", "--rehearse"], out=out)
    assert rc == harness.EXIT_REHEARSAL
    return json.loads(out.getvalue().strip().splitlines()[-1])


DEDUP_CELLS = ["dedup.news-mix", "dedup.news-fresh"]


@pytest.mark.parametrize("cell", DEDUP_CELLS)
def test_dedup_sound_run_is_correct(cell):
    assert rehearse(cell)["correct"] is True


@pytest.mark.parametrize("cell", DEDUP_CELLS)
def test_dedup_step_returns_state_unchanged(cell, monkeypatch):
    from advanced_scrapper_tpu.pipeline import dedup

    monkeypatch.setattr(dedup.NearDupEngine, "_get_fused_step",
                        lambda self: (lambda running, packed, **kw: running))
    line = rehearse(cell)
    assert line["correct"] is False
    assert line["checks"]["sig_rows_off"]["value"] > 0


@pytest.mark.parametrize("cell", DEDUP_CELLS)
def test_dedup_half_of_each_tile_left_out(cell, monkeypatch):
    from advanced_scrapper_tpu.pipeline import dedup

    orig = dedup.NearDupEngine._host_tiles

    def half(self, raw, trace_id=None):
        for tok, lens, owners in orig(self, raw, trace_id=trace_id):
            keep = max(tok.shape[0] // 2, 1)
            lens = lens.copy()
            lens[keep:] = 0  # rows past the first half carry no bytes
            yield tok, lens, owners

    monkeypatch.setattr(dedup.NearDupEngine, "_host_tiles", half)
    line = rehearse(cell)
    assert line["correct"] is False
    assert line["checks"]["sig_rows_off"]["value"] > 0


@pytest.mark.parametrize("cell", DEDUP_CELLS)
def test_dedup_answer_altered_where_produced(cell, monkeypatch):
    from advanced_scrapper_tpu.pipeline import dedup

    orig = dedup.resolve_rep_bands_from_ok

    def altered(*args, **kwargs):
        rep = np.asarray(orig(*args, **kwargs)).copy()
        valid = np.asarray(args[2])  # (rep_bands, ok, valid): padding rows are not valid
        loners = np.flatnonzero((rep == np.arange(rep.shape[0])) & valid)
        k = max(1, len(loners) // 100)  # one answer in a hundred
        rep[loners[-k:]] = loners[0]  # merged with an unrelated article
        return rep

    monkeypatch.setattr(dedup, "resolve_rep_bands_from_ok", altered)
    line = rehearse(cell)
    assert line["correct"] is False
    assert line["checks"]["unchained_share"]["value"] > 0


def test_match_sound_run_is_correct():
    assert rehearse("match.sp500-news")["correct"] is True


def test_match_half_of_each_batch_left_out(monkeypatch):
    from advanced_scrapper_tpu.pipeline import matcher

    orig = matcher._packed_screen

    def half(rows, index, **kw):
        masks, prunes = orig(rows, index, **kw)
        for a in range(1, len(masks), 2):  # every other article screened empty
            if masks[a] is not None:
                masks[a] = np.zeros_like(masks[a])
        return masks, prunes

    monkeypatch.setattr(matcher, "_packed_screen", half)
    line = rehearse("match.sp500-news")
    assert line["correct"] is False
    assert line["checks"]["articles_off"]["value"] > 0


def test_match_answer_altered_where_produced(monkeypatch):
    from advanced_scrapper_tpu.pipeline import matcher

    orig = matcher.match_chunk

    def shifted(m):  # every position of the first matched name off by one
        side = "text" if m["text"] else "title"
        name = next(iter(m[side]))
        return {**m, side: {**m[side], name: [p + 1 for p in m[side][name]]}}

    def altered(*args, **kwargs):
        return [(t, shifted(m), row) for t, m, row in orig(*args, **kwargs)]

    monkeypatch.setattr(matcher, "match_chunk", altered)
    line = rehearse("match.sp500-news")
    assert line["correct"] is False
    assert line["checks"]["articles_off"]["value"] > 0


def test_hook_clock_is_the_inner_hook_to_the_engine():
    """The engine reads ``authoritative`` and the settling evidence from
    its hook: the clock answers each as the inner hook does."""
    from types import SimpleNamespace

    from benchmark.entries.dedup_reps import HookClock

    inner = SimpleNamespace(authoritative=True, last_provenance={(0, 1): "margin"},
                            last_evicted={3}, last_participants={0, 1, 3})
    clock = HookClock(inner)
    for name in ("authoritative", "last_provenance", "last_evicted", "last_participants"):
        assert getattr(clock, name) is getattr(inner, name)
    assert getattr(clock, "no_such_attribute", None) is None
