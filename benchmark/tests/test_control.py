"""The controls (``benchmark/control.py``) must come out not correct:
each fails at least one of its cell's numbers against the cell's own
limits.  At a size a test run holds; the chip readings at the cells' own
sizes are in PERF.md."""

import pytest

from benchmark import control
from benchmark.run import Cell, compare


def failed(values: dict, cell: Cell) -> list[str]:
    return [k for k, c in compare(values, cell.limits).items() if c["value"] > c["limit"]]


def say(*_a, **_k):
    pass


@pytest.mark.parametrize("seed", [11, 2**31 + 7])
def test_estimator_only_control_fails_news_mix(seed):
    cell = Cell("dedup.news-mix", rehearse=True)
    cell.traffic.update(articles=2048, pool=1)
    reading = control.control_readings(cell, seed, say)
    # the precision tier's work dropped: both its merges and its recall show
    assert sorted(failed(reading["estimator_only"], cell)) == ["missed_share", "unchained_share"]
    assert failed(reading["sig16"], cell) == ["sig_rows_off"]


def test_program_passes_where_its_control_fails():
    cell = Cell("dedup.news-mix", rehearse=True)
    cell.traffic.update(articles=2048, pool=1)
    assert failed(control.program_reading(cell, 11, say), cell) == []


def test_sig16_control_fails_news_fresh():
    cell = Cell("dedup.news-fresh", rehearse=True)
    reading = control.control_readings(cell, 5, say)
    assert "estimator_only" not in reading  # no pair to misjudge in a fresh crawl
    assert failed(reading["sig16"], cell) == ["sig_rows_off"]


def test_whole_score_control_fails_matcher():
    cell = Cell("match.sp500-news", rehearse=True)
    cell.config["match"] = dict(cell.config["match"], tickers=503)
    cell.traffic.update(articles=600, pool=1, check_articles=600)
    assert failed(control.control_readings(cell, 3, say)["whole_scores"], cell) == ["articles_off"]
