"""Plain reference for ticker→article matching, independent of the program.

The rules of the upstream ``match_keywords.py`` (lines 17-87 and 159-180)
as written there, with ``rapidfuzz`` (the upstream's own scorer) and
``re``; it imports nothing of ``advanced_scrapper_tpu``:

- a company counts when ``(len(data) >= 2 and 'United States' in
  country) or len(data) <= 1``; each name may carry ``(Start: …)`` and
  ``(End: …)`` windows, and counts only for articles dated inside them
  (naive dates taken as UTC);
- an ALL-CAPS name longer than one letter matches at every
  ``\\b name \\b`` position in the text, and in the title;
- any other name that is not all lower-case letters matches a part when
  ``partial_ratio(part, name) > 95``, recorded with the positions of
  ``re.finditer(name, part)``;
- all lower-case alphabetic names are skipped.

Output per article: ``{ticker: {"text": {name: [pos…]}, "title": {…}}}``
with tickers whose both sides are empty left out.
"""

from __future__ import annotations

import re

import numpy as np
from dateutil import parser as dateparser
from dateutil.tz import tzutc
from rapidfuzz import fuzz, process

ATTRIBUTES = ("id_label", "ticker", "aliases", "products", "subsidiaries",
              "owned_entities", "ceos", "board_members")


def _parse_window(info: str):
    parts = info.split(" (")
    name = parts[0].strip()
    start = end = None
    for part in parts[1:]:
        for key in ("Start:", "End:"):
            if key in part:
                raw = part.replace(key, "").replace("T00:00:00Z)", "").strip()
                try:
                    val = dateparser.parse(raw)
                except (ValueError, dateparser.ParserError):
                    val = None
                if key == "Start:":
                    start = val
                else:
                    end = val
    return name, start, end


def _utc(d):
    return None if d is None else (d.replace(tzinfo=tzutc()) if d.tzinfo is None else d)


def _within(date, start, end) -> bool:
    """``date`` inside the window; all three already taken to UTC."""
    if date is None:
        return False
    if start and end:
        return start <= date <= end
    if start:
        return start <= date
    if end:
        return date <= end
    return True


class Universe:
    """The name table: ``(ticker, name, start, end, is_upper)`` rows."""

    def __init__(self, companies: list[dict]):
        keep = [c for c in companies
                if (len(companies) >= 2 and "United States" in c.get("country", []))
                or len(companies) <= 1]
        self.rows = []
        for c in keep:
            for attr in ATTRIBUTES:
                vals = c.get(attr, [])
                # one window per name and attribute: a later entry of the
                # same name replaces an earlier one, as upstream's dict does
                periods = {}
                for info in [vals] if isinstance(vals, str) else vals:
                    name, start, end = _parse_window(info)
                    periods[name] = (start, end)
                for name, (start, end) in periods.items():
                    if not name:
                        continue
                    if name.isupper():
                        if len(name) > 1:
                            self.rows.append((c["ticker"], name, _utc(start), _utc(end), True))
                    elif not (name.islower() and name.replace(" ", "").isalpha()):
                        self.rows.append((c["ticker"], name, _utc(start), _utc(end), False))
        self.upper_re = {r[1]: re.compile(r"\b" + re.escape(r[1]) + r"\b")
                         for r in self.rows if r[4]}
        self.fuzzy = [i for i, r in enumerate(self.rows) if not r[4]]
        self.fuzzy_names = [self.rows[i][1] for i in self.fuzzy]
        self.fuzzy_col = {row: col for col, row in enumerate(self.fuzzy)}


def _finditer(name: str, part: str) -> list[int]:
    try:
        return [m.start() for m in re.finditer(name, part)]
    except re.error:
        return [m.start() for m in re.finditer(re.escape(name), part)]


def match_article(u: Universe, text: str, title: str, date_raw: str, *,
                  threshold: float = 95.0, whole_scores: bool = False) -> dict:
    """The reference's verdicts for one article.  ``whole_scores`` keeps
    fuzzy scores as whole numbers (floored): the lower-precision control."""
    try:
        date = _utc(dateparser.parse(date_raw)) if date_raw else None
    except (ValueError, OverflowError, dateparser.ParserError):
        date = None
    # names as queries so the scoring spreads over every core; partial_ratio
    # aligns the shorter string inside the longer either way round
    scores = process.cdist(u.fuzzy_names, [text, title], scorer=fuzz.partial_ratio,
                           score_cutoff=threshold, dtype=np.float64, workers=-1).T
    if whole_scores:
        scores = np.floor(scores)
    out: dict = {}
    for j, (ticker, name, start, end, upper) in enumerate(u.rows):
        if not _within(date, start, end):
            continue
        hits = {}
        if upper:
            for side, part in (("text", text), ("title", title)):
                if name in part:  # \bname\b needs the substring: a plain prefilter
                    pos = [m.start() for m in u.upper_re[name].finditer(part)]
                    if pos:
                        hits[side] = pos
        else:
            col = u.fuzzy_col[j]
            for r, (side, part) in enumerate((("text", text), ("title", title))):
                if scores[r, col] > threshold:
                    hits[side] = _finditer(name, part)
        if hits:
            slot = out.setdefault(ticker, {"text": {}, "title": {}})
            for side, pos in hits.items():
                slot[side][name] = pos
    return {t: v for t, v in out.items() if v["text"] or v["title"]}
