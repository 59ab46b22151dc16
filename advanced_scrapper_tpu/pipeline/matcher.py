"""L5: entity→article matching, rerouted through the TPU q-gram screen.

Re-implements ``match_keywords.py`` end to end:

- **entity loading** (ref ``:40-120``): every ``info/*.json`` (utf-8 → gbk →
  latin1 fallback chain), company filter ``(len >= 2 and 'United States' in
  country) or len <= 1``, and ``"Name (Start: …) (End: …)"`` suffix parsing
  into per-name date windows;
- **match rules** (ref ``:159-180``), byte-identical decisions:
  - ALL-CAPS names of length > 1 → ``\\b re.escape(name) \\b`` positions in
    article text and title;
  - names that are not pure-lowercase-alphabetic → fuzzy
    ``partial_ratio(text, name) > 95`` (native C++ kernel; exact score
    parity with installed rapidfuzz 3.x is CI-fuzzed in
    ``tests/test_rapidfuzz_parity.py``), positions via un-escaped
    ``re.finditer`` like the ref;
  - everything else is skipped entirely;
  - a name only counts when the article date is inside its window
    (``is_within_period``, naive datetimes promoted to UTC, ref ``:17-37``);
- **outputs** (ref ``:128-146,195-217``): per-ticker
  ``{source}_ticker_matched_articles/{ticker}_match.csv`` rows with
  JSON-encoded match-position dicts, then a final per-file sort by
  ``time_unix``.

The TPU reroute: instead of scanning every (article × name) pair on the
host (the reference's quadratic hot loop), a device q-gram screen
(``ops/match.py``) prunes pairs first; only survivors are verified with the
exact host rules above, so outputs cannot differ — golden-tested against a
pure reference implementation.  A second device stage (``use_refine``: the
Myers alignment bound, ``ops/editdist.py``) can prune screen survivors
whose text-side fuzzy score is provably ≤ threshold before the host scorer
runs — output-identical (golden-tested), default **"auto"** (r5 verdict):
whether the bound pays depends on the prune yield and the host/device
cost ratio of the actual backend+corpus — a decoy-heavy corpus runs
2.2× FASTER with it, the bench corpus 1.9× SLOWER, and surviving-pair
count points the wrong way in both cases (the r4 gate's mistake; it
cost the tracked matcher metric 38%).  So "auto" is a measured RACE:
``run_matcher`` probes both modes on real chunks and exploits the
winner (:class:`RefineController`); direct ``match_chunk`` calls
without a measurement run screen-only.  The r3 always-on loss
(63 s vs 2.6 s) was a remote chip's per-slice dispatch latency — the
race measures that too, so high-latency transports converge to
screen-only without a special case (``--no-refine`` still forces it).

The device path rides the SAME single-dispatch plane as the dedup
engine (PR 9 → PR 10): chunks split into byte-budget width-bucketed
screen tiles, each crossing H2D as ONE packed buffer
(``ops/pack.py``, 5 int32 trailer planes) into ONE fused jitted
screen(+Myers-bound) dispatch (``ops.match.make_screen_step``), all
pipelined encode/pack → h2d → dispatch through the dispatch executor
(``pipeline/dispatch.py``) with a bounded in-flight window — so a tile
is exactly 1 put + 1 dispatch, gated numerically by the always-on
device counters (tier-1 ``tests/test_match_dispatch.py``, ``bench
--regime matcher``).  The refine race picks fused-vs-screen-only
MODES of that one step, not separate kernels.

Documented divergences from the reference (both are reference *crashes*):
- a fuzzy-matched name that is itself an invalid regex falls back to
  escaped-literal position search (the ref raises ``re.error`` mid-chunk);
- matched articles whose ``date_time`` cannot be parsed are skipped with a
  warning (the ref raises inside ``append_to_csv``).
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
from dataclasses import dataclass

import numpy as np
import pandas as pd
from dateutil import parser as dateparser
from dateutil.tz import tzutc

from advanced_scrapper_tpu.config import MatchConfig
from advanced_scrapper_tpu.cpu import native

# ops.match (and through it jax) is imported lazily inside the screen path:
# verify-pool workers must stay jax-free (they only run the host rules), and
# CLI paths that never screen shouldn't pay device-runtime import time.

ATTRIBUTES = (
    "id_label",
    "ticker",
    "aliases",
    "products",
    "subsidiaries",
    "owned_entities",
    "ceos",
    "board_members",
)  # ref :76-85

OUTPUT_FIELDS = [
    "time_unix",
    "date_time",
    "text_matches",
    "title_matches",
    "title",
    "url",
    "source",
    "source_url",
    "article_text",
]  # ref :134-144


# -- reference parsing helpers ---------------------------------------------


def is_within_period(article_date, start_date, end_date) -> bool:
    """Date-window gate (ref :17-37); naive datetimes are promoted to UTC."""
    if article_date is None:
        return False
    if article_date.tzinfo is None:
        article_date = article_date.replace(tzinfo=tzutc())
    if start_date is not None and start_date.tzinfo is None:
        start_date = start_date.replace(tzinfo=tzutc())
    if end_date is not None and end_date.tzinfo is None:
        end_date = end_date.replace(tzinfo=tzutc())
    if start_date and end_date:
        return start_date <= article_date <= end_date
    if start_date:
        return start_date <= article_date
    if end_date:
        return article_date <= end_date
    return True


def extract_time_periods(names) -> dict[str, tuple]:
    """``"Name (Start: …) (End: …)"`` → {name: (start, end)} (ref :40-65)."""
    periods: dict[str, tuple] = {}
    if isinstance(names, str):
        names = [names]
    for info in names:
        parts = info.split(" (")
        name = parts[0].strip()
        start = end = None
        for part in parts[1:]:
            if "Start:" in part:
                raw = part.replace("Start:", "").replace("T00:00:00Z)", "").strip()
                try:
                    start = dateparser.parse(raw)
                except (ValueError, dateparser.ParserError):
                    start = None
            elif "End:" in part:
                raw = part.replace("End:", "").replace("T00:00:00Z)", "").strip()
                try:
                    end = dateparser.parse(raw)
                except (ValueError, dateparser.ParserError):
                    end = None
        periods[name] = (start, end)
    return periods


def process_json_data(json_data: list) -> dict:
    """US-company filter + per-attribute period maps (ref :68-87)."""
    result = {}
    for company in json_data:
        if (len(json_data) >= 2 and "United States" in company.get("country", [])) or len(
            json_data
        ) <= 1:
            ticker = company["ticker"]
            result[ticker] = {
                attr: extract_time_periods(company.get(attr, [])) for attr in ATTRIBUTES
            }
    return result


def read_info_dir(folder: str) -> dict:
    """Load every info JSON with the encoding fallback chain (ref :90-120)."""
    out: dict = {}
    for filename in sorted(os.listdir(folder)):
        if not filename.endswith(".json"):
            continue
        path = os.path.join(folder, filename)
        data = None
        for enc in ("utf-8", "gbk", "latin1"):
            try:
                with open(path, "r", encoding=enc) as f:
                    data = json.load(f)
                break
            except (UnicodeDecodeError, json.JSONDecodeError):
                continue
        if data is None:
            print(f"could not read {filename}")
            continue
        out.update(process_json_data(data))
    return out


# -- flattened entity index (screen-ready) ----------------------------------


@dataclass(frozen=True)
class NameEntry:
    ticker: str
    attribute: str
    name: str
    start: object
    end: object
    is_exact_upper: bool  # ALL-CAPS word-boundary path
    # (fuzzy otherwise; unreachable names are never stored)


class EntityIndex:
    """Flat, screen-ready view of the processed entity data."""

    def __init__(self, processed: dict):
        self.processed = processed
        self.entries: list[NameEntry] = []
        for ticker, attrs in processed.items():
            for attribute, names in attrs.items():
                for name, (start, end) in names.items():
                    if not name:
                        # empty names (reachable via extract_time_periods on
                        # strings starting " (") score partial_ratio 0.0 in
                        # rapidfuzz 3.x — they can never match; storing them
                        # would only waste screen lanes
                        continue
                    if name.isupper():
                        if len(name) > 1:
                            self.entries.append(
                                NameEntry(ticker, attribute, name, start, end, True)
                            )
                        # single-char upper names never match (ref :166)
                    elif not (name.islower() and name.replace(" ", "").isalpha()):
                        self.entries.append(
                            NameEntry(ticker, attribute, name, start, end, False)
                        )
                    # pure-lowercase-alpha names are skipped (ref :174)
        self._tables: dict | None = None
        self._verify_arena = None
        self._upper_matcher: tuple | None = None
        #: compiled packed screen steps per mode (False = screen-only,
        #: True = fused screen+bound) — built lazily by ``_screen_steps``
        self._packed_steps: dict = {}
        #: optional per-tile observer ``(dict) -> None`` on the packed
        #: dispatch loop (tile index, rows, width, h2d_bytes, put/dispatch
        #: ms) — ``tools/profile_hostpath.py --device`` renders it
        self.dispatch_probe = None

    @classmethod
    def from_info_dir(cls, folder: str) -> "EntityIndex":
        return cls(read_info_dir(folder))

    def screen_tables(self) -> dict:
        if self._tables is None:
            from advanced_scrapper_tpu.obs import stages
            from advanced_scrapper_tpu.ops.match import prepare_names

            with stages.timed("matcher_build"):
                names = [e.name.encode("utf-8", "replace") for e in self.entries]
                fuzzy = np.array(
                    [not e.is_exact_upper for e in self.entries], bool
                )
                self._tables = prepare_names(names, fuzzy=fuzzy)
        return self._tables

    def upper_matcher(self):
        """``(MultiPattern | None, {name: pattern_id})`` over the unique
        ASCII ALL-CAPS names — the single-pass multi-pattern core that
        replaces per-name ``\\b re.escape(name) \\b`` scans.  Built lazily
        ONCE per EntityIndex (so streaming chunks never rebuild it) and
        never pickled: verify-pool workers reconstruct the index from
        ``processed`` at init and build their own on first use.  None when
        no native backend (or no eligible names) — callers keep the
        per-name regex path, which stays the behavioural oracle."""
        if self._upper_matcher is None:
            from advanced_scrapper_tpu.cpu.native import MultiPattern
            from advanced_scrapper_tpu.obs import stages

            with stages.timed("matcher_build"):
                names = sorted({
                    e.name for e in self.entries
                    if e.is_exact_upper and e.name.isascii()
                })
                mp = None
                if names:
                    cand = MultiPattern([n.encode("ascii") for n in names])
                    mp = cand if cand.available else None
                self._upper_matcher = (mp, {n: i for i, n in enumerate(names)})
        return self._upper_matcher

    def verify_arena(self):
        """Packed-needle arena over all entry names (rows = entry index),
        built lazily once per EntityIndex — the native verify scores
        screened rows against it without per-article re-encoding.  Pool
        workers rebuild the index from ``processed`` at init and each build
        their own arena on first use; the parent's is never shipped."""
        if self._verify_arena is None:
            self._verify_arena = native.CutoffArena(
                [e.name for e in self.entries]
            )
        return self._verify_arena


# -- matching ----------------------------------------------------------------


def _find_positions(pattern: str, text: str) -> list[int]:
    return [m.start() for m in re.finditer(pattern, text)]


# ASCII \w table (letters, digits, underscore): on ASCII text this is
# exactly Python re's Unicode \w membership, which is what the boundary
# replay below must reproduce.
_ASCII_WORD = bytes(
    1 if (chr(c).isalnum() or c == ord("_")) else 0 for c in range(128)
) + bytes(128)


def _upper_positions(index: "EntityIndex", text: str) -> dict[str, list[int]] | None:
    """Per-name start positions of every ALL-CAPS name in ``text`` via ONE
    automaton pass — output-identical to running
    ``re.finditer(r"\\b" + re.escape(name) + r"\\b", text)`` per name.

    None routes the caller to the per-name regex path (no native automaton,
    or non-ASCII text, where byte offsets would diverge from char offsets).
    The \\b replay: a boundary holds where exactly one side is a word char,
    so each raw automaton hit checks its edge bytes against the name's edge
    bytes; surviving hits then replay finditer's non-overlap rule per name
    (a match consumes its span; a boundary-rejected occurrence consumes
    nothing).  Names absent from the dict simply have no matches.
    """
    mp, mid_of = index.upper_matcher()
    if mp is None or not text.isascii():
        return None
    data = text.encode("ascii")
    ids, starts = mp.scan(data)
    out: dict[str, list[int]] = {}
    if not len(ids):
        return out
    n = len(data)
    last_end: dict[int, int] = {}
    names = mp.patterns
    for i, s in zip(ids.tolist(), starts.tolist()):
        nb = names[i]
        e = s + len(nb)
        # \b before: boundary between text[s-1] and name[0]
        if _ASCII_WORD[nb[0]]:
            if s > 0 and _ASCII_WORD[data[s - 1]]:
                continue
        elif s == 0 or not _ASCII_WORD[data[s - 1]]:
            continue
        # \b after: boundary between name[-1] and text[e]
        if _ASCII_WORD[nb[-1]]:
            if e < n and _ASCII_WORD[data[e]]:
                continue
        elif e >= n or not _ASCII_WORD[data[e]]:
            continue
        if s < last_end.get(i, 0):
            continue  # finditer resumes at the previous match's end
        last_end[i] = e
        out.setdefault(nb.decode("ascii"), []).append(s)
    return out


def _find_positions_literal_fallback(name: str, text: str) -> list[int]:
    try:
        return _find_positions(name, text)
    except re.error:
        return _find_positions(re.escape(name), text)


def match_article(
    text: str,
    title: str,
    article_date,
    index: EntityIndex,
    candidate_mask: np.ndarray | None = None,
    threshold: float = 95.0,
    text_pruned: set | None = None,
) -> dict:
    """Exact match rules for one article → {ticker: {'text': …, 'title': …}}.

    ``candidate_mask[j]`` (from the TPU screen) prunes name j; None means
    scan everything (the pure reference path used for goldens).
    ``text_pruned`` holds name indices whose *text-side* fuzzy score is
    device-proven ≤ threshold (``ops/editdist.py`` Myers bound) — the
    expensive long-text ``partial_ratio`` call is skipped for those; the
    title side still runs (the bound applies per part).
    """
    per_ticker: dict[str, dict] = {}

    def slot(ticker: str) -> dict:
        return per_ticker.setdefault(ticker, {"text": {}, "title": {}})

    # Pass 1: filter (screen mask + date window) and split by rule kind.
    # Fuzzy scores batch into ONE native call per (article, side) —
    # per-name calls re-encode the whole article and pay a ctypes round
    # trip each (measured ~65 screened names/article); decisions and the
    # j-ascending insert order below are identical to the per-name loop.
    pending: list[tuple[int, object]] = []
    text_rows: list[int] = []   # entry indices j to score against the text
    title_rows: list[int] = []  # entry indices j to score against the title
    entries = index.entries
    if candidate_mask is None:
        survivors = range(len(entries))
    else:
        # iterate screen survivors only (C-level nonzero), not every entry
        survivors = np.flatnonzero(candidate_mask).tolist()
    any_upper = False
    for j in survivors:
        e = entries[j]
        if not is_within_period(article_date, e.start, e.end):
            continue
        pending.append((j, e))
        if not e.is_exact_upper:
            # text side skipped when the device bound proved it ≤ threshold
            if text_pruned is None or j not in text_pruned:
                text_rows.append(j)
            title_rows.append(j)
        else:
            any_upper = True

    arena = index.verify_arena()
    text_score = dict(zip(text_rows, arena.scores(text, text_rows, threshold)))
    title_score = dict(zip(title_rows, arena.scores(title, title_rows, threshold)))

    # ALL-CAPS positions: one automaton pass per article part replaces the
    # per-name \b regex scans (identical output; _upper_positions).  None
    # (no native core / non-ASCII part) keeps the regex path per part.
    auto_names: dict | None = None
    text_hits = title_hits = None
    if any_upper:
        auto_names = index.upper_matcher()[1]
        text_hits = _upper_positions(index, text)
        title_hits = _upper_positions(index, title)

    # Pass 2: apply the decisions in the original j order.
    for j, e in pending:
        if e.is_exact_upper:
            # positions are the decision (ref :165-173)
            in_auto = auto_names is not None and e.name in auto_names
            pattern = None
            if in_auto and text_hits is not None:
                text_pos = text_hits.get(e.name, [])
            else:
                pattern = r"\b" + re.escape(e.name) + r"\b"
                text_pos = _find_positions(pattern, text)
            if in_auto and title_hits is not None:
                title_pos = title_hits.get(e.name, [])
            else:
                if pattern is None:
                    pattern = r"\b" + re.escape(e.name) + r"\b"
                title_pos = _find_positions(pattern, title)
            if text_pos:
                slot(e.ticker)["text"][e.name] = text_pos
            if title_pos:
                slot(e.ticker)["title"][e.name] = title_pos
        else:
            # the score is the decision; positions recorded even if empty
            # (ref :174-180); cutoff semantics: sub-threshold scores are 0
            if text_score.get(j, 0.0) > threshold:
                slot(e.ticker)["text"][e.name] = _find_positions_literal_fallback(
                    e.name, text
                )
            if title_score.get(j, 0.0) > threshold:
                slot(e.ticker)["title"][e.name] = _find_positions_literal_fallback(
                    e.name, title
                )
    return {t: v for t, v in per_ticker.items() if v["text"] or v["title"]}


def _get_col(row, *candidates, default=""):
    for c in candidates:
        if c in row and pd.notna(row[c]):
            return str(row[c])
    return default


def _refine_candidates(index: EntityIndex):
    """Fuzzy names the Myers bound kernel can handle: non-exact-upper,
    1..MAX_PATTERN bytes, pure ASCII (the bound is byte-level; multi-byte
    chars would break its soundness vs the char-level oracle).  Returns
    ``(name_indices, mask_tables)``; the tables depend only on the index,
    and ``_screen_steps`` builds them once per index."""
    from advanced_scrapper_tpu.ops.editdist import MAX_PATTERN, build_pattern_masks

    ix, names = [], []
    for j, e in enumerate(index.entries):
        nb = e.name.encode("utf-8", "replace")
        if not e.is_exact_upper and 0 < len(nb) <= MAX_PATTERN and nb.isascii():
            ix.append(j)
            names.append(nb)
    return np.asarray(ix, dtype=np.int64), build_pattern_masks(names)


class RefineController:
    """Measured race for the alignment-bound stage (r5, VERDICT r4 item 3).

    The r4 "auto" gate keyed on a 256-pair breakeven — the WRONG
    statistic: re-measured on the same CPU backend, the adversarial decoy
    corpus (~840 surviving pairs/batch) runs 2.2× FASTER with refine
    while the bench corpus (~4,200 pairs/batch — MORE pairs) runs 1.9×
    SLOWER; a pair-count threshold picks wrong in both directions, and it
    cost the driver-tracked matcher metric 38% in r4.  Whether the bound
    kernel pays depends on the prune yield and the host-vs-device cost
    ratio of the actual (backend, corpus) pair — knowable only by
    measurement, so the streaming path RACES the two modes: probe each
    mode once on real chunks, commit to the winner (refine must beat
    screen-only by 5% to win — ties go to the simpler mode), and re-RACE
    from scratch every ``PROBE_EVERY`` chunks so corpus drift can flip
    the verdict (a min kept forever would let a stale win pin a mode
    that has since degraded).  Within an epoch, per-mode cost is the MIN
    observed s/row — robust against pipeline-queue inflation, which only
    ever adds time.
    """

    PROBE_EVERY = 16
    WIN_MARGIN = 0.95

    def __init__(self):
        # locked: since the streaming path became a stage graph,
        # next_mode() runs in the screen stage's worker thread while
        # record() runs in the drain (caller) thread — an epoch reset
        # must never be observed half-applied
        self._lock = threading.Lock()
        self._best: dict[bool, float | None] = {False: None, True: None}
        self._chunks = 0
        self._default = False  # verdict carried across epoch resets

    def next_mode(self) -> bool:
        with self._lock:
            if self._best[False] is None:
                return False
            if self._best[True] is None:
                return True
            return self._verdict_locked()

    def record(self, mode: bool, seconds_per_row: float) -> None:
        with self._lock:
            self._chunks += 1
            if self._chunks % self.PROBE_EVERY == 0:
                # epoch boundary: carry the verdict as the default, re-race
                self._default = self._verdict_locked()
                self._best = {False: None, True: None}
            prev = self._best[mode]
            if prev is None or seconds_per_row < prev:
                self._best[mode] = seconds_per_row

    def verdict(self) -> bool:
        with self._lock:
            return self._verdict_locked()

    def _verdict_locked(self) -> bool:
        off, on = self._best[False], self._best[True]
        if off is None or on is None:
            return self._default  # mid-race: the last settled verdict
        return on < off * self.WIN_MARGIN


# -- packed single-dispatch screen tiles (the PR 9 plane) --------------------


def _screen_tile_rows(tile_bytes: int, width: int) -> int:
    """Full-tile row count for a screen width bucket: the byte budget
    divided by the row width, power-of-two bucketed, clamped to
    [16, 4096].  THE single source of the formula — the tile chunker and
    :func:`prewarm_screen` must draw from the same shape set, or
    prewarming silently compiles a disjoint set and defeats itself
    (the dedup encoder's `_tile_bs` lesson)."""
    bs = min(max(tile_bytes // max(width, 1), 16), 4096)
    return 1 << (int(bs).bit_length() - 1)


def _screen_rows_options(bs: int) -> list[int]:
    """Every row count the greedy tile chunker can emit for a width
    bucket: the full tile plus the descending power-of-two tail chunks
    (≥16; the last one zero-pads) — the O(log bs) shape set prewarm
    compiles (``core.tokenizer.tile_rows_options``, shared with the
    dedup tile plane)."""
    from advanced_scrapper_tpu.core.tokenizer import tile_rows_options

    return tile_rows_options(bs, 16)


def _screen_steps(index: EntityIndex, use_refine: bool):
    """The index's compiled packed screen step for one MODE — screen-only
    or fused screen+Myers-bound (``ops.match.make_screen_step``; the
    refine-race controller picks between these two modes, not between
    separate kernels).  Built lazily once per (index, mode) — the name
    tables constant-fold into the step, so streaming chunks never re-ship
    them (zero per-tile table traffic)."""
    cache = getattr(index, "_packed_steps", None)
    if cache is None:
        cache = index._packed_steps = {}
    key = bool(use_refine)
    step = cache.get(key)
    if step is None:
        from advanced_scrapper_tpu.obs import devprof, stages
        from advanced_scrapper_tpu.ops.match import make_screen_step

        with stages.timed("matcher_build"):
            refine = None
            if key:
                fuzzy_ix, mask_tables = _refine_candidates(index)
                if len(fuzzy_ix):
                    masks, lens, ok = mask_tables
                    refine = (masks, lens, ok, fuzzy_ix)
                else:
                    # no refine candidates ⇒ the fused mode IS the
                    # screen-only step — alias it instead of compiling an
                    # identical kernel under a second jit closure (the
                    # recompile sentinel rides the alias too: one wrapped
                    # object, one jit cache)
                    step = cache[key] = _screen_steps(index, False)
                    return step
            # recompile sentinel (obs/devprof.py): every jit-cache miss
            # counts on astpu_jit_compiles_total{kernel=
            # "matcher_screen_step"} — prewarm_screen's compiles are the
            # expected counts, a steady-state increment is the stall the
            # prewarmed shape set exists to prevent
            step = devprof.instrument_jit(
                make_screen_step(index.screen_tables(), refine),
                "matcher_screen_step",
            )
        cache[key] = step
    return step


def _match_cfg() -> MatchConfig:
    """Env-resolved matcher knobs (``ASTPU_MATCH_*``) for direct
    ``match_chunk*`` callers that pass no explicit values — re-read per
    chunk (cheap: a handful of environ lookups) so tests and sweeps can
    flip knobs between calls."""
    from advanced_scrapper_tpu.config import from_env

    return from_env(MatchConfig, "match")


def _packed_screen(
    rows: list,
    index: EntityIndex,
    *,
    use_refine: bool,
    threshold: float,
    screen_block: int,
    tile_bytes: int,
    window: int,
    put_workers: int,
) -> tuple[list, list]:
    """Screen a chunk through the packed single-dispatch tile plane:
    width-bucketed rows → byte-budget tiles → ONE ``device_put`` + ONE
    fused jitted dispatch per tile, pipelined (encode/pack → h2d →
    dispatch) through the dispatch executor with a bounded in-flight
    window.  Returns ``(masks, text_prunes)`` in ``match_chunk_async``'s
    shapes; rows above ``screen_block`` never enter a tile (mask None =
    full host scan, counted in ``astpu_matcher_overlong_total``).

    Out-of-order tile arrival from the put pool never matters: each
    tile's rows carry their article owners (packed into the buffer,
    returned by the step), and the host scatter is per-row."""
    import jax

    from advanced_scrapper_tpu.obs import devprof, stages, telemetry
    from advanced_scrapper_tpu.ops.match import FLAG_REFINE_OK, MASK_TEXT_PRUNED
    from advanced_scrapper_tpu.ops.pack import pack_tile_planes
    from advanced_scrapper_tpu.core.tokenizer import bucket_widths, encode_batch
    from advanced_scrapper_tpu.pipeline.dispatch import (
        PipelinedDispatcher,
        resolve_dispatch_window,
    )

    n = len(rows)
    masks: list[np.ndarray | None] = [None] * n
    prunes: list[set | None] = [None] * n
    with stages.timed("matcher_screen"):
        raw = [
            (title + "\n" + text).encode("utf-8", "replace")
            for text, title, _, _ in rows
        ]
        lens = np.fromiter(map(len, raw), np.int64, count=n)
        title_len = np.array(
            [len(t.encode("utf-8", "replace")) for _, t, _, _ in rows],
            np.int32,
        )
        # per-char encoding ⇒ len(title\ntext) = len(title) + 1 + len(text)
        # exactly, so the text side never pays a second full-article encode
        text_len = (lens - title_len - 1).astype(np.int32)
        flags = np.array(
            [
                FLAG_REFINE_OK if (t and t.isascii()) else 0
                for t, _, _, _ in rows
            ],
            np.int32,
        )
        overlong = lens > screen_block
        n_overlong = int(overlong.sum())
        if n_overlong:
            telemetry.event_counter(
                "astpu_matcher_overlong_total",
                "articles above screen_block routed to the full host scan",
            ).inc(n_overlong)
        eligible = np.flatnonzero(~overlong)
    if eligible.size == 0:
        return masks, prunes
    widths = bucket_widths(
        lens[eligible], min_bucket=1024, max_bucket=screen_block
    )
    order = np.argsort(widths, kind="stable")
    sorted_w = widths[order]
    group_lo = np.flatnonzero(np.r_[True, sorted_w[1:] != sorted_w[:-1]])
    step = _screen_steps(index, use_refine)
    probe = getattr(index, "dispatch_probe", None)

    def tiles():
        for g, lo in enumerate(group_lo):
            hi = group_lo[g + 1] if g + 1 < len(group_lo) else len(order)
            idx = eligible[order[lo:hi]]
            w = int(sorted_w[lo])
            bs = _screen_tile_rows(tile_bytes, w)  # shared with prewarm
            start = 0
            while start < len(idx):
                remaining = len(idx) - start
                nrows = bs
                if remaining < bs:
                    nrows = 16
                    while nrows * 2 <= remaining:
                        nrows *= 2
                sel = idx[start : start + nrows]
                with stages.timed("matcher_screen"):
                    tok, dl = encode_batch(
                        [raw[j] for j in sel], block_len=w
                    )
                    own = sel.astype(np.int32)
                    if tok.shape[0] < nrows:
                        pad = nrows - tok.shape[0]
                        tok = np.concatenate(
                            [tok, np.zeros((pad, w), np.uint8)]
                        )
                        dl = np.concatenate([dl, np.zeros((pad,), np.int32)])
                        own = np.concatenate(
                            [own, np.full((pad,), -1, np.int32)]
                        )
                    tl = np.zeros((nrows,), np.int32)
                    ttl = np.zeros((nrows,), np.int32)
                    fl = np.zeros((nrows,), np.int32)
                    tl[: len(sel)] = text_len[sel]
                    ttl[: len(sel)] = title_len[sel]
                    fl[: len(sel)] = flags[sel]
                yield tok, dl, tl, ttl, fl, own, w
                start += nrows

    def pack(item):
        # plane order is the step's SCREEN_PLANES unpack contract
        tok, dl, tl, ttl, fl, own, w = item
        with stages.timed("matcher_screen"):
            buf = pack_tile_planes(tok, dl, tl, ttl, fl, own)
        return buf, tok.shape[0], w

    def put(item):
        buf, nrows, w = item
        t0 = time.perf_counter()
        with stages.timed("h2d"):
            dev = jax.device_put(buf)
        stages.count_device_put(buf.nbytes, "matcher")
        return dev, nrows, w, buf.nbytes, time.perf_counter() - t0

    waits = stages.Tally()  # added once per chunk

    def scatter(result) -> None:
        mask_dev, own_dev = result
        with waits.timed("matcher_screen_wait", "matcher.screen_wait"):
            m = np.asarray(mask_dev)  # readback sync: THIS tile only
            own = np.asarray(own_dev)
        keep = (m & 1).astype(bool)
        for local in range(m.shape[0]):
            a = int(own[local])
            if a >= 0:
                masks[a] = keep[local]
        if use_refine:
            for r, c in zip(*np.nonzero(m & MASK_TEXT_PRUNED)):
                a = int(own[r])
                if a < 0:
                    continue
                if prunes[a] is None:
                    prunes[a] = set()
                prunes[a].add(int(c))

    # Mask readback trails the dispatch loop by a bounded LAG (the
    # executor's own residency bound) instead of syncing per tile (a
    # stall per tile) or deferring every tile to end-of-chunk: a
    # 20k-row chunk against a large entity index would otherwise hold
    # O(tiles) [rows, N] device masks at once.  Syncing a tile that is
    # `lag` dispatches behind costs ~nothing — it has almost surely
    # completed — so the pipeline stays full with device residency
    # capped at lag mask buffers.
    lag = resolve_dispatch_window(window, put_workers) + put_workers + 1
    results: list = []
    pipe = PipelinedDispatcher(
        tiles(),
        pack=pack,
        put=put,
        put_workers=put_workers,
        window=window,
        name="matcher.h2d",
    )
    try:
        for i, item in enumerate(pipe):
            dev, nrows, w, nbytes, put_s = item
            t0 = time.perf_counter()
            with stages.timed("matcher_screen"), devprof.dispatch_span(
                "matcher_screen_tile", rows=nrows, width=w
            ):
                # async dispatch; trailing tiles drain below
                out = step(dev, threshold, rows=nrows, width=w)
            stages.count_dispatch("matcher")
            results.append(out)
            if probe is not None:
                probe(
                    {
                        "tile": i,
                        "rows": nrows,
                        "width": w,
                        "h2d_bytes": nbytes,
                        "put_ms": round(put_s * 1e3, 3),
                        "dispatch_ms": round(
                            (time.perf_counter() - t0) * 1e3, 3
                        ),
                    }
                )
            if len(results) > lag:
                with stages.timed("matcher_screen"):
                    scatter(results.pop(0))
    finally:
        pipe.close()
    with stages.timed("matcher_screen"):
        for result in results:
            scatter(result)
    waits.flush()
    return masks, prunes


def prewarm_screen(
    index: EntityIndex,
    *,
    use_refine: bool | None = None,
    threshold: float = 95.0,
    screen_block: int = 1 << 16,
    tile_bytes: int | None = None,
) -> int:
    """Compile the packed screen-step shape set ahead of the first chunk
    (the matcher twin of ``NearDupEngine.prewarm``): every width bucket
    from 1024 to ``screen_block`` × its O(log bs) tile row options, for
    the screen-only mode, the fused mode, or both (``use_refine=None``
    compiles both — the refine race will dispatch whichever wins).
    Returns the number of shape variants compiled.  With
    the persistent compile cache the compiles persist across processes
    and later prewarms are cache loads."""
    import jax.numpy as jnp

    from advanced_scrapper_tpu.core.mesh import maybe_enable_compile_cache
    from advanced_scrapper_tpu.ops.match import SCREEN_PLANES
    from advanced_scrapper_tpu.ops.pack import packed_nbytes

    maybe_enable_compile_cache()
    if tile_bytes is None:
        tile_bytes = _match_cfg().screen_tile_bytes
    widths = []
    w = 1024
    while w < screen_block:
        widths.append(w)
        w *= 2
    widths.append(screen_block)
    modes = (False, True) if use_refine is None else (bool(use_refine),)
    compiled = 0
    warmed: set[int] = set()
    for mode in modes:
        step = _screen_steps(index, mode)
        if id(step) in warmed:
            continue  # fused mode aliased to screen-only (no candidates)
        warmed.add(id(step))
        for w in widths:
            for rows in _screen_rows_options(_screen_tile_rows(tile_bytes, w)):
                packed = jnp.zeros(
                    (packed_nbytes(rows, w, SCREEN_PLANES),), jnp.uint8
                )
                mask, _own = step(packed, threshold, rows=rows, width=w)
                mask.block_until_ready()
                compiled += 1
    return compiled


def match_chunk_async(
    chunk: pd.DataFrame,
    index: EntityIndex,
    *,
    use_screen: bool = True,
    use_refine: bool | str = "auto",
    screen_block: int = 1 << 16,
    threshold: float = 95.0,
    pool=None,
    screen_tile_bytes: int | None = None,
    dispatch_window: int | None = None,
    screen_put_workers: int | None = None,
):
    """Screen + submit a frame NOW; return a zero-arg ``collect()`` whose
    call yields :func:`match_chunk`'s result.

    With a pool, the verify slices are already in flight when this
    returns, so a streaming caller (``run_matcher``) can screen chunk
    i+1 on the device while chunk i's verify work runs in the pool —
    the reference's own overlap (its ``mp.Pool`` never sits idle between
    20k-row chunks, ``match_keywords.py:227-238``).  Without a pool,
    ``collect()`` does the verify work serially when called.
    """
    # identity checks, not `in (True, False, "auto")`: 1 == True would
    # slip through equality and silently demote a forced-on request to auto
    if not (use_refine is True or use_refine is False or use_refine == "auto"):
        raise ValueError(f"use_refine must be True/False/'auto', got {use_refine!r}")
    if use_refine is True and not use_screen:
        # refine lives inside the screen path; silently no-opping here would
        # betray a direct caller's explicit request (previously this guard
        # lived only in run_matcher).  "auto" is opportunistic, not a
        # request — without the screen it simply never engages.
        raise ValueError("use_refine requires use_screen (see DESIGN.md §4)")
    if use_refine == "auto":
        # "auto" defers to a RefineController verdict measured on THIS
        # (backend, corpus) pair — run_matcher's streaming race attaches
        # one to the index; without a measurement refine stays off (the
        # r4 pair-count gate guessed, and guessed wrong; see
        # RefineController)
        ctrl = getattr(index, "refine_controller", None)
        use_refine = ctrl.verdict() if ctrl is not None else False

    from advanced_scrapper_tpu.obs import stages, telemetry, trace

    m_articles = telemetry.counter(
        "astpu_matcher_articles_total", "articles entering the matcher"
    )
    m_matches = telemetry.counter(
        "astpu_matcher_matches_total", "(ticker, article) matches produced"
    )
    tid = trace.new_trace_id()

    rows = []
    with stages.timed("matcher_rows"), trace.span("matcher.rows", trace=tid):
        # plain dicts, not Series: ~100 µs/row cheaper to build, identical
        # mapping access in _get_col, and far cheaper to pickle to workers
        for row in chunk.to_dict("records"):
            text = _get_col(row, "article_text", "article")
            title = _get_col(row, "title")
            raw_date = _get_col(row, "date_time", "datetime", default="")
            try:
                adate = dateparser.parse(raw_date) if raw_date else None
            except (ValueError, OverflowError, dateparser.ParserError):
                adate = None
            rows.append((text, title, adate, row))

    m_articles.inc(len(rows))
    masks: list[np.ndarray | None] = [None] * len(rows)
    text_prunes: list[set | None] = [None] * len(rows)
    if use_screen and index.entries:
        with trace.span("matcher.screen", trace=tid, articles=len(rows)):
            # knob resolution: explicit args win, else the ASTPU_MATCH_* env
            # (run_matcher passes its MatchConfig fields through explicitly)
            if None in (screen_tile_bytes, dispatch_window, screen_put_workers):
                _cfg = _match_cfg()
                if screen_tile_bytes is None:
                    screen_tile_bytes = _cfg.screen_tile_bytes
                if dispatch_window is None:
                    dispatch_window = _cfg.dispatch_window
                if screen_put_workers is None:
                    screen_put_workers = _cfg.put_workers
            # byte-budget width-bucketed tiles, ONE packed put + ONE
            # fused screen(+bound) dispatch per tile, pipelined
            masks, text_prunes = _packed_screen(
                rows,
                index,
                use_refine=bool(use_refine),
                threshold=threshold,
                screen_block=screen_block,
                tile_bytes=screen_tile_bytes,
                window=dispatch_window,
                put_workers=screen_put_workers or 1,
            )

    if pool is not None and len(rows) > 1:
        # ship (text, title, date, row-INDEX) out; the full row record stays
        # here and is re-attached on return (half the IPC volume)
        light = [(t, ti, d, i) for i, (t, ti, d, _r) in enumerate(rows)]
        n_slices = min(getattr(pool, "_max_workers", 4), len(rows))
        bounds = np.linspace(0, len(rows), n_slices + 1).astype(int)
        futures = [
            pool.submit(
                _verify_slice,
                light[lo:hi], masks[lo:hi], text_prunes[lo:hi], threshold,
                time.monotonic(),
            )
            for lo, hi in zip(bounds[:-1], bounds[1:])
            if hi > lo
        ]

        def collect():
            out = []
            busy = queued = 0.0
            with stages.timed("matcher_verify"), trace.span(
                "matcher.verify", trace=tid, articles=len(rows)
            ):
                for f in futures:  # slice order == row order
                    got, busy_s, queued_s = f.result()
                    busy += busy_s
                    queued += queued_s
                    out.extend((ticker, m, rows[i][3]) for ticker, m, i in got)
            # the workers' own clocks: they never reach this process's
            # histograms, so their sums are added here, once per chunk
            stages.add("matcher_verify_busy", busy)
            stages.add("matcher_verify_queue", queued)
            m_matches.inc(len(out))
            return out

        collect.futures = futures  # introspectable: the in-flight slices
        return collect

    def collect():
        out = []
        with stages.timed("matcher_verify"), stages.timed(
            "matcher_verify_busy"
        ), trace.span("matcher.verify", trace=tid, articles=len(rows)):
            for (text, title, adate, row), mask, pruned in zip(
                rows, masks, text_prunes
            ):
                matches = match_article(
                    text, title, adate, index, mask, threshold, pruned
                )
                for ticker, m in matches.items():
                    out.append((ticker, m, row))
        m_matches.inc(len(out))
        return out

    return collect


def match_chunk(
    chunk: pd.DataFrame,
    index: EntityIndex,
    *,
    use_screen: bool = True,
    use_refine: bool | str = "auto",
    screen_block: int = 1 << 16,
    threshold: float = 95.0,
    pool=None,
    screen_tile_bytes: int | None = None,
    dispatch_window: int | None = None,
    screen_put_workers: int | None = None,
) -> list[tuple[str, dict, dict]]:
    """Match a frame of articles → [(ticker, matches, row_record), …].

    Accepts both the reference dataset schema (``article_text``/``date_time``)
    and this framework's scraper schema (``article``/``datetime``).

    ``pool`` (an executor from :func:`make_verify_pool`) fans the host-side
    exact-verify stage out across processes — the successor of the
    reference's ``np.array_split`` × ``mp.Pool.starmap(cpu_count)``
    (``match_keywords.py:231-238``).  The device screen always runs in THIS
    process (one device context); only the CPU verify work ships out.
    Output order is identical with and without a pool.
    """
    return match_chunk_async(
        chunk,
        index,
        use_screen=use_screen,
        use_refine=use_refine,
        screen_block=screen_block,
        threshold=threshold,
        pool=pool,
        screen_tile_bytes=screen_tile_bytes,
        dispatch_window=dispatch_window,
        screen_put_workers=screen_put_workers,
    )()


# -- verify-stage process pool (ref match_keywords.py:231-238) ---------------

_WORKER_INDEX: EntityIndex | None = None


def _verify_worker_init(processed: dict) -> None:
    """Build the worker's EntityIndex ONCE (not per slice)."""
    global _WORKER_INDEX
    _WORKER_INDEX = EntityIndex(processed)


def _warm_noop() -> bool:
    return True


def _verify_slice(rows, masks, prunes, threshold: float, submitted: float):
    """Run the host exact-verify rules over one row slice (no jax, no
    device: masks/prunes were computed by the screen in the parent).
    ``rows`` carry row INDICES, echoed back for parent-side re-attach.

    Returns ``(matches, busy_s, queued_s)``: the worker's seconds in the
    rules, and the seconds from the parent's submit (``submitted``, a
    ``time.monotonic()`` stamp: the clock is system-wide on Linux, so the
    two processes' stamps compare) to this worker's start."""
    start = time.monotonic()
    index = _WORKER_INDEX
    out = []
    for (text, title, adate, row_ix), mask, pruned in zip(rows, masks, prunes):
        matches = match_article(text, title, adate, index, mask, threshold, pruned)
        for ticker, m in matches.items():
            out.append((ticker, m, row_ix))
    return out, time.monotonic() - start, start - submitted


def make_verify_pool(index: EntityIndex, workers: int | None = None):
    """ProcessPoolExecutor for the exact-verify stage, or None for ≤ 1
    worker.  The entity data ships once via the initializer, not per chunk.

    Start method: **forkserver**, fork-safe by construction (VERDICT r3
    item 7).  jax's fork warning flags ``os.fork()`` in a process whose
    (jax-internal) locks may be mid-acquire; with forkserver, every worker
    is forked from the forkserver's own server process — a fresh
    interpreter that never imports jax (worker code is host-only
    re/native/dateutil; ``ops.match`` device imports are lazy and live in
    the parent's screen stage).  No fork ever happens in a jax-threaded
    process, no matter when the pool is created or how imports evolve,
    and no worker initialises a jax backend (it would hold, or wait on,
    the parent's chip; ``chip_smoke.py`` checks this on the chip)."""
    import multiprocessing as mp
    from concurrent.futures import ProcessPoolExecutor, wait

    if workers is None or workers == 0:  # 0 = auto, matching cfg.verify_workers
        workers = os.cpu_count() or 1
    if workers <= 1:
        return None
    try:
        ctx = mp.get_context("forkserver")
    except ValueError:  # non-POSIX (no fork at all): spawn
        ctx = mp.get_context("spawn")
    pool = ProcessPoolExecutor(
        max_workers=workers,
        mp_context=ctx,
        initializer=_verify_worker_init,
        initargs=(index.processed,),
    )
    # Executors create workers lazily on first submit; warm every worker
    # now so a host that refuses worker processes shows up here
    warm = [pool.submit(_warm_noop) for _ in range(workers)]
    wait(warm)
    dead = next((f.exception() for f in warm if f.exception()), None)
    if dead is not None:
        # container/sandbox hosts that refuse worker processes must
        # degrade to inline verify, not poison every later submit
        import sys

        print(
            "verify pool unavailable "
            f"({type(dead).__name__}: {dead}); verifying inline",
            file=sys.stderr,
        )
        pool.shutdown(wait=False, cancel_futures=True)
        return None
    return pool


# -- output writing (ref :128-146, :195-217) --------------------------------


def append_match(out_dir: str, ticker: str, matches: dict, row) -> bool:
    raw_date = _get_col(row, "date_time", "datetime")
    try:
        ts = int(dateparser.parse(raw_date).timestamp())
    except Exception:
        print(f"skipping row with unparseable date_time: {raw_date!r}")
        return False
    record = {
        "time_unix": ts,
        "date_time": raw_date,
        "text_matches": json.dumps(matches["text"]),
        "title_matches": json.dumps(matches["title"]),
        "title": _get_col(row, "title"),
        "url": _get_col(row, "url"),
        "source": _get_col(row, "source"),
        "source_url": _get_col(row, "source_url"),
        "article_text": _get_col(row, "article_text", "article"),
    }
    path = os.path.join(out_dir, f"{ticker}_match.csv")
    header = not os.path.exists(path)
    pd.DataFrame([record]).to_csv(path, mode="a", index=False, header=header)
    return True


def sort_matched_csv(path: str) -> None:
    """Final per-file time sort (ref :195-217)."""
    try:
        df = pd.read_csv(path)
        if "time_unix" not in df.columns:
            df["date_time"] = df["date_time"].apply(dateparser.parse)
            df["time_unix"] = df["date_time"].apply(lambda x: int(x.timestamp()))
        df = df.sort_values("time_unix", ascending=True)
        df["time_unix"] = df["time_unix"].astype(int)
        df.to_csv(path, index=False)
    except Exception as e:
        print(f"Error processing {path}: {e}")


def run_matcher(
    cfg: MatchConfig,
    *,
    use_screen: bool | None = None,
    use_refine: bool | str = "auto",
    articles_csv: str | None = None,
    workers: int | None = None,
) -> int:
    """CLI entry: full matching run (ref ``__main__`` :220-246).

    The verify stage fans out over ``workers`` processes (default
    ``cfg.verify_workers``; 0 = ``os.cpu_count()``, the reference's pool
    width) — one pool for the whole run, created BEFORE the screen touches
    the device so fork never duplicates an active device context.  CSV
    writing stays in this process: single-writer by construction, unlike
    the reference's lock-free multi-process appends
    (``match_keywords.py:128-146``, a known race designed out here).
    """
    articles_csv = articles_csv or cfg.articles_csv
    if not os.path.exists(articles_csv):
        print(f"Articles CSV '{articles_csv}' not found.")
        return 1
    index = EntityIndex.from_info_dir(cfg.info_dir)
    out_dir = f"{cfg.source_name}{cfg.out_dir_suffix}"
    os.makedirs(out_dir, exist_ok=True)
    use_screen = cfg.use_tpu if use_screen is None else use_screen
    if use_refine is True and not use_screen:
        # "auto" is opportunistic and simply never engages without the
        # screen; only an explicit always-on request conflicts
        raise ValueError("use_refine requires use_screen (see DESIGN.md §4)")
    if workers is None:
        workers = cfg.verify_workers
    if cfg.prewarm and use_screen and index.entries:
        # compile the screen-step shape set before the first chunk (the
        # NearDupEngine.prewarm twin).  Under "auto" both
        # modes compile so the refine race can flip without a mid-stream
        # compile stall; a forced mode prewarms only the mode that can
        # ever dispatch.
        prewarm_screen(
            index,
            use_refine=None if use_refine == "auto" else bool(use_refine),
            threshold=cfg.fuzzy_threshold,
            tile_bytes=cfg.screen_tile_bytes,
        )
    pool = make_verify_pool(index, workers)  # 0/None normalise to cpu_count
    n_matches = 0
    # the streaming race that calibrates "auto" for THIS backend+corpus:
    # per-chunk screen+verify wall per row feeds the controller, which
    # probes each mode once and then exploits the measured winner
    # no controller without the screen: refine cannot engage there, and a
    # raw "auto" string must never reach controller.record
    controller = (
        RefineController() if use_refine == "auto" and use_screen else None
    )
    if controller is not None:
        index.refine_controller = controller

    def drain(item) -> None:
        nonlocal n_matches
        collect, mode, screen_s, nrows = item
        t0 = time.perf_counter()
        for ticker, matches, row in collect():
            if append_match(out_dir, ticker, matches, row):
                n_matches += 1
        if controller is not None and nrows:
            controller.record(mode, (screen_s + time.perf_counter() - t0) / nrows)

    # screen→verify as a stage graph: the single-worker ``screen`` stage
    # reads a chunk and submits its device screen + pool verify slices;
    # the capacity-1 ``screened`` edge bounds the window at ≤3 resident
    # chunks (one draining, one buffered, one the stage just screened
    # before blocking on put — one more than the old deque's 2, traded
    # for the screen never idling), and the drain stays in THIS thread so
    # CSV appends remain single-writer, in chunk order (FIFO edge + one
    # worker ⇒ order preserved by construction).
    from advanced_scrapper_tpu.runtime import DONE, StageGraph

    chunks = pd.read_csv(articles_csv, chunksize=cfg.chunk_size)

    def read_next():
        try:
            return next(chunks)
        except StopIteration:
            return DONE

    def screen(chunk):
        mode = (
            controller.next_mode()
            if controller is not None and use_screen
            else use_refine
        )
        t0 = time.perf_counter()
        collect = match_chunk_async(
            chunk,
            index,
            use_screen=use_screen,
            use_refine=mode,
            threshold=cfg.fuzzy_threshold,
            pool=pool,
            screen_tile_bytes=cfg.screen_tile_bytes,
            dispatch_window=cfg.dispatch_window,
            screen_put_workers=cfg.put_workers,
        )
        return (collect, mode, time.perf_counter() - t0, len(chunk))

    try:
        if pool is None:
            # serial mode keeps its deliberate single-chunk residency
            # bound: collect() is lazy caller-thread work with no overlap
            # to gain, so screening ahead would only double peak memory
            while True:
                chunk = read_next()
                if chunk is DONE:
                    break
                drain(screen(chunk))
        else:
            graph = StageGraph("matcher")
            screened = graph.edge("screened", capacity=1)
            graph.stage("screen", source=read_next, fn=screen, out_edge=screened)
            graph.start()
            try:
                for item in screened:
                    drain(item)
                if graph.error is not None:
                    raise graph.error  # the original screen-stage exception
            finally:
                graph.stop()
                graph.join(timeout=30, raise_error=False)
    finally:
        if pool is not None:
            pool.shutdown()
        # detach the controller: it holds a verdict measured on THIS
        # backend+corpus, and a later direct match_chunk(..., 'auto')
        # against the shared index must fall back to the measured-safe
        # off default instead of silently reusing a stale measurement
        if controller is not None and getattr(
            index, "refine_controller", None
        ) is controller:
            del index.refine_controller
    for f in os.listdir(out_dir):
        sort_matched_csv(os.path.join(out_dir, f))
    print(f"Matching complete: {n_matches} ticker-article matches → {out_dir}/")
    return 0
