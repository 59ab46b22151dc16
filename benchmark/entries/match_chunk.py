"""Entry driver: ``pipeline.matcher.match_chunk``, chunk after chunk.

Set-up builds the entity universe from the configuration file's fixed
seed (the screen step constant-folds the name tables, so a universe per
``--seed`` would recompile for minutes every run), the ``EntityIndex``,
the verify pool at the configured width (0 = ``cpu_count``, the
upstream ``mp.Pool`` width) and the pool of chunks from ``--seed``.
Warm-up runs every chunk once: every screen tile shape the window uses
is compiled or loaded, and every verify worker has built its tables.
``use_refine`` is the production ``"auto"``; with no
``RefineController`` attached (only ``run_matcher`` attaches one), auto
resolves to the screen-only mode.

The check covers the screen and the verify pool through the per-article
match sets: a seeded sample of the articles of every chunk the window
ran, the chunk's longest article included, must equal the plain
reference's verdicts exactly.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import generate
from benchmark.generate import entity_universe, sub_seed, universe_names
from benchmark.reference import match as ref


class Entry:
    SPAN = "bench.match_chunk"
    RATE = "match_articles_per_s"

    def __init__(self, cell, seed: int, say):
        import pandas as pd

        from advanced_scrapper_tpu.pipeline.matcher import (
            EntityIndex,
            make_verify_pool,
            process_json_data,
        )

        self.say, self.seed = say, seed
        self.mix, self.cfg = cell.traffic, cell.config["match"]
        t0 = time.perf_counter()
        self.companies = entity_universe(self.cfg["universe_seed"], self.cfg["tickers"])
        self.index = EntityIndex(process_json_data(self.companies))
        self.pool = make_verify_pool(self.index, self.cfg["verify_workers"])
        names = universe_names(self.companies)
        make = getattr(generate, self.mix["generator"])
        self.rows = [make(self.mix, seed, i, names) for i in range(int(self.mix["pool"]))]
        self.frames = [pd.DataFrame(r) for r in self.rows]
        say("matcher set-up", tickers=len(self.index.processed), names=len(self.index.entries),
            chunks=len(self.frames), articles=self.mix["articles"],
            pool_workers=getattr(self.pool, "_max_workers", 0),
            refine="auto: no RefineController attached, so screen-only",
            seconds=time.perf_counter() - t0)
        self.results: dict = {}

    def pool_size(self) -> int:
        return len(self.frames)

    def size(self, i: int) -> int:
        return len(self.frames[i])

    def warm(self) -> None:
        for i in range(len(self.frames)):
            t0 = time.perf_counter()
            self.run(i)
            self.say("warm-up chunk", index=i, seconds=time.perf_counter() - t0)

    def run(self, i: int):
        from advanced_scrapper_tpu.pipeline.matcher import match_chunk

        return match_chunk(self.frames[i], self.index, pool=self.pool,
                           use_refine="auto", threshold=self.cfg["fuzzy_threshold"])

    def begin_window(self) -> None:
        from advanced_scrapper_tpu.obs import stages

        self.stage0 = stages.snapshot_ms()

    def end_window(self, window) -> dict:
        from advanced_scrapper_tpu.obs import stages

        st = stages.snapshot_ms()
        return {"stage_ms": {s: v - self.stage0.get(s, 0.0) for s, v in st.items()}}

    def end_to_end(self, window) -> dict:
        return {self.RATE: window.articles / window.elapsed}

    def release(self, window) -> None:
        """Keep each chunk's per-article verdicts, stop the verify pool."""
        for i, res in window.results:
            by_url: dict = {}
            for ticker, m, row in res:
                by_url.setdefault(row["url"], {})[ticker] = m
            self.results.setdefault(i, []).append(by_url)
        if self.pool is not None:
            self.pool.shutdown(wait=True)
            self.pool = None
        stop_forkserver()
        self.index = None

    def check(self, window) -> dict:
        """``articles_off``: sampled articles whose match set differs from
        the reference's, over every result of the window."""
        t0 = time.perf_counter()
        u = ref.Universe(self.companies)
        off = checked = 0
        for i, outs in sorted(self.results.items()):
            for k in sample(self.rows[i], self.seed, i, int(self.mix["check_articles"])):
                row = self.rows[i][k]
                want = ref.match_article(u, row["article_text"], row["title"], row["date_time"],
                                         threshold=self.cfg["fuzzy_threshold"])
                for by_url in outs:
                    checked += 1
                    off += int(by_url.get(row["url"], {}) != want)
        self.say("matcher reference", articles=checked, seconds=time.perf_counter() - t0)
        return {"articles_off": off}


def sample(rows: list[dict], seed: int, i: int, n: int) -> list[int]:
    """The articles of chunk ``i`` the check compares: ``n`` drawn from the
    seed, and the longest."""
    rng = np.random.RandomState(sub_seed(seed, 7919, i))
    pick = rng.choice(len(rows), size=min(n, len(rows)), replace=False)
    longest = int(np.argmax([len(r["article_text"]) for r in rows]))
    return sorted(set(pick.tolist()) | {longest})


def stop_forkserver() -> None:
    """End the forkserver process the verify pool started, and wait for it."""
    from multiprocessing import forkserver

    server = getattr(forkserver, "_forkserver", None)
    stop = getattr(server, "_stop", None)
    if stop is not None:
        stop()
