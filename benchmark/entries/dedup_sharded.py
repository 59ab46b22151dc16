"""Benchmark entry: ``NearDupEngine.dedup_reps_sharded`` over a device mesh,
corpus after corpus.

Set-up builds the engine from the configuration file's ``dedup`` block
and checks that it runs what the file states (the sharded plane applies
no rerank tier and no exact verify, so the file turns both off), builds
the ``mesh`` the file names over the first devices, and generates the
pool of corpora from ``--seed``.  Warm-up runs every corpus once, so each
tile shape and the epilogue are compiled (or loaded from the persistent
cache) before the window opens.  The window submits whole corpora in a
closed loop.

The check, once the program's state is freed: ``reps_off``, the articles
of every corpus the window ran whose representative differs from the
plain reference's (``reference/lsh.py``: the same resolution over the
reference's own signatures); ``unchained_share`` and ``missed_share``
(``reference/dedup.py`` ``compare_reps``, worst corpus) hold the
estimator's precision and recall.

The timed path calls only ``dedup_reps_sharded`` and ``build_mesh``; the
padded-tile counter (``obs.stages.sharded_tile_counters``) is read where
the program has it and otherwise reported as not counted.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from benchmark import generate
from benchmark.reference import lsh

#: below this many articles in all, the reference runs in this process
POOL_FROM = 4 * lsh.CHUNK


class Entry:
    SPAN = "bench.dedup_reps_sharded"
    RATE = "dedup_articles_per_s"

    def __init__(self, cell, seed: int, say):
        import jax

        from advanced_scrapper_tpu.config import DedupConfig
        from advanced_scrapper_tpu.core.mesh import build_mesh
        from advanced_scrapper_tpu.pipeline.dedup import NearDupEngine

        self.say, self.seed = say, seed
        self.mix = cell.traffic
        self.stated = stated = dict(cell.config["dedup"])
        self.eng = NearDupEngine(DedupConfig(**stated))
        effective = {k: getattr(self.eng.cfg, k) for k in stated}
        if effective != stated:
            raise RuntimeError(f"engine runs {effective}, the configuration states {stated}")
        rows, cols = cell.config["mesh"]
        self.chips = rows * cols
        self.mesh = build_mesh(rows, cols, devices=jax.devices()[: self.chips])
        say("dedup config", **stated, mesh=[rows, cols])
        t0 = time.perf_counter()
        make = getattr(generate, self.mix["generator"])
        self.pool = [make(self.mix, seed, i) for i in range(int(self.mix["pool"]))]
        say("corpora built", corpora=len(self.pool), articles=self.mix["articles"],
            bytes=[sum(map(len, c.docs)) for c in self.pool],
            seconds=time.perf_counter() - t0)

    def pool_size(self) -> int:
        return len(self.pool)

    def size(self, i: int) -> int:
        return len(self.pool[i].docs)

    def warm(self) -> None:
        for i in range(len(self.pool)):
            t0 = time.perf_counter()
            self.run(i)
            self.say("warm-up corpus", index=i, seconds=time.perf_counter() - t0,
                     tile_groups=self.eng.last_tiles)

    def run(self, i: int) -> np.ndarray:
        return self.eng.dedup_reps_sharded(self.pool[i].docs, self.mesh)

    def begin_window(self) -> None:
        from advanced_scrapper_tpu.obs import stages

        self.stage0 = stages.snapshot_ms()
        self.tiles0 = tile_counters()

    def end_window(self, window) -> dict:
        from advanced_scrapper_tpu.obs import stages

        st = stages.snapshot_ms()
        tiles = tile_counters()
        if tiles is None:
            self.say("padded tiles: not counted by this program")
        else:
            per = {s: {k: v - self.tiles0.get(s, {}).get(k, 0.0) for k, v in c.items()}
                   for s, c in tiles.items()}
            self.say("padded tiles", zero_tiles=sum(c["pad_tiles"] for c in per.values()),
                     tile_slots=sum(c["tile_slots"] for c in per.values()), per_shard=per)
        k = self.eng.params.shingle_k
        shingles = nbytes = 0
        for i, _ in window.results:
            lens = np.fromiter(map(len, self.pool[i].docs), np.int64)
            shingles += int(np.maximum(lens - k + 1, 0).sum())
            nbytes += int(lens.sum())
        return {
            "stage_ms": {s: v - self.stage0.get(s, 0.0) for s, v in st.items()},
            "shingles": shingles,
            "article_bytes": nbytes,
            "num_perm": self.eng.params.num_perm,
            "shingle_k": k,
            "chips": self.chips,
        }

    def end_to_end(self, window) -> dict:
        return {self.RATE: window.articles / window.elapsed}

    def release(self, window) -> None:
        self.eng = self.mesh = None

    def check(self, window) -> dict:
        return compare_window(self.pool, window.results, self.stated, self.say)


def tile_counters() -> dict | None:
    """The program's per-shard ``{tile_slots, pad_tiles}``, or None where
    it keeps no such counter."""
    from advanced_scrapper_tpu.obs import stages

    read = getattr(stages, "sharded_tile_counters", None)
    return None if read is None else read()


def executor_for(pool, items) -> contextlib.AbstractContextManager:
    """A process pool where the corpora are large, else no pool."""
    if sum(len(pool[i].docs) for i in items) < POOL_FROM:
        return contextlib.nullcontext(None)
    return lsh.pool()


def compare_window(pool, results, cfg: dict, say, *, want: dict | None = None,
                   executor=None) -> dict:
    """``reps_off``: articles whose representative differs from the
    reference's, summed over every result; ``unchained_share`` and
    ``missed_share``: the worst result.  ``want`` may carry the
    reference's representatives by corpus index."""
    t0 = time.perf_counter()
    ran = sorted({i for i, _ in results})
    k, thr = cfg["shingle_k"], cfg["sim_threshold"]
    distinct: dict[int, list] = {}
    for i, reps in results:
        got = distinct.setdefault(i, [])
        if not any(np.array_equal(reps, r) for r in got):
            got.append(np.asarray(reps))
    with (contextlib.nullcontext(executor) if executor is not None
          else executor_for(pool, ran)) as ex:
        if ex is None:
            shares = {i: lsh.shares(pool[i].docs, pool[i].family, distinct[i], k, thr)
                      for i in ran}
        else:
            futs = {i: ex.submit(lsh.shares, pool[i].docs, pool[i].family, distinct[i], k, thr)
                    for i in ran}
        if want is None:
            want = dict(zip(ran, lsh.reference_reps([pool[i].docs for i in ran], cfg,
                                                     executor=ex)))
        if ex is not None:
            shares = {i: f.result() for i, f in futs.items()}
    off = sum(int((np.asarray(reps) != want[i]).sum()) for i, reps in results)
    worst = {"unchained_share": 0.0, "missed_share": 0.0}
    pairs = 0
    for i in ran:
        for res in shares[i]:
            say("dedup check", corpus=i, **res)
            pairs = max(pairs, res["true_pairs"])
            worst["unchained_share"] = max(worst["unchained_share"], res["unchained_share"])
            worst["missed_share"] = max(worst["missed_share"], res["missed_share"])
    say("lsh reference", seconds=time.perf_counter() - t0, corpora=ran, results=len(results))
    if not pairs:  # a mix with no true pair has no recall to miss
        del worst["missed_share"]
    return {"reps_off": off, **worst}
