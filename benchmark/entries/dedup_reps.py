"""Entry driver: ``NearDupEngine.dedup_reps``, corpus after corpus.

Set-up builds the engine from the configuration file's ``dedup`` block
(the shipped ``DedupConfig`` defaults), wraps its rerank hook in a clock,
and generates the pool of corpora from ``--seed``.  Warm-up runs every
corpus of the pool once, so each tile, epilogue and settle shape the
window uses is compiled (or loaded from the persistent cache) before the
window opens.  The window submits whole corpora in a closed loop.

The check covers the three layers the cells name: the fused tile step's
signatures (a seeded sample of rows of every corpus the window ran,
longest article included, bit-equal to the reference), and the verdicts
of candidacy, rerank and exact verify through the final representatives
of every result, against the planted families' true Jaccard.  The
signatures are those the engine hands its rerank hook at the declared
``RERANK_HOOK_EDGE``: a corpus for which the engine does not call its
hook hands none over, and its sampled rows count as wrong.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import generate
from benchmark.generate import sub_seed
from benchmark.reference import dedup as ref


class HookClock:
    """Times the engine's rerank hook and keeps the device signatures it
    is handed (the timed path's own fused-tile-step output) at the
    declared ``RERANK_HOOK_EDGE``.  Every other attribute is the inner
    hook's: the engine reads ``authoritative`` (``_prepare``) and the
    settling evidence ``last_provenance``, ``last_evicted`` and
    ``last_participants`` (``_emit_decisions``) from its hook, so the
    wrapped engine runs as the shipped one does."""

    def __init__(self, inner):
        self.inner = inner
        self.seconds = 0.0
        self.sigs = None

    def __getattr__(self, name):
        if name == "inner":  # not yet set: no inner hook to ask
            raise AttributeError(name)
        return getattr(self.inner, name)

    def __call__(self, raw, sigs, rep_bands, valid):
        import jax

        self.sigs = sigs
        jax.block_until_ready(sigs)  # the hook's first step waits here anyway
        with jax.profiler.TraceAnnotation("bench.rerank_hook"):
            t0 = time.perf_counter()
            out = self.inner(raw, sigs, rep_bands, valid)
            self.seconds += time.perf_counter() - t0
        return out


class Entry:
    SPAN = "bench.dedup_reps"
    RATE = "dedup_articles_per_s"

    def __init__(self, cell, seed: int, say, dedup_overrides: dict | None = None):
        from advanced_scrapper_tpu.config import DedupConfig
        from advanced_scrapper_tpu.pipeline.dedup import NearDupEngine

        self.say, self.seed = say, seed
        self.mix = cell.traffic
        self.stated = stated = dict(cell.config["dedup"], **(dedup_overrides or {}))
        self.eng = NearDupEngine(DedupConfig(**stated))
        effective = {k: getattr(self.eng.cfg, k) for k in stated}
        if effective != stated:
            raise RuntimeError(f"engine runs {effective}, the configuration states {stated}")
        self.clock = None
        if self.eng.rerank_hook is not None:
            self.clock = HookClock(self.eng.rerank_hook)
            self.eng.rerank_hook = self.clock
        say("dedup config", **stated, rerank_hook=self.clock is not None)
        t0 = time.perf_counter()
        make = getattr(generate, self.mix["generator"])
        self.pool = [make(self.mix, seed, i) for i in range(int(self.mix["pool"]))]
        say("corpora built", corpora=len(self.pool), articles=self.mix["articles"],
            bytes=[sum(map(len, c.docs)) for c in self.pool],
            seconds=time.perf_counter() - t0)
        self.sigs: dict[int, object] = {}
        self.sig_rows: dict[int, tuple] = {}

    def pool_size(self) -> int:
        return len(self.pool)

    def size(self, i: int) -> int:
        return len(self.pool[i].docs)

    def warm(self) -> None:
        for i in range(len(self.pool)):
            t0 = time.perf_counter()
            self.run(i)
            tier = self.eng.rerank_tier
            self.say("warm-up corpus", index=i, seconds=time.perf_counter() - t0,
                     tier=None if tier is None else tier.stats)

    def run(self, i: int) -> np.ndarray:
        reps = self.eng.dedup_reps(self.pool[i].docs)
        if self.clock is not None:
            self.sigs[i] = self.clock.sigs
        return reps

    def begin_window(self) -> None:
        from advanced_scrapper_tpu.obs import stages

        self.stage0 = stages.snapshot_ms()
        self.hook0 = self.clock.seconds if self.clock is not None else 0.0

    def end_window(self, window) -> dict:
        from advanced_scrapper_tpu.obs import stages

        st = stages.snapshot_ms()
        k = self.eng.params.shingle_k
        shingles = nbytes = 0
        for i, _ in window.results:
            lens = np.fromiter(map(len, self.pool[i].docs), np.int64)
            shingles += int(np.maximum(lens - k + 1, 0).sum())
            nbytes += int(lens.sum())
        return {
            "stage_ms": {s: v - self.stage0.get(s, 0.0) for s, v in st.items()},
            "hook_s": (self.clock.seconds - self.hook0) if self.clock is not None else None,
            "shingles": shingles,
            "article_bytes": nbytes,
            "num_perm": self.eng.params.num_perm,
            "shingle_k": k,
        }

    def end_to_end(self, window) -> dict:
        return {self.RATE: window.articles / window.elapsed}

    def release(self, window) -> None:
        """Read the sampled signature rows back, then free the engine."""
        n_rows = int(self.mix["check_sig_rows"])
        for i in sorted({i for i, _ in window.results}):
            rows = sig_sample(self.pool[i].docs, self.seed, i, n_rows)
            got = np.asarray(self.sigs[i])[rows] if i in self.sigs else None
            self.sig_rows[i] = (rows, got)
        self.sigs.clear()
        self.eng = self.clock = None

    def check(self, window) -> dict:
        return compare_window(self.pool, window.results, self.sig_rows, self.stated, self.say)


def sig_sample(docs: list, seed: int, i: int, n: int) -> np.ndarray:
    """The rows of corpus ``i`` whose signatures the check compares: ``n``
    drawn from the seed, and the longest article."""
    rng = np.random.RandomState(sub_seed(seed, 104729, i))
    rows = rng.choice(len(docs), size=min(n, len(docs)), replace=False)
    return np.unique(np.r_[rows, int(np.argmax([len(d) for d in docs]))])


def compare_window(pool, results, sig_rows, cfg: dict, say) -> dict:
    """``sig_rows_off``: sampled signature rows unequal to the reference
    (summed); ``unchained_share`` and ``missed_share``: the worst result
    (``missed_share`` only where the corpora hold true pairs)."""
    t0 = time.perf_counter()
    k, thr = cfg["shingle_k"], cfg["sim_threshold"]
    a, b = ref.device_family(cfg["seed"], cfg["num_perm"])
    sig_off = 0
    worst = {"unchained_share": 0.0, "missed_share": 0.0}
    pairs = 0
    for i in sorted({i for i, _ in results}):
        corpus = pool[i]
        rows, got = sig_rows.get(i, (np.zeros(0, np.int64), None))
        if got is None:
            sig_off += len(rows)  # the timed path handed no signatures over
        else:
            want = ref.signatures([corpus.docs[r] for r in rows], a, b, k)
            sig_off += int((got != want).any(axis=1).sum())
        cache = ref.ShingleCache(corpus.docs, k)
        truth = ref.true_pairs(corpus.family, cache, thr)
        pairs += len(truth)
        seen: list[tuple[np.ndarray, dict]] = []
        for j, reps in results:
            if j != i:
                continue
            res = next((r for prev, r in seen if np.array_equal(prev, reps)), None)
            if res is None:
                res = ref.compare_reps(reps, truth, cache, thr)
                seen.append((reps, res))
                say("dedup check", corpus=i, **res)
            worst["unchained_share"] = max(worst["unchained_share"], res["unchained_share"])
            worst["missed_share"] = max(worst["missed_share"], res["missed_share"])
    say("dedup reference", seconds=time.perf_counter() - t0)
    if not pairs:  # a mix with no true pair has no recall to miss
        del worst["missed_share"]
    return {"sig_rows_off": sig_off, **worst}
