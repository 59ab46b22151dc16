"""Dedup engines — the TPU rerouting of the reference's dedup steps.

- :class:`NearDupEngine` — MinHash(k=5, 128-perm) + 16-band LSH near-dup
  clustering (the north-star workload; no analogue in the reference, which
  only ever does exact dedup).
- :class:`ExactDedup` — byte-identical replacement for pandas
  ``drop_duplicates(subset=['url'], keep='first')``
  (``yahoo_links_selenium.py:79,174``): 128-bit device hashing proposes
  groups, the host confirms true string equality inside each group, so the
  surviving row set is *provably* identical to the pandas path.
"""

from __future__ import annotations

import os
import time
from typing import Sequence

import numpy as np

from advanced_scrapper_tpu.config import DedupConfig
from advanced_scrapper_tpu.core.hashing import MinHashParams, make_params
from advanced_scrapper_tpu.core.tokenizer import (
    bucket_len,
    bucket_widths,
    encode_blocks,
    to_bytes,
)
from advanced_scrapper_tpu.ops.exact import ExactHasher
from advanced_scrapper_tpu.ops.lsh import (
    borderline_edge_mask,
    fine_edge_thresholds,
    resolve_rep_bands,
    resolve_rep_bands_from_ok,
)
from advanced_scrapper_tpu.ops.minhash import resolve_signature_fn


#: the DECLARED hook edge for ROADMAP item 2's candidate-verification
#: (rerank) tier: in the dedup stage graph (encode → h2d → kernel →
#: candidates → resolve) this names the edge between candidate generation
#: and union-find resolution.  A :attr:`NearDupEngine.rerank_hook`
#: callable ``(raw, sigs, rep_bands, valid) -> rep_bands`` slots in here —
#: BOTH resolution paths (async/estimator-only and the certified one-shot)
#: route their candidate matrix through it before resolving, so a
#: device-batched exact-Jaccard rerank tier becomes a graph edge, not a
#: bespoke rewrite of either path.
RERANK_HOOK_EDGE = "dedup.candidates->dedup.resolve"


_LSH_EPILOGUES: dict = {}


def _lsh_epilogue(name: str):
    """``ops.lsh``'s fused end-of-corpus epilogues, wrapped ONCE in the
    recompile sentinel (``obs/devprof.py``) under ``kernel="dedup_<name>"``
    — so a steady-state epilogue recompile (e.g. a silently-varying
    ``num_articles`` bucket) is as countable as a tile-step one.  Lazy
    (the epilogues are jitted at ``ops.lsh`` import, which pulls jax) and
    memoised (the wrapper is per-process, like the underlying jit
    cache)."""
    fn = _LSH_EPILOGUES.get(name)
    if fn is None:
        from advanced_scrapper_tpu.obs import devprof
        from advanced_scrapper_tpu.ops import lsh

        fn = devprof.instrument_jit(getattr(lsh, name), f"dedup_{name}")
        _LSH_EPILOGUES[name] = fn
    return fn


def _jump_rounds(n: int) -> int:
    r = 1
    while (1 << r) < n:
        r += 1
    return r


def _tile_bs(cfg: DedupConfig, width: int) -> int:
    """Full-tile row count for a width bucket.  ``cfg.batch_size`` keeps
    its pre-bucketing meaning — peak device bytes per dispatch stay
    ``batch_size × block_len`` — so rows scale up as the bucket narrows.
    THE single source of the formula: the encode chunker and
    :meth:`NearDupEngine.prewarm` must draw from the same shape set, or
    prewarming silently compiles a disjoint set and defeats itself."""
    return min(max(cfg.batch_size * cfg.block_len // width, 64), 16384)


def _tile_rows_options(bs: int) -> list[int]:
    """Every row count the greedy chunker can emit for a width bucket:
    the full tile plus the descending power-of-two tail chunks (≥64) —
    the O(log bs) shape set prewarm compiles
    (``core.tokenizer.tile_rows_options``, shared with the matcher's
    screen tile plane)."""
    from advanced_scrapper_tpu.core.tokenizer import tile_rows_options

    return tile_rows_options(bs, 64)


def _prewarm_widths(cfg: DedupConfig) -> list[int]:
    """The chunker's width-bucket set: powers of two below ``block_len``
    (mirroring ``bucket_widths(..., max_bucket=block_len)``) plus
    ``block_len`` itself — the body/long-tail bucket, which need not be a
    power of two and must not be skipped or prewarm misses the dominant
    width.  THE single source for every prewarm (single-device and
    mesh-sharded): a width added to the chunker without landing here
    would silently disjoint the prewarmed set (the PR 9 lesson)."""
    widths = []
    w = 64
    while w < cfg.block_len:
        widths.append(w)
        w *= 2
    widths.append(cfg.block_len)
    return widths


#: dispatch knobs the perf-ledger profile may resolve, with the explicit
#: env key that always wins over a ledger row
_KNOB_PROFILE_FIELDS: tuple[tuple[str, str], ...] = (
    ("put_workers", "ASTPU_DEDUP_PUT_WORKERS"),
    ("dispatch_window", "ASTPU_DEDUP_DISPATCH_WINDOW"),
    ("rerank_tile_rows", "ASTPU_DEDUP_RERANK_TILE_ROWS"),
)


def _resolve_knob_profile(cfg: DedupConfig) -> DedupConfig:
    """Per-platform knob-profile store: fill still-default dispatch knobs
    from the perf ledger's best same-platform sweep row.

    Resolution order per knob (unit-tested in ``tests/test_perf_obs.py``):

    1. explicit env (``ASTPU_DEDUP_PUT_WORKERS`` etc.) — always wins,
       applied here so a directly-constructed ``DedupConfig()`` honours
       it exactly like a ``config.from_env`` one;
    2. a caller-pinned config value (field differs from the dataclass
       default) — the constructor argument is an explicit choice;
    3. the best same-platform row of ``$ASTPU_PERF_LEDGER``
       (``obs.perfdb.best_knob_profile`` — max articles/sec sweep row
       whose platform partition matches this process's jax backend);
    4. the dataclass default (no ledger / no matching row / no knob in
       the winning row's tag) — current constants, unchanged.
    """
    import dataclasses

    defaults = DedupConfig()
    env_updates: dict[str, int] = {}
    open_knobs: list[str] = []
    for f, env_key in _KNOB_PROFILE_FIELDS:
        raw = os.environ.get(env_key)
        if raw is not None:
            try:
                env_updates[f] = int(raw)
            except ValueError:
                pass  # malformed env: leave the field as constructed
            continue
        if getattr(cfg, f) == getattr(defaults, f):
            open_knobs.append(f)
    if env_updates:
        cfg = dataclasses.replace(cfg, **env_updates)
    path = os.environ.get("ASTPU_PERF_LEDGER", "")
    if not path or not os.path.exists(path) or not open_knobs:
        return cfg
    try:
        import jax

        from advanced_scrapper_tpu.obs.perfdb import best_knob_profile

        profile = best_knob_profile(path, jax.devices()[0].platform)
    except Exception:  # a torn/foreign ledger must never fail engine init
        return cfg
    updates = {
        k: v for k, v in profile.items() if k in open_knobs and v
    }
    return dataclasses.replace(cfg, **updates) if updates else cfg


def resolve_put_workers(cfg: DedupConfig) -> int:
    """Effective H2D put-thread count: ``cfg.put_workers``, with 0 meaning
    one put thread (local backends measured fastest with a single put
    thread).  Lives in the engine so production configs and bench
    defaults resolve identically."""
    return cfg.put_workers or 1


class NearDupEngine:
    """Batch near-duplicate detector.

    Long texts are split into overlapping blocks (`core.tokenizer.encode_blocks`)
    so device shapes stay fixed; block signatures are exactly min-combined per
    article. Block batches are padded to a fixed size to avoid recompilation.
    """

    def __init__(self, cfg: DedupConfig | None = None, params: MinHashParams | None = None):
        self.cfg = cfg or DedupConfig()
        self.params = params or make_params(
            num_perm=self.cfg.num_perm,
            num_bands=self.cfg.num_bands,
            shingle_k=self.cfg.shingle_k,
            seed=self.cfg.seed,
        )
        # the sharded plane's compiled programs (tile step, accumulator
        # init, resolve and keys epilogues), keyed on (mesh, kind) —
        # meshes are hashable
        self._sharded_steps: dict = {}
        #: the single-dispatch packed tile step (ops.minhash.
        #: make_fused_tile_step), built lazily — params constant-fold in
        self._fused_step = None
        # per-platform knob-profile resolution (perf-ledger defaults):
        # still-default dispatch knobs pick up the best same-platform
        # sweep row's values; explicit env / caller-pinned fields win
        self.cfg = _resolve_knob_profile(self.cfg)
        #: the rerank tier's slot on :data:`RERANK_HOOK_EDGE` — when set,
        #: every resolution path passes its candidate matrix through it
        #: before union-find (None = pass-through)
        self.rerank_hook = None
        #: whether the LAST corpus's candidates actually passed through
        #: an AUTHORITATIVE tier (settled true-Jaccard verdicts): the
        #: certified path then resolves the rewritten matrix verbatim
        #: instead of re-litigating edges with the estimator-era
        #: exact-verify stage.  False whenever the hook is absent,
        #: bypassed by the skip_rerank brownout, or non-authoritative.
        self._rerank_applied = False
        #: the default precision tier (pipeline/rerank.py) when
        #: ``cfg.rerank`` — kept as an attribute so callers can attach a
        #: persistent index for the borderline ANN re-probe or read the
        #: per-corpus settlement stats; ``rerank_hook = None`` (or
        #: ASTPU_DEDUP_RERANK=0) remains the opt-out
        self.rerank_tier = None
        if self.cfg.rerank:
            from advanced_scrapper_tpu.pipeline.rerank import RerankTier

            self.rerank_tier = RerankTier(self.cfg, self.params)
            self.rerank_hook = self.rerank_tier
        #: optional :class:`~advanced_scrapper_tpu.runtime.admission.
        #: DegradationLadder` — when installed, the engine honours the
        #: declared brownout steps at its decision points: a halved
        #: dispatch window ("shrink_window"), a bypassed rerank tier
        #: ("skip_rerank"), and half the LSH bands on the stream-index
        #: path ("fewer_bands"); each application is counted via
        #: ``ladder.count_effect`` and reverses the moment the step exits
        self.ladder = None
        #: optional per-tile observer ``(dict) -> None`` on the dispatch
        #: executor loop (tile index, rows, width, h2d_bytes, put/dispatch
        #: ms) — ``tools/profile_hostpath.py --device`` renders it as a
        #: timeline; None = no per-tile host work
        self.dispatch_probe = None
        #: tiles dispatched by the most recent corpus (set by
        #: ``_accumulate_device``; the per-tile traffic gate divides the
        #: device counters by this)
        self.last_tiles = 0
        self._instrument()
        # ASTPU_DEDUP_PREWARM=N; initialises jax.  1 = default (one
        # batch_size corpus); >1 pins the expected per-corpus article
        # count, whose bucket the step set keys on.
        if self.cfg.prewarm:
            self.prewarm(None if self.cfg.prewarm == 1 else self.cfg.prewarm)

    def _instrument(self) -> None:
        """Telemetry handles (no-ops when disabled) + the production home
        of the once-orphaned ``StepTimer``: every device block-batch
        dispatch lands in it, so ``step_summary()`` answers "what is the
        per-dispatch latency right now" on a live engine."""
        from advanced_scrapper_tpu.obs import telemetry
        from advanced_scrapper_tpu.obs.profiler import StepTimer

        self.step_timer = StepTimer(
            histogram=telemetry.histogram(
                "astpu_dedup_step_seconds", "device block-batch dispatch latency"
            )
        )
        self._m_batches = telemetry.counter(
            "astpu_dedup_batches_total", "device block batches dispatched"
        )
        self._m_docs = {
            regime: telemetry.counter(
                "astpu_dedup_docs_total",
                "documents entering dedup",
                regime=regime,
            )
            for regime in ("oneshot", "async", "sharded")
        }
        self._m_dups = {
            regime: telemetry.counter(
                "astpu_dedup_dups_total",
                "documents resolved as near-duplicates",
                regime=regime,
            )
            for regime in ("oneshot", "sharded")
        }
        self._m_ratio = {
            regime: telemetry.gauge(
                "astpu_dedup_ratio",
                "last corpus' duplicate fraction",
                regime=regime,
            )
            for regime in ("oneshot", "sharded")
        }
        self._m_cand = telemetry.counter(
            "astpu_dedup_candidate_pairs_total",
            "LSH candidate (row, band) hits examined by the certified "
            "one-shot resolution (async/sharded never sync candidates)",
        )
        self._m_borderline = telemetry.counter(
            "astpu_dedup_borderline_edges_total",
            "estimator-fragile edges flagged for exact confirmation",
        )
        self._m_exact_checks = telemetry.counter(
            "astpu_dedup_exact_checks_total",
            "exact shingle-set Jaccard confirmations run",
        )

    def step_summary(self) -> dict:
        """Rolling per-dispatch latency/throughput (``StepTimer.summary``)."""
        return self.step_timer.summary()

    def _count_result(self, regime: str, n: int, reps: np.ndarray) -> None:
        """Host-side dedup-ratio accounting — only for paths that already
        synced ``reps`` (the async path never syncs; it counts docs only).
        The numpy reduction is metric-only work, so it is skipped entirely
        when telemetry handed out no-op handles (the disabled cost model)."""
        from advanced_scrapper_tpu.obs.telemetry import NOOP

        if n == 0 or self._m_dups[regime] is NOOP:
            return
        dups = int((reps[:n] != np.arange(n)).sum())
        self._m_dups[regime].inc(dups)
        self._m_ratio[regime].set(dups / n)

    def signatures(self, texts: Sequence[str | bytes]) -> np.ndarray:
        """uint32[N, num_perm] MinHash signatures (blockwise, batched).

        With ``cfg.backend == "oph"`` block signatures are the *raw* OPH
        form (empty bins ``U32_MAX``) so the per-article segment-min combine
        stays exact; densification runs once after the combine (see
        ``ops/oph.py`` for why that order is load-bearing).
        """
        if len(texts) == 0:
            return np.zeros((0, self.params.num_perm), np.uint32)
        from advanced_scrapper_tpu.obs import stages, trace

        tid = trace.new_trace_id()
        sigs = self._signatures_device(texts, trace_id=tid)
        with stages.timed("kernel"), trace.span(
            "dedup.readback", trace=tid, docs=len(texts)
        ):  # readback sync: the device drains here
            return np.asarray(sigs)[: len(texts)]

    def _signatures_device(self, texts: Sequence[str | bytes], trace_id=None):
        """Device ``uint32[bucket_len(N), num_perm]`` combined signatures
        (densified for the OPH backend): :meth:`_accumulate_device` plus
        the one densify dispatch the raw OPH accumulator defers.  The
        resolution paths skip this and fold the densify into their fused
        epilogue instead (:meth:`_prepare`)."""
        running, _n_bucket, use_oph = self._accumulate_device(
            texts, trace_id=trace_id
        )
        if use_oph:
            from advanced_scrapper_tpu.obs import stages
            from advanced_scrapper_tpu.ops.oph import densify

            running = densify(running)
            stages.count_dispatch("dedup")
        return running

    def _get_fused_step(self):
        """The engine's single-dispatch tile step (params constant-folded;
        built once — jit caches per static (rows, width, num_articles)).
        Wrapped in the recompile sentinel (``obs/devprof.py``): every
        jit-cache miss counts on ``astpu_jit_compiles_total{kernel=
        "dedup_fused_tile"}`` — prewarm/warmup compiles are expected
        counts, a steady-state increment is the stall prewarm exists to
        prevent, tier-1-asserted at zero."""
        step = self._fused_step
        if step is None:
            from advanced_scrapper_tpu.obs import devprof
            from advanced_scrapper_tpu.ops.minhash import make_fused_tile_step

            step = devprof.instrument_jit(
                make_fused_tile_step(self.params, self.cfg.backend),
                "dedup_fused_tile",
            )
            self._fused_step = step
        return step

    def prewarm(self, n_articles: int | None = None) -> int:
        """Compile the packed tile-step shape set ahead of the first
        corpus: every width bucket's full tile plus its descending
        power-of-two tail chunks — the same O(log bs)-per-width shape set
        ``_accumulate_device`` draws from.  Returns the number of shape
        variants compiled.  Initialises the jax backend.

        ``n_articles`` pins the article-axis bucket (default: one
        ``batch_size`` corpus) and the pin is LOAD-BEARING: the fused
        step is compiled per static ``num_articles = bucket_len(N)``, so
        only corpora whose article count buckets the same skip their
        per-shape compiles — prewarm with the corpus size you will
        actually stream (``ASTPU_DEDUP_PREWARM=<count>``).  With
        the persistent compile cache (``core.mesh.
        maybe_enable_compile_cache``) the compiles persist across
        processes and later prewarms (any bucket) are cache loads.
        """
        import jax.numpy as jnp

        from advanced_scrapper_tpu.core.mesh import maybe_enable_compile_cache
        from advanced_scrapper_tpu.ops.pack import packed_nbytes
        from advanced_scrapper_tpu.ops.shingle import U32_MAX

        maybe_enable_compile_cache()
        cfg = self.cfg
        n_bucket = bucket_len(
            n_articles if n_articles else cfg.batch_size, min_bucket=64
        )
        step = self._get_fused_step()
        compiled = 0
        for w in _prewarm_widths(cfg):
            # same derivation as the encode chunker (_tile_bs /
            # _tile_rows_options) — shared helpers, never re-derived here
            for rows in _tile_rows_options(_tile_bs(cfg, w)):
                running = jnp.full(
                    (n_bucket, self.params.num_perm), U32_MAX, jnp.uint32
                )
                packed = jnp.zeros((packed_nbytes(rows, w),), jnp.uint8)
                step(
                    running, packed, rows=rows, width=w, num_articles=n_bucket
                ).block_until_ready()
                compiled += 1
        if self.rerank_tier is not None:
            # the precision tier's settle tiles ride the same shared
            # tile_rows_options derivation — prewarm them (plus the
            # finalize) here so a first real corpus leaves the recompile
            # sentinel flat
            compiled += self.rerank_tier.prewarm()
        return compiled

    def _host_tiles(self, raw: list, trace_id=None):
        """Width-bucketed power-of-two tile generator ``(tok, lens,
        owners)`` — THE shared encode chunker of every packed dedup
        plane: the single-device executor (:meth:`_accumulate_device`)
        and the mesh-sharded one (:meth:`_accumulate_device_sharded`)
        both draw tiles from here, so their shape sets — and the
        prewarmed sets (``_tile_bs``/``_tile_rows_options``, shared) —
        can never silently diverge.  The eager prologue (vectorised
        range bucketing) runs on the caller's thread; per-group encode
        and greedy chunking run lazily where the consumer pulls."""
        cfg, params = self.cfg, self.params

        from advanced_scrapper_tpu.cpu.hostbatch import (
            block_counts,
            encode_blocks_ranges,
        )
        from advanced_scrapper_tpu.obs import stages, trace

        n = len(raw)
        overlap = params.shingle_k - 1
        stride = cfg.block_len - overlap
        with stages.timed("encode"), trace.span(
            "dedup.encode", trace=trace_id, docs=n
        ):
            # Vectorised RANGE bucketing, one numpy pass, no per-article
            # Python loop.  Every document becomes one TAIL range (the
            # whole doc when it fits a single block) routed to the
            # power-of-two width bucket of the tail's length, plus — for
            # documents longer than block_len — one BODY range that encodes
            # as exactly its m−1 FULL blocks at block_len.  The block SET
            # (bytes, per-block lengths, owners) is identical to a
            # whole-document split, but the tail rides a fitted row instead
            # of a block_len-wide one: tail padding alone was ~30% of the
            # ragged regime's dispatched bytes (a 12 kB article's last
            # block averages ~50% zeros at 4096 width).  The corpus
            # flattens into ONE blob + offset table; width groups are cut
            # straight out of it by the range-native encoder.
            lens = np.fromiter(map(len, raw), np.int64, count=n)
            doc_off = np.zeros((n + 1,), dtype=np.int64)
            np.cumsum(lens, out=doc_off[1:])
            blob = b"".join(raw)
            m = block_counts(lens, cfg.block_len, overlap)
            tail_start = (m - 1) * stride
            tail_len = lens - tail_start
            body_sel = np.flatnonzero(m > 1)
            range_starts = np.concatenate(
                [doc_off[:n] + tail_start, doc_off[:n][body_sel]]
            )
            range_lens = np.concatenate(
                [tail_len, tail_start[body_sel] + overlap]
            )
            range_owner = np.concatenate(
                [np.arange(n, dtype=np.int64), body_sel]
            )
            range_width = np.concatenate([
                bucket_widths(tail_len, max_bucket=cfg.block_len),
                np.full((len(body_sel),), cfg.block_len, np.int64),
            ])
            order = np.argsort(range_width, kind="stable")
            sorted_w = range_width[order]
            n_ranges = len(order)
            group_lo = (
                np.flatnonzero(np.r_[True, sorted_w[1:] != sorted_w[:-1]])
                if n_ranges
                else np.zeros((0,), np.int64)
            )

        def host_batches():
            # a generator: encode stays lazy, overlapping device dispatch
            # in both consumption modes below
            for g, lo in enumerate(group_lo):
                hi = group_lo[g + 1] if g + 1 < len(group_lo) else n_ranges
                idx = order[lo:hi]
                w = int(sorted_w[lo])
                with stages.timed("encode"):
                    r_starts = range_starts[idx]
                    r_lens = range_lens[idx]
                    enc = encode_blocks_ranges(
                        blob, r_starts, r_lens,
                        block_counts(r_lens, w, overlap), w, overlap,
                    )
                    if enc is None:  # no compiler: per-group Python slices
                        r_doc = range_owner[idx]
                        rel = r_starts - doc_off[r_doc]
                        enc = encode_blocks(
                            [
                                raw[d][s : s + ln]
                                for d, s, ln in zip(
                                    r_doc.tolist(), rel.tolist(),
                                    r_lens.tolist(),
                                )
                            ],
                            w,
                            overlap=overlap,
                        )
                    tok, blk_lens, owners_local = enc
                    owners = range_owner[idx].astype(np.int32)[owners_local]
                n_blocks = tok.shape[0]
                bs = _tile_bs(cfg, w)  # shared with prewarm's shape set
                # Greedy power-of-two row chunks: full bs tiles, then the
                # tail decomposes into descending power-of-two dispatches
                # (≥64; the last one zero-pads).  A width group with 33
                # leftover blocks must not dispatch (and compute!) a
                # 16384-row tile — measured 2.5× of the ragged regime's
                # device bytes were tail padding at 2k articles.  Chunks,
                # not one bucketed tail tile: every corpus then draws from
                # the SAME O(log bs) shape set per width, so one warm corpus
                # compiles (almost) everything — a per-corpus bucketed tail
                # would trickle fresh shapes (and recompiles) into every
                # corpus that follows.
                start = 0
                while start < n_blocks:
                    remaining = n_blocks - start
                    rows = bs
                    if remaining < bs:
                        rows = 64
                        while rows * 2 <= remaining:
                            rows *= 2
                    t = tok[start : start + rows]
                    l = blk_lens[start : start + rows]
                    o = owners[start : start + rows]
                    if t.shape[0] < rows:
                        pad = rows - t.shape[0]
                        t = np.concatenate([t, np.zeros((pad, w), np.uint8)])
                        l = np.concatenate([l, np.zeros((pad,), np.int32)])
                        o = np.concatenate([o, np.zeros((pad,), np.int32)])
                    yield (t, l, o)
                    start += rows

        return host_batches()

    def _accumulate_device(self, texts: Sequence[str | bytes], trace_id=None):
        """``(running, n_bucket, use_oph)``: the device-resident combined
        signature accumulator (RAW for the OPH backend — densify happens
        once downstream) after streaming every tile through the pipelined
        dispatch executor.

        The ragged corpus is grouped by power-of-two *width buckets* (a doc
        of 700 B rides a 1024-wide row, not a block_len-wide one) and docs
        longer than ``cfg.block_len`` split blockwise (:meth:`_host_tiles`,
        the shared chunker); every group folds into one running per-article
        minimum on device.  Three properties are load-bearing for
        throughput on an H2D-constrained link (the ragged regime is
        transfer-bound, not compute-bound — DESIGN.md §5):

        - bucketing cuts padded bytes on realistic length mixes vs
          one-width encoding, and padding that remains is zeros (cheap for
          a compressing transport);
        - each tile crosses the boundary as ONE packed ``device_put``
          (``ops/pack.py``) and ONE fused jitted dispatch with the
          accumulator donated (``ops.minhash.make_fused_tile_step``);
        - puts queue ahead of compute (async dispatch, no host sync until
          the caller materialises a result), and the
          encode→pack→put→dispatch stages run pipelined with a bounded
          in-flight window (``pipeline/dispatch.py``).

        Rows past ``len(texts)`` are untouched ⇒ all-``U32_MAX``.
        """
        cfg, params = self.cfg, self.params
        use_oph = cfg.backend == "oph"
        resolve_signature_fn(cfg.backend)  # validates the name up front

        import jax
        import jax.numpy as jnp

        from advanced_scrapper_tpu.core.mesh import maybe_enable_compile_cache

        maybe_enable_compile_cache()

        from advanced_scrapper_tpu.obs import stages, trace
        from advanced_scrapper_tpu.ops.pack import pack_tile
        from advanced_scrapper_tpu.ops.shingle import U32_MAX

        tid = trace_id or trace.new_trace_id()
        raw = [to_bytes(t) for t in texts]
        n = len(raw)
        # Bucket the article count so combine compiles O(log N) variants, not
        # one per corpus size (same trick as the block-length axis).
        n_bucket = bucket_len(n, min_bucket=64)
        host_batches = self._host_tiles(raw, trace_id=tid)

        # The tile plane rides the pipelined dispatch executor
        # (pipeline/dispatch.py): a pack stage draws width-group tiles off
        # the encode generator, a put pool (ASTPU_DEDUP_PUT_WORKERS; 0 =
        # transport auto — resolve_put_workers) overlaps H2D round trips,
        # and this thread drains the depth-N staged window and dispatches.
        # The min-combine is order-independent, so out-of-order arrival
        # from the pool never matters.
        from advanced_scrapper_tpu.obs import devprof
        from advanced_scrapper_tpu.pipeline.dispatch import (
            PipelinedDispatcher,
            dispatch_with_oom_backoff,
        )

        put_workers = resolve_put_workers(cfg)
        probe = self.dispatch_probe
        step = self._get_fused_step()

        def pack(batch):
            t, l, o = batch
            with stages.timed("encode"):  # host memcpy: encode plane
                return pack_tile(t, l, o), t.shape[0], t.shape[1]

        def put(item):
            buf, rows, w = item
            t0 = time.perf_counter()
            with stages.timed("h2d"):
                dev = jax.device_put(buf)
            stages.count_device_put(buf.nbytes, "dedup")
            return dev, rows, w, buf.nbytes, time.perf_counter() - t0

        def dispatch(running, item):
            dev, rows, w, _nb, _pms = item
            # latency ledger: per-dispatch submit cost by kernel/shape
            with devprof.dispatch_span(
                "dedup_fused_tile", rows=rows, width=w, trace=tid
            ):
                out = step(
                    running, dev, rows=rows, width=w, num_articles=n_bucket
                )
            # counted on success, INSIDE the fn: the OOM-backoff
            # ladder then ledgers exactly its leaf dispatches
            stages.count_dispatch("dedup")
            return out

        def split_packed(item):
            """Device-OOM halving: D2H the packed buffer, re-pack as
            two half-row tiles, re-put.  Each sub-item carries its
            TRUE row count — on an odd-row tile (non-power-of-two
            ``block_len`` configs) the halves differ by one row, and
            a mislabeled count would shift the trailer decode.  For
            the default power-of-two shapes the halves stay inside
            the prewarmed set (no recompile storm); odd shapes may
            compile a backoff variant once.  The extra puts/bytes
            land on the always-on device ledger like any transfer."""
            dev, rows, w, _nb, _pms = item
            buf = np.asarray(dev)
            tok = buf[: rows * w].reshape(rows, w)
            trailer = buf[rows * w :].view("<i4").reshape(2, rows)
            half = rows // 2
            out = []
            for lo, hi in ((0, half), (half, rows)):
                sl = slice(lo, hi)
                pb = pack_tile(tok[sl], trailer[0, sl], trailer[1, sl])
                with stages.timed("h2d"):
                    d = jax.device_put(pb)
                stages.count_device_put(pb.nbytes, "dedup")
                out.append((d, hi - lo, w, pb.nbytes, 0.0))
            return out

        running = jnp.full((n_bucket, params.num_perm), U32_MAX, jnp.uint32)
        dispatched = 0
        window = cfg.dispatch_window
        if self.ladder is not None and self.ladder.active("shrink_window"):
            # brownout step 1: halve the in-flight dispatch window —
            # less resident device memory, more backpressure upstream
            from advanced_scrapper_tpu.pipeline.dispatch import (
                resolve_dispatch_window,
            )

            window = max(
                1, resolve_dispatch_window(cfg.dispatch_window, put_workers) // 2
            )
            self.ladder.count_effect("shrink_window")
        pipe = PipelinedDispatcher(
            host_batches,
            pack=pack,
            put=put,
            put_workers=put_workers,
            window=window,
        )
        try:
            for item in pipe:
                _dev, rows, w, nbytes, put_s = item
                t0 = time.perf_counter()
                with stages.timed("kernel"), self.step_timer.step(rows):
                    # async dispatch; device waits land here.
                    # RESOURCE_EXHAUSTED halves the tile (re-pack, re-put,
                    # retry — byte-identical fold) down to the 64-row
                    # floor, then fails cleanly
                    running = dispatch_with_oom_backoff(
                        dispatch, running, item,
                        split=split_packed,
                        rows_of=lambda it: it[1],
                    )
                if probe is not None:
                    probe(
                        {
                            "tile": dispatched,
                            "rows": rows,
                            "width": int(w),
                            "h2d_bytes": int(nbytes),
                            "put_ms": round(put_s * 1e3, 3),
                            "dispatch_ms": round(
                                (time.perf_counter() - t0) * 1e3, 3
                            ),
                        }
                    )
                dispatched += 1
        finally:
            pipe.close()
        self._m_batches.inc(dispatched)
        self.last_tiles = dispatched
        if trace.RECORDER.active:
            trace.record(
                "span", "dedup.dispatch", trace=tid, batches=dispatched, docs=n
            )
        return running, n_bucket, use_oph

    def _fine_salt(self) -> np.ndarray:
        """``subband_salt(cand_subbands)`` (validated against num_perm) or
        a zero-length array — the fused epilogues select the fine-band
        variant by its static shape."""
        cs = self.cfg.cand_subbands
        if not cs:
            return np.zeros((0,), np.uint32)
        if self.params.num_perm % cs:
            raise ValueError(
                f"cand_subbands {cs} must divide num_perm "
                f"{self.params.num_perm} (each sub-band folds "
                "num_perm/cand_subbands signature rows)"
            )
        from advanced_scrapper_tpu.ops.lsh import subband_salt

        return subband_salt(cs)

    def _valid_device(self, raw: list, n_bucket: int):
        """Device ``bool[n_bucket]`` shingle-eligibility mask (counted as
        the one per-corpus put the epilogue needs beside the tiles)."""
        import jax

        from advanced_scrapper_tpu.obs import stages

        n = len(raw)
        lens = np.fromiter((len(r) for r in raw), np.int64, count=n)
        valid = np.zeros((n_bucket,), bool)
        valid[:n] = lens >= self.params.shingle_k
        dev = jax.device_put(valid)
        stages.count_device_put(valid.nbytes, "dedup")
        return dev

    def _prepare(self, texts: Sequence[str | bytes]):
        """Shared front half of both resolution paths: encode → device
        signature accumulator → ONE fused epilogue dispatch (OPH densify +
        coarse/fine candidate keys + per-band candidates), so a full
        corpus is ``tiles × 1`` dispatches plus this epilogue before
        resolution."""
        from advanced_scrapper_tpu.obs import stages, trace

        fused_candidate_epilogue = _lsh_epilogue("fused_candidate_epilogue")
        tid = trace.new_trace_id()
        n = len(texts)
        raw = [to_bytes(t) for t in texts]  # encode once; identity on bytes
        running, n_bucket, use_oph = self._accumulate_device(
            raw, trace_id=tid
        )
        valid = self._valid_device(raw, n_bucket)
        with stages.timed("resolve"), trace.span(
            "dedup.candidates", trace=tid, docs=n
        ):
            sigs, keys, rep_bands = fused_candidate_epilogue(
                running,
                valid,
                np.asarray(self.params.band_salt),
                self._fine_salt(),
                densify_oph=use_oph,
            )
            stages.count_dispatch("dedup")
        self._rerank_applied = False
        if self.rerank_hook is not None:
            if self.ladder is not None and self.ladder.active("skip_rerank"):
                # brownout step 2: the precision tier is bypassed under
                # sustained pressure — candidates pass through unreranked
                # (counted; reverses the moment the step exits)
                self.ladder.count_effect("skip_rerank")
            else:
                # the declared RERANK_HOOK_EDGE: candidates flow through
                # the rerank tier before EITHER resolution path sees them
                with trace.span("dedup.rerank", trace=tid, docs=n):
                    rep_bands = self.rerank_hook(raw, sigs, rep_bands, valid)
                self._rerank_applied = bool(
                    getattr(self.rerank_hook, "authoritative", False)
                )
        return raw, sigs, keys, valid, rep_bands, n_bucket, tid

    def dedup_reps_async(self, texts: Sequence[str | bytes], *, _regime: str = "async"):
        """Dispatch the full dedup and return the DEVICE ``int32[bucket]``
        rep array without syncing — everything from encode to resolve is
        async, so a caller streaming multiple corpora overlaps corpus i+1's
        H2D/compute with corpus i's readback (the production firehose
        regime; one-shot callers use :meth:`dedup_reps`).  Rows past
        ``len(texts)`` are padding (invalid ⇒ self-assigned).

        This path never syncs, so borderline edges are handled by the
        estimator-only ``fine_margin`` bar — the exact-Jaccard
        confirmation stage needs a host round trip and lives in the
        one-shot :meth:`dedup_reps` (measured trade in DESIGN.md §2e).
        """
        # Device-resident end to end: combined signatures never round-trip to
        # the host (the sig D2H + re-H2D bounce cost ~0.3 s per 8k articles
        # on a remote chip); the only D2H is the final int32[N] reps.
        from advanced_scrapper_tpu.obs import stages, trace

        if self.rerank_hook is not None:
            # the declared RERANK_HOOK_EDGE needs the candidate matrix at
            # the host boundary → the two-stage split (one extra dispatch)
            _raw, sigs, keys, valid, rep_bands, n_bucket, tid = self._prepare(
                texts
            )
            self._m_docs[_regime].inc(len(texts))
            with stages.timed("resolve"), trace.span(
                "dedup.resolve", trace=tid, regime=_regime, docs=len(texts)
            ):
                if self._rerank_applied:
                    # an authoritative tier rewrote the matrix: its cells
                    # are settled TRUE-Jaccard cluster edges, already
                    # exact-verified where it mattered — re-screening them
                    # by estimator agreement would re-drop precisely the
                    # true pairs whose signatures underestimate (the tier
                    # keeps them on settled evidence), so resolve trusts
                    # every non-self cell
                    rb_host = np.asarray(rep_bands)
                    ok = rb_host != np.arange(
                        rb_host.shape[0], dtype=rb_host.dtype
                    )[:, None]
                    rep = resolve_rep_bands_from_ok(
                        rep_bands, ok, valid,
                        jump_rounds=_jump_rounds(n_bucket),
                    )
                    stages.count_dispatch("dedup")
                    return rep
                if self.cfg.cand_subbands and self.cfg.fine_margin:
                    thr = fine_edge_thresholds(
                        rep_bands,
                        keys,
                        self.cfg.sim_threshold,
                        self.cfg.fine_margin,
                        num_coarse=self.params.num_bands,
                    )
                    stages.count_dispatch("dedup")
                else:
                    thr = self.cfg.sim_threshold
                rep = resolve_rep_bands(
                    rep_bands, sigs, valid, thr,
                    jump_rounds=_jump_rounds(n_bucket),
                )
                stages.count_dispatch("dedup")
                return rep
        # no hook: the WHOLE resolution is one fused dispatch — a full
        # corpus is tiles × 1 dispatches plus this epilogue
        fused_resolve_epilogue = _lsh_epilogue("fused_resolve_epilogue")

        tid = trace.new_trace_id()
        raw = [to_bytes(t) for t in texts]
        running, n_bucket, use_oph = self._accumulate_device(
            raw, trace_id=tid
        )
        valid = self._valid_device(raw, n_bucket)
        # _regime: the one-shot API's estimator-only branch delegates here —
        # its documents must count as "oneshot", not inflate the async series
        self._m_docs[_regime].inc(len(texts))
        with stages.timed("resolve"), trace.span(
            "dedup.resolve", trace=tid, regime=_regime, docs=len(texts)
        ):
            rep = fused_resolve_epilogue(
                running,
                valid,
                np.asarray(self.params.band_salt),
                self._fine_salt(),
                self.cfg.sim_threshold,
                self.cfg.fine_margin,
                densify_oph=use_oph,
                num_coarse=self.params.num_bands,
                jump_rounds=_jump_rounds(n_bucket),
                use_fine_margin=bool(
                    self.cfg.cand_subbands and self.cfg.fine_margin
                ),
            )
            stages.count_dispatch("dedup")
            return rep

    # -- mesh-sharded packed plane (pod-scale dedup) ---------------------------

    def _get_sharded_fused_step(self, mesh):
        """The mesh's shard_map'd single-dispatch tile step (params
        constant-folded, accumulator donated per shard) — cached per
        mesh; jit then caches per static (rows, width, num_articles),
        the same shape set :meth:`prewarm_sharded` compiles."""
        key = (mesh, "fused")
        step = self._sharded_steps.get(key)
        if step is None:
            from advanced_scrapper_tpu.obs import devprof
            from advanced_scrapper_tpu.parallel.sharded_packed import (
                make_sharded_fused_tile_step,
            )

            step = devprof.instrument_jit(
                make_sharded_fused_tile_step(
                    mesh, self.params, self.cfg.backend
                ),
                "sharded_fused_tile",
            )
            self._sharded_steps[key] = step
        return step

    def _get_sharded_init(self, mesh):
        key = (mesh, "init")
        init = self._sharded_steps.get(key)
        if init is None:
            from advanced_scrapper_tpu.parallel.sharded_packed import (
                make_sharded_accumulator_init,
            )

            init = make_sharded_accumulator_init(mesh, self.params.num_perm)
            self._sharded_steps[key] = init
        return init

    def _get_sharded_epilogue(self, mesh):
        """The end-of-corpus combine+resolve dispatch (``pmin`` across
        shards, then the async path's estimator-only resolution)."""
        key = (mesh, "resolve")
        epi = self._sharded_steps.get(key)
        if epi is None:
            from advanced_scrapper_tpu.parallel.sharded_packed import (
                make_sharded_resolve_epilogue,
            )

            epi = make_sharded_resolve_epilogue(
                mesh,
                self.params,
                threshold=self.cfg.sim_threshold,
                fine_margin=self.cfg.fine_margin,
                fine_salt=self._fine_salt(),
                backend=self.cfg.backend,
            )
            self._sharded_steps[key] = epi
        return epi

    def _get_sharded_keys_epilogue(self, mesh):
        key = (mesh, "keys")
        epi = self._sharded_steps.get(key)
        if epi is None:
            from advanced_scrapper_tpu.parallel.sharded_packed import (
                make_sharded_keys_epilogue,
            )

            epi = make_sharded_keys_epilogue(mesh, self.params, self.cfg.backend)
            self._sharded_steps[key] = epi
        return epi

    def _sharded_tile_groups(self, tiles, nsh: int, shards=None):
        """Group the shared chunker's same-shape tiles into per-shard
        groups of ``nsh`` — one group = one partitioned dispatch, each
        shard owning one tile.  The min-combine is order- and
        placement-independent, so which shard folds which tile never
        shows in the output.  A shape's leftover group pads with zero
        tiles (lens 0 ⇒ all-``U32_MAX`` signatures, the min identity —
        exactly how in-tile padding rows already behave), so every
        shard's ledger stays uniform: tiles + 1 puts, tiles + 1
        dispatches per corpus, per shard.  Each group handed on counts
        one tile slot on each of ``shards`` (default: all), and a slot
        that holds a padding tile counts as one
        (``stages.sharded_tile_counters``)."""
        from advanced_scrapper_tpu.obs import stages

        shards = range(nsh) if shards is None else shards

        def counted(group, real: int):
            for s in shards:
                stages.count_sharded_tile_slot(s, padded=s >= real)
            return group

        pending: dict = {}
        for t, l, o in tiles:
            shape = (t.shape[0], t.shape[1])
            bucket = pending.setdefault(shape, [])
            bucket.append((t, l, o))
            if len(bucket) == nsh:
                yield counted((shape, pending.pop(shape)), nsh)
        for (rows, w), bucket in list(pending.items()):
            real = len(bucket)
            while len(bucket) < nsh:
                bucket.append(
                    (
                        np.zeros((rows, w), np.uint8),
                        np.zeros((rows,), np.int32),
                        np.zeros((rows,), np.int32),
                    )
                )
            yield counted(((rows, w), bucket), real)

    def _accumulate_device_sharded(self, raw: list, mesh, trace_id=None):
        """``(running, n_bucket, use_oph)`` — the sharded twin of
        :meth:`_accumulate_device`: the same shared chunker feeds the
        same pipelined executor (``pipeline/dispatch.py``, a sharded
        source on the one graph), but each tile group crosses H2D as one
        packed ``device_put`` PER SHARD (this host puts its local shards
        only) assembled into a global dim-0-sharded buffer — zero-copy —
        and dispatches as ONE partitioned fused step that folds every
        shard's tile into its own DONATED accumulator row.  Per-shard
        ledger (``shard=`` label on the always-on device counters):
        exactly tiles + 1 puts and tiles + 1 dispatches per corpus, the
        single-device plane's contract applied at pod scale.  ``raw``
        is the already-``to_bytes``-converted corpus (both callers
        convert once at their boundary)."""
        cfg, params = self.cfg, self.params
        use_oph = cfg.backend == "oph"
        resolve_signature_fn(cfg.backend)  # validates the name up front

        import jax

        from advanced_scrapper_tpu.core.mesh import maybe_enable_compile_cache

        maybe_enable_compile_cache()

        from advanced_scrapper_tpu.obs import stages, trace
        from advanced_scrapper_tpu.ops.pack import pack_tile
        from advanced_scrapper_tpu.parallel.sharded_packed import (
            assemble_packed_tiles,
            local_shard_rows,
            mesh_num_shards,
            shard_row_devices,
        )
        from advanced_scrapper_tpu.obs import devprof
        from advanced_scrapper_tpu.pipeline.dispatch import PipelinedDispatcher

        tid = trace_id or trace.new_trace_id()
        n = len(raw)
        n_bucket = bucket_len(n, min_bucket=64)
        nsh = mesh_num_shards(mesh)
        devices = shard_row_devices(mesh)
        local_rows = local_shard_rows(mesh)
        step = self._get_sharded_fused_step(mesh)

        from advanced_scrapper_tpu.ops.pack import packed_nbytes

        def pack(group):
            (rows, w), batch = group
            with stages.timed("encode"):  # host memcpy: encode plane
                # LOCAL shards only: a remote shard's tile is packed (and
                # put) by the host that owns it — packing all n_shards
                # here would burn encode-plane memcpy on buffers this
                # host immediately discards
                bufs = {s: pack_tile(*batch[s]) for s in local_rows}
            return bufs, rows, w

        def put(item):
            bufs, rows, w = item
            t0 = time.perf_counter()
            nb = packed_nbytes(rows, w)  # uniform across shards
            with stages.timed("h2d"):
                shards = []
                for s in local_rows:
                    # one put per shard per tile, onto the device that
                    # owns that accumulator row (shard_row_devices —
                    # derived from the sharding's index map)
                    shards.append(jax.device_put(bufs[s][None], devices[s]))
                    stages.count_device_put(
                        bufs[s].nbytes, "sharded", shard=s
                    )
                packed = assemble_packed_tiles(mesh, shards, nb)
            nbytes = sum(bufs[s].nbytes for s in local_rows)
            return packed, rows, w, nbytes, time.perf_counter() - t0

        def dispatch(running, item):
            packed, rows, w, _nb, _pms = item
            # ONE latency observation per partitioned launch (labeling it
            # per shard would count the same wall clock nsh times); the
            # per-shard truth lives in the put/dispatch count ledger below
            with devprof.dispatch_span(
                "sharded_fused_tile", rows=rows, width=w, trace=tid
            ):
                out = step(
                    running, packed, rows=rows, width=w, num_articles=n_bucket
                )
            # one partitioned launch = one execution per shard
            for s in local_rows:
                stages.count_dispatch("sharded", shard=s)
            return out

        running = self._get_sharded_init(mesh)(num_articles=n_bucket)
        probe = self.dispatch_probe
        dispatched = 0
        # the whole tile loop, the chunker's eager prologue included
        with stages.timed("sharded_tiles"), trace.span(
            "dedup.sharded.tiles", trace=tid, docs=n, shards=nsh
        ):
            tiles = self._sharded_tile_groups(
                self._host_tiles(raw, trace_id=tid), nsh, local_rows
            )
            pipe = PipelinedDispatcher(
                tiles,
                pack=pack,
                put=put,
                put_workers=resolve_put_workers(cfg),
                window=cfg.dispatch_window,
                name="dedup.sharded.h2d",
            )
            try:
                for item in pipe:
                    t0 = time.perf_counter()
                    rows = int(item[1])
                    with stages.timed("kernel"), self.step_timer.step(rows * nsh):
                        running = dispatch(running, item)
                    if probe is not None:
                        probe(
                            {
                                "tile": dispatched,
                                "rows": rows,
                                "width": int(item[2]),
                                "shards": nsh,
                                "h2d_bytes": int(item[3]),
                                "put_ms": round(item[4] * 1e3, 3),
                                "dispatch_ms": round(
                                    (time.perf_counter() - t0) * 1e3, 3
                                ),
                            }
                        )
                    dispatched += 1
            finally:
                pipe.close()
        self._m_batches.inc(dispatched)
        self.last_tiles = dispatched
        if trace.RECORDER.active:
            trace.record(
                "span", "dedup.dispatch", trace=tid,
                batches=dispatched, docs=n, shards=nsh,
            )
        return running, n_bucket, use_oph

    def _valid_device_sharded(self, raw: list, n_bucket: int, mesh):
        """Replicated device ``bool[n_bucket]`` eligibility mask — the
        sharded twin of :meth:`_valid_device` (one replica lands on every
        shard, so the ledger counts one put per shard)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from advanced_scrapper_tpu.obs import stages
        from advanced_scrapper_tpu.parallel.sharded_packed import (
            local_shard_rows,
        )

        n = len(raw)
        lens = np.fromiter((len(r) for r in raw), np.int64, count=n)
        valid = np.zeros((n_bucket,), bool)
        valid[:n] = lens >= self.params.shingle_k
        dev = jax.device_put(valid, NamedSharding(mesh, P(None)))
        for s in local_shard_rows(mesh):
            stages.count_device_put(valid.nbytes, "sharded", shard=s)
        return dev

    def prewarm_sharded(self, mesh, n_articles: int | None = None) -> int:
        """Compile the sharded packed plane's (mesh, bucket, rows) shape
        set ahead of the first corpus — the sharded twin of
        :meth:`prewarm`, drawing from the SAME derivation
        (``_prewarm_widths`` × ``_tile_bs``/``_tile_rows_options``) the
        shared chunker emits, so the two shape sets cannot silently
        disjoint (the PR 9 lesson, jit-cache-asserted in tier-1).  Also
        compiles the end-of-corpus resolve epilogue for the bucket.
        With the persistent compile cache the compiles persist across
        processes.  Returns the number of shape variants compiled."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from advanced_scrapper_tpu.core.mesh import maybe_enable_compile_cache
        from advanced_scrapper_tpu.ops.pack import packed_nbytes
        from advanced_scrapper_tpu.parallel.sharded_packed import (
            assemble_packed_tiles,
            local_shard_rows,
            shard_row_devices,
        )

        maybe_enable_compile_cache()
        cfg = self.cfg
        n_bucket = bucket_len(
            n_articles if n_articles else cfg.batch_size, min_bucket=64
        )
        step = self._get_sharded_fused_step(mesh)
        init = self._get_sharded_init(mesh)
        devices = shard_row_devices(mesh)
        local_rows = local_shard_rows(mesh)
        compiled = 0
        for w in _prewarm_widths(cfg):
            for rows in _tile_rows_options(_tile_bs(cfg, w)):
                running = init(num_articles=n_bucket)
                nb = packed_nbytes(rows, w)
                zeros = np.zeros((1, nb), np.uint8)
                shards = [
                    jax.device_put(zeros, devices[s]) for s in local_rows
                ]
                packed = assemble_packed_tiles(mesh, shards, nb)
                step(
                    running, packed, rows=rows, width=w, num_articles=n_bucket
                ).block_until_ready()
                compiled += 1
        # the per-bucket epilogue (combine + resolve) compiles here too,
        # so the first corpus pays zero compiles end to end
        running = init(num_articles=n_bucket)
        valid = jax.device_put(
            np.zeros((n_bucket,), bool), NamedSharding(mesh, P(None))
        )
        self._get_sharded_epilogue(mesh)(
            running, valid, jump_rounds=_jump_rounds(n_bucket)
        ).block_until_ready()
        compiled += 1
        return compiled

    def dedup_reps_sharded(self, texts: Sequence[str | bytes], mesh) -> np.ndarray:
        """int32[N] representatives over a device mesh — the pod-scale
        twin of :meth:`dedup_reps_async`'s estimator-only resolution
        (byte-identical, parity-tested; use the one-shot
        :meth:`dedup_reps` when the exact-verify precision path is
        required).

        The shared width-bucketed chunker feeds per-shard packed
        single-put tiles through the pipelined executor into one
        partitioned fused donated dispatch per tile group (1 put + 1
        dispatch per tile per shard, shard-labelled on the always-on
        ledger), with the cross-shard ``pmin`` combine + LSH resolution
        as one end-of-corpus epilogue dispatch."""
        from advanced_scrapper_tpu.obs import stages, trace
        from advanced_scrapper_tpu.parallel.sharded_packed import (
            local_shard_rows,
        )

        n = len(texts)
        if n == 0:
            return np.zeros((0,), np.int32)
        tid = trace.new_trace_id()
        self._m_docs["sharded"].inc(n)
        raw = [to_bytes(t) for t in texts]
        running, n_bucket, _use_oph = self._accumulate_device_sharded(
            raw, mesh, trace_id=tid
        )
        valid = self._valid_device_sharded(raw, n_bucket, mesh)
        epi = self._get_sharded_epilogue(mesh)
        with stages.timed("resolve"), trace.span(
            "dedup.resolve", trace=tid, regime="sharded", docs=n
        ), stages.timed("sharded_epilogue"), trace.span(
            "dedup.sharded.epilogue", trace=tid, docs=n
        ):
            rep = epi(running, valid, jump_rounds=_jump_rounds(n_bucket))
            for s in local_shard_rows(mesh):
                stages.count_dispatch("sharded", shard=s)
            out = np.asarray(rep)[:n]
        self._count_result("sharded", n, out)
        return out

    def _keys_wide_sharded(self, raw: list, mesh) -> np.ndarray:
        """Host ``uint32[N, nb, 2]`` wide band keys off the mesh-sharded
        packed accumulator — the sharded twin of
        ``signatures_and_keys(wide=True, sync_sigs=False)``: one keys
        epilogue dispatch (``pmin`` combine + ``band_keys_wide``),
        replicated, signatures never synced."""
        from advanced_scrapper_tpu.obs import stages, trace
        from advanced_scrapper_tpu.parallel.sharded_packed import (
            local_shard_rows,
        )

        tid = trace.new_trace_id()
        running, _n_bucket, _use_oph = self._accumulate_device_sharded(
            raw, mesh, trace_id=tid
        )
        keys_dev = self._get_sharded_keys_epilogue(mesh)(running)
        for s in local_shard_rows(mesh):
            stages.count_dispatch("sharded", shard=s)
        with stages.timed("kernel"), trace.span(
            "dedup.readback", trace=tid, docs=len(raw)
        ):  # readback sync: the device drains here
            return np.asarray(keys_dev)[: len(raw)]

    def _exact_verified_ok(self, raw, sigs, keys, valid, rep_bands):
        """Verified-edge matrix with statistically fragile edges confirmed
        (or killed) by EXACT shingle-set Jaccard.

        The estimator cannot meet the precision budget alone: at 128 perms
        its σ≈0.04, and the borderline band [0.70, 0.72) holds both the
        false merges (true J < 0.7, the r4 ~3.2-point precision giveback)
        and the genuine bridges that recover cross-estimator disagreement
        recall (measured frontier: tools/sweep_fine_margin.py).  Exact
        Jaccard — the oracle's own ``shingle_set``/``jaccard`` definition,
        imported so the two can never diverge — separates them perfectly,
        and the flagged set is tiny (~130 pairs per 2048 docs), so the
        host cost is noise in the one-shot path.  Returns the device
        ``ok`` matrix (agreement pass runs ONCE) with refuted edges
        cleared, ready for ``resolve_rep_bands_from_ok``.
        """
        from advanced_scrapper_tpu.cpu.oracle import jaccard, shingle_set
        from advanced_scrapper_tpu.obs import stages

        need_dev, ok_dev = borderline_edge_mask(
            rep_bands,
            sigs,
            keys,
            valid,
            self.cfg.sim_threshold,
            self.cfg.exact_verify_band,
            num_coarse=self.params.num_bands,
        )
        stages.count_dispatch("dedup")
        from advanced_scrapper_tpu.obs.telemetry import NOOP

        need = np.asarray(need_dev)
        # decision provenance: which pairs the margin tier re-settled
        # (exact Jaccard, or the strict estimator bar past the cap) —
        # consumed by _emit_decisions when attributing verdict tiers
        self._last_exact_pairs = {}
        if self._m_cand is not NOOP:
            # metric-only host work (skipped when telemetry is disabled),
            # counted BEFORE the borderline early-return: candidate volume
            # must not read 0 just because every edge cleared the bar
            rb_m = np.asarray(rep_bands)
            self._m_cand.inc(
                int((rb_m != np.arange(rb_m.shape[0])[:, None]).sum())
            )
            self._m_borderline.inc(int(need.sum()))
        if not need.any():
            return ok_dev
        rb = np.asarray(rep_bands)
        ok = np.asarray(ok_dev).copy()
        pairs = {}  # (lo, hi) -> verdict; an edge is undirected
        shingles: dict[int, set] = {}

        def sset(i: int) -> set:
            if i not in shingles:
                shingles[i] = shingle_set(raw[i], self.params.shingle_k)
            return shingles[i]

        checked = 0
        sigs_np = None
        for r, c in zip(*np.nonzero(need)):
            j = int(rb[r, c])
            key = (min(int(r), j), max(int(r), j))
            if key not in pairs:
                if checked >= self.cfg.exact_verify_cap:
                    # past the cap (pathological all-borderline corpora)
                    # the edge keeps an ESTIMATOR verdict — but at the
                    # strict fine-only bar (base + fine_margin) the
                    # estimator-only paths apply, not plain base: the
                    # certified path must never verify a flagged edge
                    # more laxly than the uncertified ones do
                    if sigs_np is None:
                        sigs_np = np.asarray(sigs)
                    agree = float(
                        (sigs_np[key[0]] == sigs_np[key[1]]).mean()
                    )
                    pairs[key] = agree >= (
                        self.cfg.sim_threshold + self.cfg.fine_margin
                    )
                else:
                    checked += 1
                    pairs[key] = (
                        jaccard(sset(key[0]), sset(key[1]))
                        >= self.cfg.sim_threshold
                    )
            if not pairs[key]:
                ok[r, c] = False  # exact Jaccard (or strict bar) refuted it
        self._m_exact_checks.inc(checked)
        self._last_exact_pairs = pairs
        return ok

    def _emit_decisions(self, regime: str, out, keys_dev, n: int) -> None:
        """Decision-provenance emission for the certified one-shot path:
        per-verdict tier counters always, journal rows (with the winning
        band key) only when the journal is enabled — the keys D2H sync is
        gated on it, so the disabled journal costs zero extra transfers.

        Tier attribution joins the resolve output against the settling
        evidence the tiers left behind: the rerank hook's
        ``last_provenance`` (host-resettled pairs → margin/reprobe,
        everything else the device sketch settled → rerank, evicted
        members → rerank uniques), or — hookless — the margin stage's
        ``_last_exact_pairs``.  A doc with no settling evidence was
        decided by raw band collision geometry ("band").  The async path
        deliberately never emits: it never syncs verdicts to host, and a
        provenance sync would break that contract — streaming callers get
        provenance from the index path instead.
        """
        from advanced_scrapper_tpu.obs.decisions import get_recorder

        rec = get_recorder()
        out = np.asarray(out)[:n]
        dup = out != np.arange(n)
        if self._rerank_applied:
            prov = getattr(self.rerank_hook, "last_provenance", None) or {}
            evicted = getattr(self.rerank_hook, "last_evicted", None) or set()
            participants = getattr(
                self.rerank_hook, "last_participants", None
            ) or set()
            # strongest host evidence per doc: reprobe > margin
            host_tier: dict[int, str] = {}
            for (a, b), t in prov.items():
                if t in ("margin", "reprobe"):
                    for d in (a, b):
                        if t == "reprobe" or d not in host_tier:
                            host_tier[d] = t

            def dup_tier(i: int, r: int) -> str:
                key = (i, r) if i < r else (r, i)
                return prov.get(key, "rerank")

            def uniq_tier(i: int) -> str:
                if i in evicted:
                    return "rerank"
                t = host_tier.get(i)
                if t is not None:
                    return t
                return "rerank" if i in participants else "band"
        else:
            pairs = getattr(self, "_last_exact_pairs", None) or {}
            margin_docs = {d for k in pairs for d in k}

            def dup_tier(i: int, r: int) -> str:
                key = (i, r) if i < r else (r, i)
                return "margin" if key in pairs else "band"

            def uniq_tier(i: int) -> str:
                return "margin" if i in margin_docs else "band"

        tiers = [
            dup_tier(i, int(out[i])) if dup[i] else uniq_tier(i)
            for i in range(n)
        ]
        counts: dict[tuple[str, bool], int] = {}
        for i, t in enumerate(tiers):
            k = (t, bool(dup[i]))
            counts[k] = counts.get(k, 0) + 1
        for (t, is_dup), c in counts.items():
            rec.count(t, "dup" if is_dup else "unique", c)
        if rec.journal is None:
            return
        keys = np.asarray(keys_dev)[:n]  # journal-gated D2H sync
        rows = []
        for i in range(n):
            r = int(out[i])
            band_key = None
            if dup[i]:
                # winning band: the first candidate column where this
                # doc's key collides with its representative's (None for
                # purely transitive merges)
                cols = np.flatnonzero(keys[i] == keys[r])
                if cols.size:
                    band_key = int(keys[i, cols[0]])
            rows.append(
                {
                    "doc": i,
                    "verdict": "dup" if dup[i] else "unique",
                    "tier": tiers[i],
                    "attr": r if dup[i] else -1,
                    "band_key": band_key,
                    "regime": regime,
                }
            )
        rec.journal_rows(rows)

    def _emit_index_decisions(self, out, keys64, eligible, index) -> None:
        """Decision provenance for the streaming-index path: every
        eligible row's verdict settled at tier "index" (a persistent
        posting hit, or a fresh post).  When the journal is enabled, dup
        rows' winning band keys come from a per-key re-probe of their own
        (already-posted) keys: the column whose per-key attribution
        equals the row's answer is the colliding band — works for
        cross-run and intra-batch attributions alike, no index API
        change, and runs only when journaling."""
        from advanced_scrapper_tpu.obs.decisions import get_recorder

        rec = get_recorder()
        out = np.asarray(out)
        dup_rows = np.flatnonzero(out >= 0)
        n_dup = int(dup_rows.size)
        rec.count("index", "dup", n_dup)
        rec.count("index", "unique", int(eligible.sum()) - n_dup)
        if rec.journal is None:
            return
        k2 = keys64 if keys64.ndim == 2 else keys64.reshape(out.shape[0], -1)
        band_keys: dict[int, int | None] = {}
        if n_dup:
            nb = k2.shape[1]
            attr = np.asarray(
                index.probe_batch(k2[dup_rows].reshape(-1))
            ).reshape(n_dup, nb)
            for x, i in enumerate(dup_rows.tolist()):
                cols = np.flatnonzero(attr[x] == out[i])
                band_keys[i] = int(k2[i, cols[0]]) if cols.size else None
        rows = [
            {
                "doc": int(i),
                "verdict": "dup" if out[i] >= 0 else "unique",
                "tier": "index",
                "attr": int(out[i]),
                "band_key": band_keys.get(int(i)),
                "regime": "stream",
            }
            for i in np.flatnonzero(eligible).tolist()
        ]
        rec.journal_rows(rows)

    def dedup_reps(self, texts: Sequence[str | bytes]) -> np.ndarray:
        """int32[N] first-seen-wins representative per text (union-find
        roots), with exact-Jaccard confirmation of statistically fragile
        edges (``exact_verify_band``) — the certified precision path."""
        n = len(texts)
        if n == 0:
            return np.zeros((0,), np.int32)
        from advanced_scrapper_tpu.obs import trace

        # exact verification is independent of fine-band candidacy:
        # coarse-borderline edges need confirmation even at cand_subbands=0
        # (borderline_edge_mask handles the no-fine-columns case)
        if not self.cfg.exact_verify_band:
            out = np.asarray(self.dedup_reps_async(texts, _regime="oneshot"))[:n]
            self._count_result("oneshot", n, out)
            return out
        from advanced_scrapper_tpu.obs import stages

        raw, sigs, keys, valid, rep_bands, n_bucket, tid = self._prepare(texts)
        self._m_docs["oneshot"].inc(n)
        with stages.timed("resolve_wait"), trace.span(
            "dedup.resolve", trace=tid, regime="oneshot", docs=n
        ):
            if self._rerank_applied:
                # the tier settled every cell by TRUE (sketch/exact)
                # Jaccard and already paid its precision eviction —
                # re-litigating with the estimator-era exact-verify
                # stage would refute deliberate keeps (settled recall
                # pairs with true J just under threshold) and re-admit
                # nothing: resolve the rewritten matrix verbatim
                rb_host = np.asarray(rep_bands)
                ok = rb_host != np.arange(
                    rb_host.shape[0], dtype=rb_host.dtype
                )[:, None]
            else:
                ok = self._exact_verified_ok(
                    raw, sigs, keys, valid, rep_bands
                )
            rep = resolve_rep_bands_from_ok(
                rep_bands, ok, valid, jump_rounds=_jump_rounds(n_bucket)
            )
            stages.count_dispatch("dedup")
            out = np.asarray(rep)[:n]
        with stages.timed("decisions"), trace.span(
            "dedup.decisions", trace=tid, docs=n
        ):
            self._count_result("oneshot", n, out)
            self._emit_decisions("oneshot", out, keys, n)
        return out

    def keep(self, texts: Sequence[str | bytes]) -> np.ndarray:
        reps = self.dedup_reps(texts)
        return reps == np.arange(len(reps))

    def signatures_and_keys(
        self,
        texts: Sequence[str | bytes],
        *,
        wide: bool = False,
        sync_sigs: bool = True,
    ) -> tuple[np.ndarray | None, np.ndarray]:
        """Host ``(sigs[:N], keys[:N])`` with the keys computed ON DEVICE
        from the device-resident accumulator — one fused epilogue dispatch
        (``ops.lsh.fused_keys_epilogue``).

        ``wide=False`` returns the coarse+fine candidate keys
        (``candidate_keys`` semantics — ``uint32[N, nb+cand_subbands]``);
        ``wide=True`` the two-lane wide keys (``band_keys_wide`` —
        ``uint32[N, nb, 2]``, pack on host).  Replaces the streaming
        backends' old shape — sync host signatures, then feed them BACK
        through ``band_keys*`` (a D2H → re-H2D bounce plus extra
        dispatches per batch).

        ``sync_sigs=False`` returns ``(None, keys)``: callers that only
        consume keys (the bloom/persist stream indexes — neither stores
        signatures) skip the ``uint32[bucket, num_perm]`` D2H entirely,
        which is ~8× the key volume for nothing.
        """
        from advanced_scrapper_tpu.obs import stages, trace

        fused_keys_epilogue = _lsh_epilogue("fused_keys_epilogue")
        n = len(texts)
        if n == 0:
            nb = self.params.num_bands
            shape = (0, nb, 2) if wide else (0, nb + self.cfg.cand_subbands)
            sigs0 = np.zeros((0, self.params.num_perm), np.uint32)
            return (sigs0 if sync_sigs else None), np.zeros(shape, np.uint32)
        tid = trace.new_trace_id()
        raw = [to_bytes(t) for t in texts]
        running, _n_bucket, use_oph = self._accumulate_device(
            raw, trace_id=tid
        )
        sig_dev, keys_dev = fused_keys_epilogue(
            running,
            np.asarray(self.params.band_salt),
            self._fine_salt(),
            densify_oph=use_oph,
            wide=wide,
        )
        stages.count_dispatch("dedup")
        with stages.timed("kernel"), trace.span(
            "dedup.readback", trace=tid, docs=n
        ):  # readback sync: the device drains here
            sigs = np.asarray(sig_dev)[:n] if sync_sigs else None
            return sigs, np.asarray(keys_dev)[:n]

    def open_stream_index(self, index_dir: str):
        """Open the durable stream index this engine's config names: a
        local :class:`~advanced_scrapper_tpu.index.store.PersistentIndex`
        under ``index_dir``, or — when ``cfg.index_fleet`` is set — a
        :class:`~advanced_scrapper_tpu.index.fleet.ShardedIndexClient`
        over the remote shard fleet (``index_dir`` then holds only the
        degraded-mode spill journals).  Either return value is a valid
        ``index`` argument to :meth:`dedup_against_index` — the fleet is
        a config string, not a call-site change."""
        if self.cfg.index_fleet:
            from advanced_scrapper_tpu.index.fleet import open_fleet_index

            return open_fleet_index(self.cfg, index_dir, space="bands")
        from advanced_scrapper_tpu.index import PersistentIndex

        return PersistentIndex(
            index_dir,
            cut_postings=self.cfg.index_cut_postings,
            compact_segments=self.cfg.index_compact_segments,
        )

    def dedup_against_index(
        self, texts: Sequence[str | bytes], index, doc_ids=None, *, mesh=None
    ) -> np.ndarray:
        """``int64[N]`` attribution of a corpus against a persistent index
        (``index.store.PersistentIndex`` — or its fleet drop-in,
        ``index.fleet.ShardedIndexClient``; ``open_stream_index`` picks by
        config): device signatures → wide uint64 band keys →
        ``check_and_add_batch``.  A row whose result is ≥ 0 is
        a near-dup of that (possibly restarts-old) doc id; fresh rows post
        their keys under ``doc_ids`` (allocated from the index when not
        given) and return -1.  Sub-shingle rows are never probed or posted
        (always -1) — same eligibility rule as every stream index.

        This is the engine-level streaming entry the persistent index was
        built for: the batch backend (`extractors/tpu_batch.py`) wraps it
        with record bookkeeping, but a raw corpus stream can consume it
        directly.

        ``mesh=``: compute the band keys on the mesh-sharded packed plane
        (per-shard fused donated tiles, ``pmin``-combined keys epilogue)
        instead of the single-device accumulator — byte-identical keys,
        so attributions never depend on the device topology.  The
        cross-shard band-key merge then rides the index plane on the
        host: a ``ShardedIndexClient`` fans each key to its ring shard
        (probe row-min + replicated insert), which is deliberately
        decoupled from the device-mesh shard count.
        """
        from advanced_scrapper_tpu.utils.bloom import pack_keys64

        n = len(texts)
        out = np.full((n,), -1, np.int64)
        if n == 0:
            return out
        raw = [to_bytes(t) for t in texts]
        # fused epilogue: the wide keys come off the device-resident
        # accumulator in one dispatch — signatures never bounce D2H→H2D,
        # and are never synced at all (the index stores keys only)
        if mesh is not None:
            keys_wide = self._keys_wide_sharded(raw, mesh)
        else:
            _sigs, keys_wide = self.signatures_and_keys(
                raw, wide=True, sync_sigs=False
            )
        keys64 = pack_keys64(keys_wide)
        if (
            self.ladder is not None
            and self.ladder.active("fewer_bands")
            and keys64.ndim == 2
            and keys64.shape[1] > 1
        ):
            # brownout step 3: probe/post only the first half of the LSH
            # bands — a declared recall brownout (fewer probe rows, fewer
            # postings) that reverses when the step exits; rows posted
            # while degraded keep their reduced band set, which is the
            # counted cost of staying up
            keys64 = keys64[:, : max(1, keys64.shape[1] // 2)]
            self.ladder.count_effect("fewer_bands", n)
        eligible = np.fromiter(
            (len(r) >= self.params.shingle_k for r in raw), bool, n
        )
        if not eligible.any():
            return out
        if doc_ids is None:
            doc_ids = index.allocate_doc_ids(n)
        doc_ids = np.asarray(doc_ids, dtype=np.uint64)
        out[eligible] = index.check_and_add_batch(
            keys64[eligible], doc_ids[eligible]
        )
        self._emit_index_decisions(out, keys64, eligible, index)
        return out


class ExactDedup:
    """First-seen exact dedup with a byte-identical guarantee.

    Default path: ONE native pass (``cpu.hostbatch.exact_keep_first_native``)
    — the corpus flattens into a single byte blob + offset table and a
    C-side open-addressing hash table decides first-seen membership,
    settling every hash-equal probe with a full ``memcmp`` (a collision can
    lengthen a probe chain but never drop a distinct row).  This is the
    pandas ``drop_duplicates(keep='first')`` replacement that actually
    out-runs pandas: no per-row Python objects, no device round trip, one
    preallocated uint64 offset array and one uint8 keep mask.

    Fallback (no compiler, mixed str/bytes input, or a caller-supplied
    hasher): the device proposes equality groups via 128-bit hashes and the
    host walks each group in original order comparing *actual* full strings
    — including past any hash-side truncation — so the kept index set
    equals the pandas path exactly on every route.
    """

    def __init__(self, hasher: ExactHasher | None = None, max_len: int = 4096):
        # A caller-supplied hasher pins the grouping path (tests inject
        # degenerate hashers; the native pass would ignore them).
        self._custom_hasher = hasher is not None
        self.hasher = hasher or ExactHasher()
        # Historical name: rows are hashed blockwise at this width, so it no
        # longer caps item length — any size hashes exactly (the linear hash
        # splits across blocks; see ``ExactHasher.hash_docs``).
        self.max_len = max_len
        #: which tier served the most recent :meth:`keep_indices` call:
        #: "zero-copy" | "blob" | "grouping" — BENCH_r05's silent 0.22×
        #: regression was the grouping fallback running where the native
        #: tiers should have (build failure swallowed); the bench now
        #: reports this so path selection is a measured fact
        self.last_path: str = ""

    def keep_indices(self, items: Sequence[str]) -> list[int]:
        keep = self._keep_indices(items)
        if items:
            # decision provenance: the exact (memcmp) tier settled every
            # verdict here — kept rows are first-seen uniques, the rest
            # byte-identical dups of an earlier row
            from advanced_scrapper_tpu.obs.decisions import get_recorder

            rec = get_recorder()
            rec.count("exact", "unique", len(keep))
            rec.count("exact", "dup", len(items) - len(keep))
        return keep

    def _keep_indices(self, items: Sequence[str]) -> list[int]:
        if not items:
            return []
        if not self._custom_hasher:
            from advanced_scrapper_tpu.cpu.exactdedup import keep_first_list
            from advanced_scrapper_tpu.cpu.hostbatch import (
                exact_keep_first_native,
            )

            # zero-copy tier first (reads str/bytes buffers in place), then
            # the blob tier (one join + offsets); both confirm every
            # hash-equal probe with a full memcmp, so each is byte-identical
            # to the pandas path on the inputs it accepts
            keep = keep_first_list(items)
            self.last_path = "zero-copy"
            if keep is None:
                keep = exact_keep_first_native(items)
                self.last_path = "blob"
            if keep is not None:
                return np.flatnonzero(keep).tolist()
        self.last_path = "grouping"
        n = len(items)
        raw = [to_bytes(s) for s in items]
        block = bucket_len(max(1, min(max(len(r) for r in raw), self.max_len)))
        h = self.hasher.hash_docs(raw, block_len=block)  # uint32[N, 4]
        # Group rows by their 128-bit hash with one C-speed lexsort instead
        # of a per-row Python dict walk: rows whose hash is unique are kept
        # outright, and only multi-member groups (true duplicates or 2⁻¹²⁸
        # collisions) ever reach the Python string-confirm below.
        hi = (h[:, 0].astype(np.uint64) << 32) | h[:, 1]
        lo = (h[:, 2].astype(np.uint64) << 32) | h[:, 3]
        order = np.lexsort((lo, hi))  # stable ⇒ ties stay in original order
        shi, slo = hi[order], lo[order]
        new_group = np.empty(n, bool)
        new_group[0] = True
        new_group[1:] = (shi[1:] != shi[:-1]) | (slo[1:] != slo[:-1])
        gid = np.empty(n, np.int64)
        gid[order] = np.cumsum(new_group) - 1
        # per-group leader = smallest original index (stability of lexsort)
        leader_of = order[np.flatnonzero(new_group)]
        counts = np.bincount(gid)
        keep = counts[gid] == 1  # singleton hash ⇒ provably first-seen unique
        multi_rows = np.flatnonzero(~keep)  # ascending ⇒ original order
        if len(multi_rows):
            # The overwhelming case is a true-duplicate group: every member
            # equals its leader.  One C-level object compare settles all of
            # them; only groups holding a member that DIFFERS from the
            # leader (a 2⁻¹²⁸ hash collision) take the per-group walk.
            obj = np.array(items, dtype=object)
            leaders = leader_of[gid[multi_rows]]
            eq_leader = obj[multi_rows] == obj[leaders]
            keep[leader_of] = True  # singleton leaders were already True
            rare = np.unique(gid[multi_rows[~eq_leader]])
            for g in rare.tolist():
                members = multi_rows[gid[multi_rows] == g]
                kept_distinct: list[int] = []
                for i in members.tolist():
                    if not any(items[j] == items[i] for j in kept_distinct):
                        kept_distinct.append(i)
                        keep[i] = True
                    else:
                        keep[i] = False
        return np.flatnonzero(keep).tolist()

    def keep_mask(self, items: Sequence[str]) -> np.ndarray:
        mask = np.zeros(len(items), dtype=bool)
        mask[self.keep_indices(items)] = True
        return mask
