"""Device-batched rerank (precision) tier on ``RERANK_HOOK_EDGE``.

:class:`RerankTier` is the engine's default ``rerank_hook``
(``DedupConfig.rerank``): it takes the candidate matrix the fused LSH
epilogue produced, settles every candidate pair's keep/kill verdict
with the vmap'd bottom-sketch Jaccard kernel (``ops/rerank.py``), and
returns a REWRITTEN candidate matrix that holds exactly the surviving
cluster edges — so both resolution paths (async estimator and the
certified one-shot) resolve the tier's verdicts instead of raw band
collisions.

Dataflow per corpus (the launch-count contract the tier-1 gate
asserts)::

    pairs   = coarse band buckets ∪ incoming candidate cells
    fold    = device_put(zeros[pair_cap])          # 1 put
    tiles   : pack_pair_tile → device_put → settle # 1 put + 1 dispatch
              (PipelinedDispatcher — encode/pack/put overlap, the
              caller's thread owns the donated fold)        × tiles
    finalize: fold → (jq, verdict)                 # 1 dispatch
    readback: ONCE                                 # Σ = tiles+1 / tiles+1

Verdicts inside the declared margin band (``rerank_margin``, ~3σ of
the sketch estimator) are re-settled on host: exact shingle Jaccard up
to ``rerank_exact_cap``, then — when a persistent index is attached —
an ANN re-probe over its segment postings (both docs' wide band keys,
``ops.rerank.band_keys_wide_host``; the pair survives when the index
attributes both to the same earliest posting).  Clusters formed from
the settled keep-edges then pass the precision-targeted eviction walk
(``ops.rerank.evict_for_precision``) with the recall floor as a hard
guard, and the surviving est-verified cluster edges are written back
as the new candidate matrix.

The tier is *authoritative*: verdicts already settled by true Jaccard
must not be second-guessed by the estimator-era exact-verify stage —
``NearDupEngine.dedup_reps`` detects ``authoritative = True`` and
resolves the rewritten matrix directly.  ``skip_rerank`` brownouts
bypass the hook in ``_prepare`` (counted, reversible) and restore the
hookless fused path byte-for-byte.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from advanced_scrapper_tpu.config import DedupConfig
from advanced_scrapper_tpu.core.hashing import MinHashParams
from advanced_scrapper_tpu.ops import rerank as oprr
from advanced_scrapper_tpu.ops.pack import pack_pair_tile, pair_tile_nbytes

__all__ = ["RerankTier"]


class RerankTier:
    """Callable ``(raw, sigs, rep_bands, valid) → rep_bands`` for
    :data:`pipeline.dedup.RERANK_HOOK_EDGE` (see module docstring).

    ``index``: optional persistent index (``index.store.PersistentIndex``
    or a fleet client) for the borderline ANN re-probe; None (default)
    keeps the tier self-contained.  ``stats`` holds the last corpus's
    settlement ledger (tiles, bytes, borderline/exact/re-probe counts,
    evictions) for tests and the bench regime.
    """

    #: the certified path trusts the rewritten matrix as settled truth
    #: (see NearDupEngine.dedup_reps) — estimator-era exact-verify would
    #: refute deliberate keeps and drop settled recall
    authoritative = True

    def __init__(
        self,
        cfg: DedupConfig,
        params: MinHashParams,
        *,
        index=None,
    ):
        self.cfg = cfg
        self.params = params
        self.index = index
        self._steps: dict[int, object] = {}
        self._finalize_fn = None
        self.stats: dict = {}

    # -- compiled-step plumbing -------------------------------------------

    def _rows_options(self) -> list[int]:
        """The settle-tile shape set — the SAME derivation the engine
        tile planes prewarm through (``core.tokenizer.tile_rows_options``),
        so the recompile sentinel stays zero in steady state."""
        from advanced_scrapper_tpu.core.tokenizer import tile_rows_options

        return tile_rows_options(max(self.cfg.rerank_tile_rows, 64), 64)

    def _step(self, rows: int):
        step = self._steps.get(rows)
        if step is None:
            from advanced_scrapper_tpu.obs import devprof

            step = devprof.instrument_jit(
                oprr.make_rerank_tile_step(rows, self.cfg.rerank_sketch),
                "rerank_tile",
            )
            self._steps[rows] = step
        return step

    def _finalize(self):
        if self._finalize_fn is None:
            from advanced_scrapper_tpu.obs import devprof

            self._finalize_fn = devprof.instrument_jit(
                oprr.make_rerank_finalize(), "rerank_finalize"
            )
        return self._finalize_fn

    def _put_workers(self) -> int:
        return self.cfg.put_workers or 1

    def prewarm(self) -> int:
        """Compile the full settle shape set (every ``_rows_options``
        tile plus the finalize) against zero buffers — after this, a
        real corpus leaves ``devprof.jit_compiles_total()`` flat.
        Returns the number of steps compiled."""
        import jax

        sketch = self.cfg.rerank_sketch
        cap = self.cfg.rerank_pair_cap
        fold = jax.device_put(np.zeros(cap, np.int32))
        compiled = 0
        for rows in self._rows_options():
            packed = pack_pair_tile(
                np.zeros((rows, sketch), np.uint32),
                np.zeros((rows, sketch), np.uint32),
                np.full(rows, cap, np.int32),  # OOB slots: scatter drops
            )
            fold = self._step(rows)(fold, jax.device_put(packed))
            compiled += 1
        jq, verdict = self._finalize()(fold, np.int32(0), np.int32(1))
        jax.block_until_ready(verdict)
        return compiled + 1

    # -- the tier ----------------------------------------------------------

    def _candidate_pairs(self, sigs, rb, valid, n):
        """Settlement work-list: datasketch-class coarse band pairs plus
        every incoming candidate cell (fine-band candidacy included),
        capped at the fold size with incoming cells prioritised."""
        pairs, capped = oprr.coarse_pairs(
            sigs[:n], valid[:n], self.params.num_bands
        )
        rows, cols = np.nonzero(rb != np.arange(rb.shape[0])[:, None])
        from_cells = set()
        for i, c in zip(rows, cols):
            j = int(rb[i, c])
            i = int(i)
            if i < n and j < n and valid[i] and valid[j] and i != j:
                from_cells.add((min(i, j), max(i, j)))
        extra = sorted(pairs - from_cells)
        ordered = sorted(from_cells) + extra
        cap = self.cfg.rerank_pair_cap
        overflow = max(0, len(ordered) - cap)
        return np.array(ordered[:cap], np.int64).reshape(-1, 2), {
            "capped_buckets": capped,
            "overflow_pairs": overflow,
        }

    def _settle_device(self, pair_arr, sketches):
        """Packed single-dispatch settlement: quantized Jaccard per pair
        slot, ONE readback.  Returns ``(jq int32[m], verdict int8[m],
        tiles, h2d_bytes)``."""
        import jax

        from advanced_scrapper_tpu.obs import stages
        from advanced_scrapper_tpu.pipeline.dispatch import (
            PipelinedDispatcher,
        )

        cfg = self.cfg
        sketch = cfg.rerank_sketch
        cap = cfg.rerank_pair_cap
        m = pair_arr.shape[0]
        thr = cfg.sim_threshold
        lo = np.int32(oprr.quantize(thr - cfg.rerank_margin))
        hi = np.int32(oprr.quantize(thr + cfg.rerank_margin))

        fold_init = np.zeros(cap, np.int32)
        fold = jax.device_put(fold_init)
        stages.count_device_put(fold_init.nbytes, "rerank")

        def tiles():
            # greedy power-of-two chunking over the shared shape set:
            # largest prewarmed tile that fits, smallest (zero-padded)
            # for the residue — same scheme as the encode chunkers
            off = 0
            options = sorted(self._rows_options(), reverse=True)
            while off < m:
                rem = m - off
                rows = next(
                    (o for o in options if o <= rem), options[-1]
                )
                take = min(rows, rem)
                yield rows, off, take
                off += take

        def pack(tile):
            rows, off, take = tile
            ii = pair_arr[off : off + take, 0]
            jj = pair_arr[off : off + take, 1]
            ska = np.zeros((rows, sketch), np.uint32)
            skb = np.zeros((rows, sketch), np.uint32)
            ska[:take] = sketches[ii]
            skb[:take] = sketches[jj]
            idx = np.full(rows, cap, np.int32)  # pad slots scatter-drop
            idx[:take] = np.arange(off, off + take, dtype=np.int32)
            return rows, pack_pair_tile(ska, skb, idx)

        def put(item):
            rows, packed = item
            dev = jax.device_put(packed)
            stages.count_device_put(packed.nbytes, "rerank")
            return rows, packed.nbytes, dev

        n_tiles = 0
        h2d = 0
        pipe = PipelinedDispatcher(
            tiles(),
            pack=pack,
            put=put,
            put_workers=self._put_workers(),
            window=cfg.dispatch_window,
            name="dedup.rerank.h2d",
        )
        try:
            for rows, nbytes, dev in pipe:
                fold = self._step(rows)(fold, dev)
                stages.count_dispatch("rerank")
                n_tiles += 1
                h2d += nbytes
        finally:
            pipe.close()
        jq_dev, verdict_dev = self._finalize()(fold, lo, hi)
        stages.count_dispatch("rerank")
        jq = np.asarray(jq_dev)[:m]  # the corpus's ONE readback
        verdict = np.asarray(verdict_dev)[:m]
        return jq, verdict, n_tiles, h2d

    def _reprobe(self, i: int, j: int, keys64) -> bool | None:
        """Borderline ANN re-probe over the persistent index's segment
        postings: both docs' wide band keys are probed; the pair survives
        when the index attributes both rows to the same earliest posted
        doc (their dup family already co-locates in the postings).
        None = no index attached / no evidence either way."""
        if self.index is None or keys64 is None:
            return None
        attr = np.asarray(self.index.probe_batch(keys64[[i, j]]))
        if attr[0] < 0 or attr[1] < 0:
            return None
        return bool(attr[0] == attr[1])

    def __call__(self, raw: Sequence[bytes], sigs, rep_bands, valid):
        from advanced_scrapper_tpu.obs import stages

        # seven stage clocks that tile the call, each a trace span of the
        # same scope, added once per corpus
        clk = stages.Tally()
        try:
            return self._rerank(raw, sigs, rep_bands, valid, clk)
        finally:
            clk.flush()

    def _rerank(self, raw, sigs, rep_bands, valid, clk):
        from advanced_scrapper_tpu.utils.bloom import pack_keys64

        cfg = self.cfg
        thr = cfg.sim_threshold
        n = len(raw)
        with clk.timed("rerank_readback", "dedup.rerank.readback"):
            sigs_np = np.asarray(sigs)
            rb = np.asarray(rep_bands)
            valid_np = np.asarray(valid)
        n_bucket, nc = rb.shape

        with clk.timed("rerank_worklist", "dedup.rerank.worklist"):
            pair_arr, stats = self._candidate_pairs(sigs_np, rb, valid_np, n)
        m = pair_arr.shape[0]
        self.stats = stats
        stats.update(
            pairs=m, tiles=0, h2d_bytes=0, borderline=0,
            exact_checks=0, reprobes=0, evicted=0, clusters=0,
            dropped_cells=0, predicted_precision=1.0,
        )
        # decision provenance for the engine's emission pass: pairs the
        # HOST re-settled, keyed (lo, hi) → settling tier ("margin" exact
        # Jaccard / "reprobe" index ANN); everything else the device
        # sketch settled ("rerank", the consumer's default), and evicted
        # members' unique verdicts belong to the eviction walk
        prov: dict[tuple[int, int], str] = {}
        self.last_provenance = prov
        self.last_evicted: set[int] = set()
        self.last_participants: set[int] = set()
        if m == 0:
            with clk.timed("rerank_evict", "dedup.rerank.evict"):
                out, _ = oprr.rewrite_rep_bands(n_bucket, nc, [])
            return out

        with clk.timed("rerank_sketch", "dedup.rerank.sketch"):
            participating = np.zeros(n, bool)
            participating[np.unique(pair_arr)] = True
            self.last_participants = set(np.unique(pair_arr).tolist())
            sketches = oprr.bottom_sketches(
                raw, self.params.shingle_k, cfg.rerank_sketch,
                skip=~(participating & valid_np[:n]),
            )

        with clk.timed("rerank_settle", "dedup.rerank.settle"):
            jq, verdict, n_tiles, h2d = self._settle_device(pair_arr, sketches)
        stats["tiles"] = n_tiles
        stats["h2d_bytes"] = h2d

        # host re-settle of the margin band: exact Jaccard up to the cap,
        # then the ANN re-probe, else the sketch verdict stands; each
        # document's sorted shingle ids are built once, on first use
        shingles: dict[int, np.ndarray] = {}

        def ids(i: int) -> np.ndarray:
            s = shingles.get(i)
            if s is None:
                s = shingles[i] = oprr.shingle_ids(
                    raw[i], self.params.shingle_k
                )
            return s

        exact_used = 0
        thr_q = oprr.quantize(thr)
        keep = verdict == 1
        border = np.flatnonzero(verdict == -1)
        stats["borderline"] = int(border.size)
        keys64 = None

        def settle_exact(i: int, j: int, jq_ij: int) -> bool:
            nonlocal exact_used
            key = (i, j) if i < j else (j, i)
            if exact_used < cfg.rerank_exact_cap:
                exact_used += 1
                prov[key] = "margin"
                return oprr.exact_jaccard(ids(i), ids(j)) >= thr
            rp = self._reprobe(i, j, keys64)
            if rp is not None:
                stats["reprobes"] += 1
                prov[key] = "reprobe"
                return rp
            prov[key] = "rerank"  # cap overflow: the sketch verdict stands
            return jq_ij >= thr_q

        with clk.timed("rerank_exact", "dedup.rerank.exact"):
            if self.index is not None and border.size:
                keys64 = pack_keys64(
                    oprr.band_keys_wide_host(
                        sigs_np[:n], np.asarray(self.params.band_salt)
                    )
                )
            for s in border:
                keep[s] = settle_exact(
                    int(pair_arr[s, 0]), int(pair_arr[s, 1]), int(jq[s])
                )
        stats["exact_checks"] = exact_used

        # cluster the settled keep-edges, then classify EVERY
        # within-cluster pair (wave-2: residual pairs the candidacy never
        # proposed are settled on host — sketch twin, margin → exact)
        with clk.timed("rerank_cluster", "dedup.rerank.cluster"):
            reps = oprr.union_find(n, pair_arr[keep])
            clusters: dict[int, list[int]] = {}
            for i in np.flatnonzero(valid_np[:n]):
                clusters.setdefault(int(reps[i]), []).append(int(i))
            clusters = {r: ms for r, ms in clusters.items() if len(ms) > 1}
            stats["clusters"] = len(clusters)

            settled = {
                (int(a), int(b)): (bool(k), int(q))
                for (a, b), k, q in zip(pair_arr, keep, jq)
            }
            margin = cfg.rerank_margin
            lanes = sigs_np.shape[1]
            # expected oracle-recall mass of the WHOLE candidate work-list
            # — candidacy is a superset of the estimator oracle's (coarse
            # buckets ⊆ candidates), so this prices the full recall
            # denominator, killed pairs included.  The eviction floor is
            # (live caught mass / this total): a number that maps directly
            # onto the measured-recall bar instead of an in-cluster ratio.
            total_op_mass = sum(
                oprr.op_weight(int(q) / oprr.SCALE, lanes, thr) for q in jq
            )
            pairinfo: dict[tuple[int, int], tuple[bool, float] | None] = {}
            # margin pairs settle below, in this order, under the exact
            # clock; their slots are held here so the order stays the loop's
            wave2_margin: list[tuple[int, int, int]] = []
            for r, ms in clusters.items():
                for x in range(len(ms)):
                    for y in range(x + 1, len(ms)):
                        a, b = ms[x], ms[y]
                        key = (a, b)
                        if key in settled:
                            is_keep, q = settled[key]
                            w = oprr.op_weight(q / oprr.SCALE, lanes, thr)
                            pairinfo[key] = (not is_keep, w)
                            continue
                        jhat = oprr.sketch_jaccard(sketches[a], sketches[b])
                        if abs(jhat - thr) < margin:
                            pairinfo[key] = None
                            wave2_margin.append((a, b, oprr.quantize(jhat)))
                        else:
                            # transitive extras the candidacy never
                            # proposed sit outside the estimator oracle's
                            # coarse buckets: merged or not, the recall
                            # denominator never counts them, so they carry
                            # zero mass — pure precision entries the
                            # eviction can drop for free
                            pairinfo[key] = (not jhat >= thr, 0.0)
        with clk.timed("rerank_exact", "dedup.rerank.exact"):
            for a, b, q in wave2_margin:
                pairinfo[(a, b)] = (not settle_exact(a, b, q), 0.0)
            shingles.clear()  # freed under the clock of the work that built them
        stats["exact_checks"] = exact_used

        with clk.timed("rerank_evict", "dedup.rerank.evict"):
            evicted, pprec = oprr.evict_for_precision(
                clusters,
                pairinfo,
                cfg.rerank_precision_target,
                recall_floor=cfg.rerank_recall_floor,
                total_op_mass=total_op_mass,
            )
            stats["evicted"] = len(evicted)
            stats["predicted_precision"] = pprec
            self.last_evicted = {int(d) for d in evicted}

            # surviving settled-TRUE cluster edges become the new candidate
            # matrix.  Truth, not the estimator: the engine's own lane
            # agreement is just another draw around the true J, and gating
            # edges on it re-drops exactly the proven-true pairs whose
            # signatures underestimate — the pairs the settle tier exists
            # to save.  Both resolve paths trust the authoritative rewrite
            # (``_rerank_applied``), so no downstream screen re-litigates.
            edges = []
            for r, ms in clusters.items():
                live = [d for d in ms if d not in evicted]
                for x in range(len(live)):
                    for y in range(x + 1, len(live)):
                        a, b = live[x], live[y]
                        if not pairinfo[(a, b)][0]:
                            edges.append((a, b))
            out, dropped = oprr.rewrite_rep_bands(n_bucket, nc, edges)
        stats["dropped_cells"] = dropped
        return out
