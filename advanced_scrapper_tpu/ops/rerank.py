"""Pair-settlement math for the device-batched rerank (precision) tier.

The LSH candidate matrix arriving on ``RERANK_HOOK_EDGE``
(``pipeline/dedup.py``) thresholds a 128-lane *estimator* (σ≈0.04), so
the merged-pair precision tops out around 0.85–0.89 against the ≥0.95
ambition.  This module holds the pure math the tier is built from — no
pipeline, runtime, index or obs imports (enforced by
``tools/lint_imports.py``; the orchestration half lives in
``pipeline/rerank.py``):

- **bottom-S shingle sketches** (:func:`bottom_sketch`): per document,
  the ``S`` smallest 32-bit-hashed k-byte shingles.  The pairwise
  Jaccard estimator built on two such sketches has σ≈√(J(1−J)/S)
  (≈0.014 at S=1024, 3× tighter than the 128-perm signature) and is
  EXACT whenever ``|union| ≤ S`` — i.e. for every document pair short
  enough that both shingle sets fit the sketch.
- **exact shingle ids** (:func:`shingle_ids`, :func:`exact_jaccard`):
  the host re-settle of margin pairs — one sorted array of exact
  shingle ids per document, Jaccard counted on integers, equal to
  ``cpu.oracle.jaccard`` of the shingle sets to the last bit.
- the **vmap'd settle kernel** (:func:`make_rerank_tile_step`): one
  packed pair tile (``ops.pack.pack_pair_tile``) in, per-pair
  quantized Jaccard scattered into a device-resident fold buffer out —
  1 ``device_put`` + 1 dispatch per tile, verdicts read back ONCE per
  corpus after :func:`make_rerank_finalize`.
- the **candidacy + clustering host half**: coarse band-bucket pair
  recovery (:func:`coarse_pairs`, datasketch's candidacy class),
  vectorised signature agreement, union-find, and the
  precision-targeted eviction policy (:func:`evict_for_precision`)
  that trades the measured tail of false merges for the ≥0.95 pooled
  precision bar while a recall floor guards the other bar.
- a **host twin of the wide band keys**
  (:func:`band_keys_wide_host`): the borderline ANN re-probe consults
  the persistent index's segment postings, whose key space is
  ``ops.lsh.band_keys_wide`` — the twin reproduces it in numpy so the
  tier never pays a device dispatch for keys (parity is pinned in
  ``tests/test_rerank_dispatch.py``).

Quantization: Jaccard values cross the device boundary as
``round(J * SCALE)`` int32 (σ·SCALE ≈ 140 quanta, so the 1e-4 grid is
noise-free resolution) — integer verdicts are byte-stable across
put-worker/window orderings, which a float fold could not promise.
"""

from __future__ import annotations

import math

import numpy as np

from advanced_scrapper_tpu.ops.pack import pair_tile_nbytes, unpack_pair_tile
from advanced_scrapper_tpu.ops.shingle import FNV_OFFSET, FNV_PRIME

__all__ = [
    "PAD",
    "SCALE",
    "band_keys_wide_host",
    "bottom_sketch",
    "bottom_sketches",
    "coarse_pairs",
    "evict_for_precision",
    "exact_jaccard",
    "make_rerank_finalize",
    "make_rerank_tile_step",
    "pair_tile_nbytes",
    "quantize",
    "rewrite_rep_bands",
    "shingle_ids",
    "signature_agreement",
    "sketch_jaccard",
    "union_find",
]

#: sketch padding sentinel — sorts after every real 32-bit hash, and real
#: hashes equal to it are dropped at build time so it is unambiguous
PAD = np.uint32(0xFFFFFFFF)

#: Jaccard quantization grid: device verdicts are ``round(J * SCALE)``
SCALE = 10_000

_M64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def quantize(j: float) -> int:
    """Host-side twin of the device quantization: ``round(j * SCALE)``."""
    return int(round(float(j) * SCALE))


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finaliser over uint64 — shingle ids → uniform hashes."""
    x = np.asarray(x, np.uint64)
    x = (x + np.uint64(0x9E3779B97F4A7C15)) & _M64
    x = ((x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)) & _M64
    x = ((x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)) & _M64
    return x ^ (x >> np.uint64(31))


# -- exact shingle ids ------------------------------------------------------


def _utf8(text: str | bytes) -> bytes:
    return (
        text.encode("utf-8", errors="replace")
        if isinstance(text, str)
        else bytes(text)
    )


def shingle_ids(text: str | bytes, k: int) -> np.ndarray:
    """Sorted unique ids of the k-byte shingle set — ``cpu.oracle.
    shingle_set`` as one array (utf-8 ``errors="replace"``, ``len < k``
    → empty), exact with no hashing.

    For ``k ≤ 8`` a window's id is its bytes packed little-endian into a
    ``uint64`` (byte ``j`` at bits ``8j``): one unaligned 8-byte load
    per offset over the zero-padded text, masked to ``k`` bytes.  Longer
    shingles are compared as ``k``-byte ``np.void`` records."""
    raw = _utf8(text)
    if k <= 8:
        if len(raw) < k:
            return np.zeros(0, np.uint64)
        buf = np.frombuffer(raw + bytes(8 - k), np.uint8)
        win = np.ndarray(
            (len(raw) - k + 1,), np.dtype("<u8"), buffer=buf, strides=(1,)
        )
        return np.unique(win & np.uint64((1 << (8 * k)) - 1))
    rec = np.dtype((np.void, k))
    if len(raw) < k:
        return np.zeros(0, rec)
    win = np.lib.stride_tricks.sliding_window_view(
        np.frombuffer(raw, np.uint8), k
    )
    return np.unique(np.ascontiguousarray(win).view(rec).ravel())


def exact_jaccard(ids_a: np.ndarray, ids_b: np.ndarray) -> float:
    """True Jaccard of two :func:`shingle_ids` arrays, equal to
    ``cpu.oracle.jaccard`` of the sets: the intersection is counted as
    an integer and divided by the integer union size; both empty → 1.0,
    one empty → 0.0."""
    na, nb = ids_a.size, ids_b.size
    if na == 0 and nb == 0:
        return 1.0
    if na == 0 or nb == 0:
        return 0.0
    if na > nb:
        ids_a, ids_b, na, nb = ids_b, ids_a, nb, na
    at = np.minimum(np.searchsorted(ids_b, ids_a), nb - 1)
    inter = int(np.count_nonzero(ids_b[at] == ids_a))
    return inter / (na + nb - inter)


# -- bottom-S sketches ------------------------------------------------------


def bottom_sketch(text: str | bytes, k: int, size: int) -> np.ndarray:
    """``uint32[size]`` bottom-``size`` sketch of the k-byte shingle set.

    Shingle semantics mirror ``cpu.oracle.shingle_set`` exactly (utf-8
    ``errors="replace"``, ``len < k`` → empty set → all-PAD sketch), so
    the sketch estimator converges on the oracle's TRUE Jaccard.  Ids
    are exact for ``k ≤ 8`` (:func:`shingle_ids`); longer shingles fold
    the tail bytes FNV-style.  All numpy, no per-shingle Python.
    """
    out = np.full((size,), PAD, np.uint32)
    if k <= 8:
        ids = shingle_ids(text, k)
    else:
        raw = _utf8(text)
        if len(raw) < k:
            return out
        b = np.frombuffer(raw, np.uint8)
        win = np.lib.stride_tricks.sliding_window_view(b, k)
        ids = np.zeros(win.shape[0], np.uint64)
        for j in range(8):
            ids |= win[:, j].astype(np.uint64) << np.uint64(8 * j)
        for j in range(8, k):
            ids = ((ids * np.uint64(0x100000001B3)) & _M64) ^ win[:, j].astype(
                np.uint64
            )
        ids = np.unique(ids)
    h = (_mix64(ids) >> np.uint64(32)).astype(np.uint32)
    h = np.unique(h)
    h = h[h != PAD]
    m = min(size, h.size)
    out[:m] = h[:m]
    return out


def bottom_sketches(
    texts, k: int, size: int, *, skip=None
) -> np.ndarray:
    """``uint32[n, size]`` stacked :func:`bottom_sketch` per document.
    ``skip`` (bool[n]) rows stay all-PAD without touching the text."""
    n = len(texts)
    out = np.full((n, size), PAD, np.uint32)
    for i in range(n):
        if skip is not None and skip[i]:
            continue
        out[i] = bottom_sketch(texts[i], k, size)
    return out


def sketch_jaccard(ska: np.ndarray, skb: np.ndarray) -> float:
    """Host reference estimator — the kernel's float twin (tests pin the
    quantized device verdict against ``quantize`` of this)."""
    size = int(ska.shape[0])
    a = ska[ska != PAD]
    b = skb[skb != PAD]
    if a.size == 0 and b.size == 0:
        return 1.0
    uni = np.union1d(a, b)
    kk = min(size, uni.size)
    if kk == 0:
        return 1.0
    inter = np.intersect1d(a, b)
    matches = int(np.isin(uni[:kk], inter, assume_unique=True).sum())
    return matches / kk


# -- the vmap'd settle kernel ----------------------------------------------


def _pair_jq(ca, cb, size: int):
    """Quantized bottom-sketch Jaccard of ONE pair (1-D uint32 sketches).

    Sorted-concat formulation: a value appearing twice is in both
    sketches (each sketch holds unique values); the union's bottom-kk
    is the first kk unique values of the sorted concat.  Everything is
    sort/cumsum — XLA-native, lane-aligned at 2·size per pair.
    """
    import jax.numpy as jnp

    pad = jnp.uint32(0xFFFFFFFF)
    c = jnp.sort(jnp.concatenate([ca, cb]))
    live = c != pad
    nxt = jnp.concatenate([c[1:], jnp.full((1,), pad, jnp.uint32)])
    dup = (c == nxt) & live
    first = jnp.concatenate([live[:1], (c[1:] != c[:-1]) & live[1:]])
    rank = jnp.cumsum(first.astype(jnp.int32)) - 1
    n_uni = jnp.sum(first.astype(jnp.int32))
    kk = jnp.minimum(n_uni, size)
    matches = jnp.sum((dup & (rank < kk)).astype(jnp.int32))
    # integer round-half-up of SCALE·matches/kk; empty∪empty ⇒ J=1
    # (oracle.jaccard's both-empty convention)
    return jnp.where(
        kk > 0,
        (SCALE * matches + kk // 2) // jnp.maximum(kk, 1),
        SCALE,
    ).astype(jnp.int32)


def make_rerank_tile_step(rows: int, sketch: int):
    """RAW jitted settle step for one packed pair tile —
    ``(fold int32[cap], packed uint8[pair_tile_nbytes]) → fold``.

    The fold buffer is donated (device-resident across tiles, one
    readback per corpus) and pad rows carry a fold slot ≥ cap, which the
    ``mode="drop"`` scatter discards.  Callers wrap the returned jit in
    the recompile sentinel (``obs.devprof.instrument_jit``) — this
    module stays obs-free by layering rule.
    """
    from functools import partial

    import jax

    @partial(jax.jit, donate_argnums=(0,))
    def rerank_tile_step(fold, packed):
        ska, skb, idx = unpack_pair_tile(packed, rows, sketch)
        jq = jax.vmap(lambda a, b: _pair_jq(a, b, sketch))(ska, skb)
        return fold.at[idx].set(jq, mode="drop")

    return rerank_tile_step


def make_rerank_finalize():
    """RAW jitted corpus finalize — ``(fold, lo, hi) → (fold, verdict)``.

    ``lo``/``hi`` are the quantized margin-band bounds passed as dynamic
    int32 scalars (ONE compile regardless of threshold/margin config —
    the recompile sentinel must stay zero in steady state).  Verdict
    int8 per slot: 1 keep (``jq ≥ hi``), 0 kill (``jq < lo``), -1
    borderline — re-settled on host (exact Jaccard / ANN re-probe).
    """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def rerank_finalize(fold, lo, hi):
        border = (fold >= lo) & (fold < hi)
        verdict = jnp.where(
            border, jnp.int8(-1), (fold >= hi).astype(jnp.int8)
        )
        return fold, verdict

    return rerank_finalize


# -- host candidacy / clustering / eviction policy -------------------------


def coarse_pairs(
    sigs: np.ndarray,
    valid: np.ndarray,
    num_bands: int,
    *,
    bucket_allpairs: int = 64,
) -> tuple[set, int]:
    """Datasketch-class candidate pairs from coarse LSH band buckets.

    Groups the ``num_bands`` band slices of ``sigs[:n]`` (host array,
    any integer dtype) by a mixed bucket key; every bucket of valid rows
    yields all ``(i < j)`` pairs up to ``bucket_allpairs`` members, and
    a star+chain (first-seen hub plus adjacent links, 2(m−1) pairs)
    above it — connectivity-preserving under union-find, so a giant
    boilerplate bucket cannot go quadratic.  Returns ``(pairs,
    n_capped_buckets)``; mixing can only MERGE buckets (never split), so
    candidacy is a superset of the oracle's — spurious pairs are settled
    by the sketch kernel downstream.
    """
    n = sigs.shape[0]
    r = sigs.shape[1] // num_bands
    pairs: set = set()
    capped = 0
    vidx = np.flatnonzero(np.asarray(valid[:n], bool))
    if vidx.size < 2:
        return pairs, capped
    sig = np.ascontiguousarray(sigs[vidx], np.uint64)
    for b in range(num_bands):
        key = np.full(vidx.size, np.uint64(b), np.uint64)
        for c in range(b * r, (b + 1) * r):
            key = _mix64(key ^ sig[:, c])
        order = np.argsort(key, kind="stable")
        sk = key[order]
        starts = np.flatnonzero(
            np.concatenate([[True], sk[1:] != sk[:-1]])
        )
        ends = np.concatenate([starts[1:], [sk.size]])
        for s, e in zip(starts, ends):
            if e - s < 2:
                continue
            members = np.sort(vidx[order[s:e]])
            m = members.size
            if m <= bucket_allpairs:
                for x in range(m):
                    for y in range(x + 1, m):
                        pairs.add((int(members[x]), int(members[y])))
            else:
                capped += 1
                hub = int(members[0])
                for x in range(1, m):
                    pairs.add((hub, int(members[x])))
                    if x + 1 < m:
                        pairs.add((int(members[x]), int(members[x + 1])))
    return pairs, capped


def signature_agreement(sigs: np.ndarray, pairs: np.ndarray) -> np.ndarray:
    """``float64[m]`` lane-agreement estimator per ``(i, j)`` pair row —
    vectorised ``cpu.oracle.estimated_jaccard``."""
    if pairs.shape[0] == 0:
        return np.zeros((0,), np.float64)
    return (sigs[pairs[:, 0]] == sigs[pairs[:, 1]]).mean(axis=1)


def union_find(n: int, edges) -> np.ndarray:
    """``int32[n]`` min-root component labels over undirected ``edges`` —
    the host twin of ``ops.lsh``'s on-device label propagation."""
    parent = np.arange(n, dtype=np.int64)

    def find(x: int) -> int:
        root = x
        while parent[root] != root:
            root = parent[root]
        while parent[x] != root:
            parent[x], x = root, parent[x]
        return root

    for i, j in edges:
        ri, rj = find(int(i)), find(int(j))
        if ri != rj:
            if ri > rj:
                ri, rj = rj, ri
            parent[rj] = ri
    return np.array([find(i) for i in range(n)], np.int32)


def op_weight(jhat: float, lanes: int, threshold: float = 0.7) -> float:
    """Recall-relevance weight of a pair: the probability a fresh
    ``lanes``-lane MinHash agreement draw at true Jaccard ≈ ``jhat``
    lands at or above ``threshold``.

    The recall bar is judged against an ESTIMATOR oracle (datasketch
    semantics): a pair enters the denominator when the oracle's own
    128-lane draw reads ≥ threshold, an event only probabilistically
    knowable from the settled truth.  Lane agreement is
    Binomial(lanes, J)/lanes, so the normal approximation
    ``Φ((jhat − threshold) / sqrt(jhat(1−jhat)/lanes))`` prices each
    pair's expected recall mass — a J=0.9 pair is certainly counted
    (w≈1), a settled-bad J=0.62 pair almost certainly is not (w≈0.03),
    and the borderline band prices in between.  The eviction policy
    sums these weights instead of counting binary estimator verdicts:
    the engine's OWN estimator draw is correlated with the oracle's
    only through the true J, so thresholding it misprices exactly the
    borderline pairs where recall is won or lost.
    """
    j = min(max(jhat, 0.02), 0.98)
    sigma = math.sqrt(j * (1.0 - j) / max(lanes, 1))
    return 0.5 * (1.0 + math.erf((jhat - threshold) / (sigma * math.sqrt(2.0))))


def evict_for_precision(
    clusters: dict,
    pairinfo: dict,
    target: float,
    *,
    recall_floor: float = 0.0,
    total_op_mass: float = 0.0,
) -> tuple[set, float]:
    """Greedy precision-targeted member eviction over settled clusters.

    ``clusters`` maps root → member list (size > 1); ``pairinfo`` maps
    each within-cluster ``(a < b)`` pair to ``(bad, w)`` — ``bad`` is
    the settled TRUE verdict (J < threshold: a false merge the
    precision metric counts against us), ``w`` the pair's expected
    recall mass (:func:`op_weight`: the probability the estimator
    oracle counts it).  Members are evicted one at a time — highest
    ``bad/(1+op_mass)`` first (ties: most recall-free bad pairs, then
    most bad pairs), only from clusters with ≥3 live members (pair
    clusters are all-or-nothing) — until the predicted merged-pair
    precision reaches ``target``.  The score is recall-aware by
    construction: a member whose bad pairs carry recall mass is
    expensive to evict, so the walk burns pure-loss pairs first.

    ``recall_floor`` (with ``total_op_mass``) is the hard guard for the
    other bar: eviction stops before predicted recall — live recall
    mass over the starting in-cluster mass — would cross below it.
    Returns ``(evicted member set, predicted precision)``.
    """
    memb: dict = {}
    good = bad = 0
    op_live = 0.0
    for (a, b), (is_bad, w) in pairinfo.items():
        good += not is_bad
        bad += is_bad
        op_live += w
        for d in (a, b):
            s = memb.setdefault(d, [0, 0.0, 0])  # bad, op_mass, badfree
            s[0] += is_bad
            s[1] += w
            s[2] += is_bad and w < 0.25
    evicted: set = set()

    def prec() -> float:
        return good / max(good + bad, 1)

    while bad and prec() < target:
        best = None
        for r, m in clusters.items():
            live = [d for d in m if d not in evicted]
            if len(live) < 3:
                continue
            for d in live:
                b_, o_, bf_ = memb.get(d, (0, 0.0, 0))
                if b_ == 0:
                    continue
                score = (b_ / (1.0 + o_), bf_, b_)
                if best is None or score > best[0]:
                    best = (score, d, r)
        if best is None:
            break
        _, d, r = best
        if total_op_mass and recall_floor:
            lost = memb.get(d, (0, 0.0, 0))[1]
            if (op_live - lost) / max(total_op_mass, 1e-9) < recall_floor:
                break
        evicted.add(d)
        for x in clusters[r]:
            if x in evicted or x == d:
                continue
            key = (d, x) if d < x else (x, d)
            is_bad, w = pairinfo[key]
            good -= not is_bad
            bad -= is_bad
            op_live -= w
            s = memb[x]
            s[0] -= is_bad
            s[1] -= w
            s[2] -= is_bad and w < 0.25
        memb[d] = [0, 0.0, 0]
    return evicted, prec()


def rewrite_rep_bands(
    n_bucket: int, nc: int, edges
) -> tuple[np.ndarray, int]:
    """``int32[n_bucket, nc]`` candidate matrix holding exactly ``edges``.

    The tier's output on ``RERANK_HOOK_EDGE``: all-self baseline, each
    surviving edge ``(i, j)`` lands on its LATER row (``max``'s row gets
    the ``min`` as candidate — resolve's edges are undirected, and
    backward cells keep first-seen-wins semantics).  Rows overflowing
    ``nc`` drop their largest-j edges (returned as the second element —
    connectivity via the smaller-j cells is what component-min resolve
    consumes first).
    """
    rb = np.tile(np.arange(n_bucket, dtype=np.int32)[:, None], (1, nc))
    fill = np.zeros(n_bucket, np.int32)
    dropped = 0
    for a, b in sorted(
        (max(int(a), int(b)), min(int(a), int(b))) for a, b in edges
    ):
        c = fill[a]
        if c >= nc:
            dropped += 1
            continue
        rb[a, c] = b
        fill[a] = c + 1
    return rb, dropped


# -- host twin of the wide band keys (the index re-probe key space) --------


def _fmix32_np(h: np.ndarray) -> np.ndarray:
    h = h.astype(np.uint32)
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def band_keys_wide_host(
    sigs: np.ndarray, band_salt: np.ndarray
) -> np.ndarray:
    """``uint32[B, nb, 2]`` — numpy twin of ``ops.lsh.band_keys_wide``
    (same FNV-1a fold, same wide-lane constants, same rotated salt), so
    the tier's borderline ANN re-probe addresses the persistent index's
    EXACT posting key space without a device dispatch.  Parity with the
    device fn is pinned in ``tests/test_rerank_dispatch.py``."""
    sig = np.asarray(sigs, np.uint32)
    salt = np.asarray(band_salt, np.uint32)
    nb = salt.shape[0]
    B, P = sig.shape
    r = P // nb
    rows = sig.reshape(B, nb, r)
    lo = np.full((B, nb), FNV_OFFSET, np.uint32)
    hi = np.full((B, nb), np.uint32(0xCBF29CE4), np.uint32)
    for j in range(r):
        lo = (lo ^ rows[:, :, j]) * FNV_PRIME
        hi = (hi ^ rows[:, :, j]) * np.uint32(0x01000197)
    rot = (salt << np.uint32(13)) | (salt >> np.uint32(19))
    return np.stack(
        [_fmix32_np(lo ^ salt[None, :]), _fmix32_np(hi ^ rot[None, :])],
        axis=-1,
    )
