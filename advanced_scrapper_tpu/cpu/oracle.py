"""CPU MinHash/LSH oracle — algorithm-identical to ``datasketch``.

``datasketch`` is the recall baseline named in BASELINE.json but is not
installable in this environment, so this module re-implements its exact
algorithm (verified against the published datasketch behaviour):

- base hash: first 4 bytes of SHA1, little-endian (``sha1_hash32``);
- permutations: ``h_i(x) = ((a_i·x + b_i) mod (2^61 - 1)) & 0xFFFFFFFF``
  with ``a_i ∈ [1, p)``, ``b_i ∈ [0, p)`` drawn from
  ``np.random.RandomState(seed)`` in datasketch's order (``core.hashing``);
- signature: elementwise min over the shingle set, initialised to 2^32-1;
- LSH: hash-table buckets keyed by band tuples (16 bands × 8 rows).

This oracle defines ground truth for the ≥0.95 near-dup recall metric and
is deliberately simple, slow and obviously-correct numpy.
"""

from __future__ import annotations

import hashlib
import struct
from collections import defaultdict
from typing import Iterable, Sequence

import numpy as np

from advanced_scrapper_tpu.core.hashing import (
    MAX_HASH,
    MERSENNE_PRIME,
    MinHashParams,
    fmix32_np,
)

#: FNV-1a 32-bit constants of the band-key fold (``ops/shingle.py``)
_FNV_OFFSET = np.uint32(0x811C9DC5)
_FNV_PRIME = np.uint32(0x01000193)
_U32_MAX = np.uint32(0xFFFFFFFF)


def sha1_hash32(data: bytes) -> int:
    """datasketch's default hash: low 4 bytes of SHA1, little-endian."""
    return struct.unpack("<I", hashlib.sha1(data).digest()[:4])[0]


def shingle_set(text: str | bytes, k: int) -> set[bytes]:
    raw = text.encode("utf-8", errors="replace") if isinstance(text, str) else text
    if len(raw) < k:
        return set()
    return {raw[i : i + k] for i in range(len(raw) - k + 1)}


def jaccard(a: set, b: set) -> float:
    if not a and not b:
        return 1.0
    if not a or not b:
        return 0.0
    return len(a & b) / len(a | b)


def oracle_signature(text: str | bytes, params: MinHashParams) -> np.ndarray:
    """uint64[num_perm] signature, exactly as datasketch.MinHash.update()."""
    hv = np.full(params.num_perm, int(MAX_HASH), dtype=np.uint64)
    for sh in shingle_set(text, params.shingle_k):
        x = np.uint64(sha1_hash32(sh))
        phv = ((params.a61 * x + params.b61) % MERSENNE_PRIME) & MAX_HASH
        hv = np.minimum(hv, phv)
    return hv


def oracle_signatures(
    texts: Sequence[str | bytes], params: MinHashParams
) -> np.ndarray:
    return np.stack([oracle_signature(t, params) for t in texts])


def oracle_signatures_fast(
    texts: Sequence[str | bytes],
    params: MinHashParams,
    *,
    chunk: int = 8192,
    _sha_cache: dict | None = None,
) -> np.ndarray:
    """Vectorised, bit-identical twin of :func:`oracle_signatures`.

    Same algorithm (sha1_hash32 base hash, 61-bit Mersenne permutations,
    elementwise min) but the per-shingle Python loop collapses to chunked
    numpy over ``[chunk, num_perm]`` tiles, and sha1 values are memoised
    across documents — planted near-dup corpora share most shingles with
    their base docs, so the certification corpus in
    ``tests/test_recall_vs_oracle.py`` gets oracle truth in seconds instead
    of minutes.  Equality with the slow oracle is CI-tested.
    """
    cache: dict[bytes, int] = {} if _sha_cache is None else _sha_cache
    out = np.empty((len(texts), params.num_perm), dtype=np.uint64)
    a = params.a61[None, :]
    b = params.b61[None, :]
    for t_i, text in enumerate(texts):
        shingles = shingle_set(text, params.shingle_k)
        hv = np.full(params.num_perm, int(MAX_HASH), dtype=np.uint64)
        if shingles:
            xs = np.fromiter(
                (
                    cache[sh] if sh in cache else cache.setdefault(sh, sha1_hash32(sh))
                    for sh in shingles
                ),
                dtype=np.uint64,
                count=len(shingles),
            )
            for start in range(0, len(xs), chunk):
                x = xs[start : start + chunk, None]
                phv = ((a * x + b) % MERSENNE_PRIME) & MAX_HASH
                hv = np.minimum(hv, phv.min(axis=0))
        out[t_i] = hv
    return out


def device_family_signatures(
    texts: Sequence[str | bytes], params: MinHashParams, *, chunk: int = 4096
) -> np.ndarray:
    """``uint32[N, num_perm]`` — the DEVICE hash family on the host.

    The device kernels do not use datasketch's family (``core/hashing.py``
    says why): each k-byte window is FNV-1a hashed and finalised with
    fmix32 (``ops/shingle.py``), then permuted by ``a32·x + b32 mod 2^32``
    and min-reduced.  This is that computation in plain numpy, written
    apart from the kernels, so device signatures can be checked
    bit-for-bit (``chip_smoke.py``).  Texts shorter than k give the
    all-``U32_MAX`` sentinel, as on the device."""
    k = params.shingle_k
    a = params.a32[None, :]
    b = params.b32[None, :]
    out = np.full((len(texts), params.num_perm), int(MAX_HASH), np.uint32)
    for i, text in enumerate(texts):
        raw = text.encode("utf-8", errors="replace") if isinstance(text, str) else text
        if len(raw) < k:
            continue
        byt = np.frombuffer(raw, np.uint8).astype(np.uint32)
        m = len(byt) - k + 1
        h = np.full((m,), 0x811C9DC5, np.uint32)
        for j in range(k):
            h = (h ^ byt[j : j + m]) * np.uint32(0x01000193)
        h ^= h >> np.uint32(16)
        h *= np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
        h = np.unique(h)
        for start in range(0, len(h), chunk):
            ph = h[start : start + chunk, None] * a + b
            out[i] = np.minimum(out[i], ph.min(axis=0))
    return out


def band_tuples(sig: np.ndarray, params: MinHashParams) -> list[tuple]:
    r = params.rows_per_band
    return [tuple(sig[b * r : (b + 1) * r].tolist()) for b in range(params.num_bands)]


def oracle_candidate_pairs(
    sigs: np.ndarray, params: MinHashParams
) -> set[tuple[int, int]]:
    """All (i < j) pairs sharing at least one LSH band bucket."""
    pairs: set[tuple[int, int]] = set()
    for b in range(params.num_bands):
        buckets: dict[tuple, list[int]] = defaultdict(list)
        r = params.rows_per_band
        for i in range(sigs.shape[0]):
            buckets[tuple(sigs[i, b * r : (b + 1) * r].tolist())].append(i)
        for members in buckets.values():
            if len(members) > 1:
                members.sort()
                for x in range(len(members)):
                    for y in range(x + 1, len(members)):
                        pairs.add((members[x], members[y]))
    return pairs


def estimated_jaccard(sig_a: np.ndarray, sig_b: np.ndarray) -> float:
    return float(np.mean(sig_a == sig_b))


def oracle_dedup_reps(
    texts: Sequence[str | bytes],
    params: MinHashParams,
    threshold: float,
) -> np.ndarray:
    """First-seen-wins union-find dedup, the CPU twin of
    ``ops.lsh.duplicate_reps`` + ``resolve_reps``."""
    sigs = oracle_signatures(texts, params)
    n = len(texts)
    parent = np.arange(n)

    def find(i: int) -> int:
        while parent[i] != i:
            parent[i] = parent[parent[i]]
            i = parent[i]
        return i

    for i, j in sorted(oracle_candidate_pairs(sigs, params)):
        if estimated_jaccard(sigs[i], sigs[j]) >= threshold:
            ri, rj = find(i), find(j)
            if ri != rj:
                lo, hi = min(ri, rj), max(ri, rj)
                parent[hi] = lo
    return np.array([find(i) for i in range(n)], dtype=np.int32)


def _salted_band_keys(sigs: np.ndarray, salts: np.ndarray) -> np.ndarray:
    """``uint32[N, len(salts)]``: each band's ``P / len(salts)`` rows
    folded FNV-1a style from the offset basis, XOR the band's salt, then
    murmur3's fmix32."""
    n, p = sigs.shape
    nb = len(salts)
    rows = sigs.reshape(n, nb, p // nb)
    k = np.full((n, nb), _FNV_OFFSET, np.uint32)
    for j in range(p // nb):
        k = (k ^ rows[:, :, j]) * _FNV_PRIME
    return fmix32_np(k ^ salts.astype(np.uint32)[None, :])


def _subband_salts(num: int, seed: int = 0x5B5C9A02) -> np.ndarray:
    """The fine sub-band salts: ``(i · 0x9E3779B97F4A7C15 + seed) mod
    2**32`` for ``i < num``."""
    i = np.arange(num, dtype=np.uint64)
    return ((i * np.uint64(0x9E3779B97F4A7C15) + np.uint64(seed))
            & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def _run_candidates(col: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``(row, candidate)`` pairs of one key column, self pairs dropped:
    rows sorted by (key, index); in each run of equal keys every row
    proposes the run head (its smallest index), its predecessor and its
    predecessor's predecessor in that order."""
    idx = np.arange(len(col))
    si = np.lexsort((idx, col))
    sk = col[si]
    start = np.r_[True, sk[1:] != sk[:-1]]
    run = np.cumsum(start) - 1
    head = si[start][run]
    pred = np.where(start, si, np.r_[si[:1], si[:-1]])
    same2 = np.r_[False, False, run[2:] == run[:-2]]
    pred2 = np.where(same2, np.r_[si[:2], si[:-2]], si)
    rows, cands = [], []
    for cand in (head, pred, pred2):
        m = cand != si
        rows.append(si[m])
        cands.append(cand[m])
    return np.concatenate(rows), np.concatenate(cands)


def oracle_lsh_reps(
    sigs: np.ndarray,
    params: MinHashParams,
    *,
    cand_subbands: int,
    threshold: float,
    valid: np.ndarray,
) -> np.ndarray:
    """``int32[N]``: the estimator-only LSH resolution the device runs
    (``ops/lsh.py`` ``duplicate_rep_bands`` + ``resolve_rep_bands``, as
    ``dedup_reps_async`` and ``dedup_reps_sharded`` use it with
    ``fine_margin`` 0), restated in plain numpy from its semantics.

    - Keys: ``params.num_bands`` coarse bands and ``cand_subbands`` fine
      sub-bands of ``num_perm / cand_subbands`` rows, each the salted
      FNV-1a fold finalised by fmix32 (coarse salts
      ``params.band_salt``, fine salts the sub-band salts).  Invalid rows
      (``valid`` false: fewer than k bytes) take key ``U32_MAX`` and sort
      in with everything else.
    - Candidates: per key column, sorted by (key, index), each row's run
      head, run predecessor and predecessor².
    - Edges: a (row, candidate) pair with both ends valid whose count of
      equal lanes over ``num_perm``, in float32, is at least
      ``float32(threshold)``.
    - Representative: the smallest index of the row's connected
      component under the edges (union-find).

    Departures from the device's wording: the device labels components
    by rounds of pull / scatter-min / pointer doubling
    (``_label_components``) that stop at its fixpoint or at its
    ``jump_rounds`` cap, whichever comes first; this runs union-find to
    the end, so a device resolve that the cap cuts short of the fixpoint
    differs.  Bucket padding rows past ``N`` are not modelled: their keys are
    ``U32_MAX`` and their indices exceed every real row's, so they
    change no real row's run head or predecessors.  The per-edge fine
    bars of ``fine_margin > 0`` are not modelled."""
    sigs = np.asarray(sigs, np.uint32)
    n, p = sigs.shape
    valid = np.asarray(valid, bool)
    if valid.shape != (n,):
        raise ValueError(f"valid has shape {valid.shape}, want ({n},)")
    keys = _salted_band_keys(sigs, np.asarray(params.band_salt))
    if cand_subbands:
        keys = np.concatenate(
            [keys, _salted_band_keys(sigs, _subband_salts(cand_subbands))], axis=1
        )
    keys[~valid] = _U32_MAX
    pairs = [_run_candidates(keys[:, c]) for c in range(keys.shape[1])]
    a = np.concatenate([r for r, _ in pairs])
    b = np.concatenate([c for _, c in pairs])
    both = valid[a] & valid[b]
    lo, hi = np.minimum(a[both], b[both]), np.maximum(a[both], b[both])
    edge = np.unique(lo.astype(np.int64) * n + hi)
    lo, hi = edge // n, edge % n
    agree = np.empty(len(edge), np.float32)
    for s in range(0, len(edge), 65536):
        eq = sigs[lo[s : s + 65536]] == sigs[hi[s : s + 65536]]
        agree[s : s + 65536] = eq.sum(axis=1).astype(np.float32) / np.float32(p)
    keep = agree >= np.float32(threshold)
    edges = set(zip(lo[keep].tolist(), hi[keep].tolist()))
    # union-find by the smallest index (``oracle_reps``; it reads only
    # the length of its texts when the pairs are given)
    return oracle_reps(range(n), params, threshold, pairs=edges).astype(np.int32)


def oracle_near_dup_pairs(
    texts: Sequence[str | bytes],
    params: MinHashParams,
    threshold: float,
    *,
    fast: bool = False,
) -> set[tuple[int, int]]:
    """Candidate pairs whose estimated Jaccard clears ``threshold`` —
    the pair set the recall metric is computed against."""
    sigs = (oracle_signatures_fast if fast else oracle_signatures)(texts, params)
    return {
        (i, j)
        for i, j in oracle_candidate_pairs(sigs, params)
        if estimated_jaccard(sigs[i], sigs[j]) >= threshold
    }


def mutate_to_jaccard(
    rng: np.random.RandomState, text: bytes, target_j: float
) -> bytes:
    """Mutant whose k-shingle Jaccard with ``text`` lands near ``target_j``.

    A contiguous substring of fraction ``f = (1-j)/(1+j)`` is replaced with
    random bytes: the surviving shingles ≈ (1-f)·S shared out of ≈ (1+f)·S
    union, giving J ≈ (1-f)/(1+f) — invertible, so the certification corpus
    can PLANT pairs across the LSH sensitivity knee instead of only the
    easy J→1 regime (the round-2 recall-test weakness)."""
    f = (1.0 - target_j) / (1.0 + target_j)
    span = max(1, int(len(text) * f))
    pos = rng.randint(0, max(1, len(text) - span))
    b = bytearray(text)
    b[pos : pos + span] = rng.randint(32, 127, size=span, dtype=np.uint8).tobytes()
    return bytes(b)


def build_certification_corpus(
    rng: np.random.RandomState,
    n_bases: int,
    *,
    min_len: int = 100,
    max_len: int = 20000,
    n_long: int = 12,
    long_len: int = 100_000,
    knee_frac: float = 0.4,
) -> list[bytes]:
    """Recall-certification corpus: ragged lengths (log-uniform
    ``min_len..max_len`` plus ``n_long`` docs at ``long_len`` forcing the
    blockwise segment-min combine), each base planted with two mutants —
    a ``knee_frac`` share targeted across the Jaccard knee (0.62..0.80,
    where LSH candidacy is genuinely probabilistic) and the rest in the
    easy high-similarity regime (0.85..0.97) — shuffled together with an
    equal count of unrelated docs."""
    lens = np.exp(
        rng.uniform(np.log(min_len), np.log(max_len), size=n_bases)
    ).astype(np.int64)
    lens[:n_long] = long_len
    texts: list[bytes] = []
    for i in range(n_bases):
        base = rng.randint(32, 127, size=int(lens[i]), dtype=np.uint8).tobytes()
        texts.append(base)
        for _ in range(2):
            if rng.rand() < knee_frac:
                tj = rng.uniform(0.62, 0.80)
            else:
                tj = rng.uniform(0.85, 0.97)
            texts.append(mutate_to_jaccard(rng, base, tj))
        texts.append(
            rng.randint(32, 127, size=int(lens[rng.randint(n_bases)]), dtype=np.uint8).tobytes()
        )
    order = rng.permutation(len(texts))
    return [texts[i] for i in order]


def oracle_reps(
    texts: Sequence[str | bytes],
    params: MinHashParams,
    threshold: float,
    *,
    fast: bool = False,
    pairs: set[tuple[int, int]] | None = None,
) -> np.ndarray:
    """Cluster representatives from the ORACLE's own pair set — i.e. what
    "datasketch plus union-find" would keep (first-seen wins: every
    cluster's rep is its smallest index).  This is the comparator for the
    engine's precision: both sides threshold the same 128-lane estimator
    and close transitively, so the engine's merged-pair precision is
    certified against this clustering's, not against an unreachable 1.0.

    ``pairs`` may carry a precomputed ``oracle_near_dup_pairs`` result —
    the pair set is the expensive part, and recall metrics already
    computed it for the same corpus.
    """
    parent = np.arange(len(texts))

    def find(x: int) -> int:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = int(parent[x])
        return x

    if pairs is None:
        pairs = oracle_near_dup_pairs(texts, params, threshold, fast=fast)
    for i, j in pairs:
        ri, rj = find(i), find(j)
        if ri != rj:
            # union by min index so reps are first-seen, like the engine
            lo, hi = (ri, rj) if ri < rj else (rj, ri)
            parent[hi] = lo
    return np.array([find(i) for i in range(len(texts))])


def measured_recall(
    texts: Sequence[str | bytes],
    reps: np.ndarray,
    params: MinHashParams,
    threshold: float,
    *,
    pairs: set[tuple[int, int]] | None = None,
) -> tuple[float, int]:
    """(recall, n_oracle_pairs): fraction of datasketch-semantics near-dup
    pairs the engine clustered together (``reps`` from
    ``NearDupEngine.dedup_reps``).  The north-star bar is ≥0.95
    (BASELINE.json).  ``pairs`` reuses a precomputed oracle pair set
    (callers that also build ``oracle_reps`` share one computation)."""
    if pairs is None:
        pairs = oracle_near_dup_pairs(texts, params, threshold, fast=True)
    if not pairs:
        return 1.0, 0
    hit = sum(1 for i, j in pairs if reps[i] == reps[j])
    return hit / len(pairs), len(pairs)


def measured_precision(
    texts: Sequence[str | bytes],
    reps: np.ndarray,
    shingle_k: int,
    threshold: float,
    *,
    edge_slack: float = 0.10,
) -> tuple[float, int, int]:
    """``(precision, n_engine_pairs, n_unchained)`` over the pairs the
    ENGINE merged (same rep), judged by TRUE shingle-set Jaccard.

    ``precision`` counts merged pairs with true J ≥ ``threshold``.  It is
    NOT expected to be 1.0: both the engine and datasketch threshold an
    *estimator* (128-lane agreement), so edges slightly below threshold
    can verify, and transitive closure then merges mutant-mutant pairs
    whose direct J is lower still — identical behaviour to datasketch
    plus union-find.

    The hard certification is ``n_unchained``: every member of a cluster
    must be REACHABLE from its peers through edges of true
    J ≥ ``threshold − edge_slack`` (edges the estimator can plausibly
    accept; at J = 0.60 a false accept is <1% per edge).  A member only
    reachable through weaker edges is a genuine false merge — the bar is
    ZERO.
    """
    clusters: dict[int, list[int]] = defaultdict(list)
    for i, r in enumerate(reps):
        clusters[int(r)].append(i)

    edge_bar = threshold - edge_slack
    n_pairs = good = unchained = 0
    for members in clusters.values():
        m = len(members)
        if m < 2:
            continue
        # shingle sets scoped per cluster: cross-cluster pairs are never
        # compared, so peak memory is one cluster's worth, not the corpus'
        sets = [shingle_set(texts[i], shingle_k) for i in members]
        jmat = np.ones((m, m))
        for a in range(m):
            for b in range(a + 1, m):
                jmat[a, b] = jmat[b, a] = jaccard(sets[a], sets[b])
        n_pairs += m * (m - 1) // 2
        good += int(np.count_nonzero(np.triu(jmat >= threshold, k=1)))
        # members outside the LARGEST strong-edge component are the wrongly
        # attached ones (seeding from an arbitrary member would overcount
        # whenever the weak outlier happened to be the seed)
        strong = jmat >= edge_bar
        unvisited = np.ones(m, bool)
        biggest = 0
        while unvisited.any():
            seed = int(np.flatnonzero(unvisited)[0])
            seen = np.zeros(m, bool)
            seen[seed] = True
            frontier = [seed]
            while frontier:
                nxt = np.flatnonzero(strong[frontier].any(axis=0) & ~seen)
                seen[nxt] = True
                frontier = nxt.tolist()
            biggest = max(biggest, int(seen.sum()))
            unvisited &= ~seen
        unchained += m - biggest
    return (good / n_pairs if n_pairs else 1.0), n_pairs, unchained
