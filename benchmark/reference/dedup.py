"""Plain reference for near-duplicate dedup, independent of the program.

It imports nothing of ``advanced_scrapper_tpu`` and takes nothing the
program made.  Two parts:

- :func:`signatures`: the device MinHash family in numpy, from its
  published definition (``core/hashing.py``'s docstring): each k-byte
  window FNV-1a hashed and finalised with murmur3's fmix32, permuted by
  ``a·x + b mod 2**32`` with odd ``a``, min-reduced over the article.
  ``a``, ``b`` are drawn here from the configuration's seed the way that
  docstring states.  The program's signatures must equal these bit for
  bit.
- :func:`shingle_ids` and :func:`jaccard`: exact k-byte shingle-set
  Jaccard, the quantity the configuration's threshold is stated on.
"""

from __future__ import annotations

import numpy as np

U32_MAX = np.uint32(0xFFFFFFFF)
#: clusters above this size are not searched pair by pair: the planted
#: families stay far below it, so only a broken run reaches it
MAX_CLUSTER = 512


def device_family(seed: int, num_perm: int) -> tuple[np.ndarray, np.ndarray]:
    """``(a32, b32)``: the device family's permutation constants —
    ``RandomState((seed + 0x5F3759DF) mod 2**31)``, ``a`` made odd."""
    gen = np.random.RandomState((seed + 0x5F3759DF) % (1 << 31))
    a = (gen.randint(0, 1 << 32, size=num_perm, dtype=np.uint64) | 1).astype(np.uint32)
    b = gen.randint(0, 1 << 32, size=num_perm, dtype=np.uint64).astype(np.uint32)
    return a, b


def _window_hashes(doc: bytes, k: int) -> np.ndarray:
    byt = np.frombuffer(doc, np.uint8).astype(np.uint32)
    m = len(byt) - k + 1
    h = np.full((m,), 0x811C9DC5, np.uint32)
    for j in range(k):
        h = (h ^ byt[j : j + m]) * np.uint32(0x01000193)
    h ^= h >> np.uint32(16)
    h *= np.uint32(0x85EBCA6B)
    h ^= h >> np.uint32(13)
    h *= np.uint32(0xC2B2AE35)
    h ^= h >> np.uint32(16)
    return np.unique(h)


def signatures(docs, a: np.ndarray, b: np.ndarray, k: int, *, bits: int = 32,
               chunk: int = 4096) -> np.ndarray:
    """``uint32[len(docs), num_perm]``; an article shorter than ``k``
    gives all ``U32_MAX``.  ``bits < 32`` keeps only the low ``bits`` of
    each permuted hash: the lower-precision control."""
    out = np.full((len(docs), a.shape[0]), U32_MAX, np.uint32)
    mask = np.uint32((1 << bits) - 1) if bits < 32 else U32_MAX
    with np.errstate(over="ignore"):
        for i, doc in enumerate(docs):
            if len(doc) < k:
                continue
            h = _window_hashes(doc, k)
            for s in range(0, len(h), chunk):
                ph = (h[s : s + chunk, None] * a[None, :] + b[None, :]) & mask
                out[i] = np.minimum(out[i], ph.min(axis=0))
    return out


def shingle_ids(doc: bytes, k: int) -> np.ndarray:
    """Sorted unique k-byte shingles of ``doc`` as uint64 (k <= 8)."""
    x = np.frombuffer(doc, np.uint8).astype(np.uint64)
    if len(x) < k:
        return np.zeros((0,), np.uint64)
    m = len(x) - k + 1
    v = np.zeros((m,), np.uint64)
    for j in range(k):
        v |= x[j : j + m] << np.uint64(8 * j)
    return np.unique(v)


def jaccard(sa: np.ndarray, sb: np.ndarray) -> float:
    if not len(sa) and not len(sb):
        return 1.0
    inter = len(np.intersect1d(sa, sb, assume_unique=True))
    return inter / (len(sa) + len(sb) - inter)


class ShingleCache:
    """Shingle sets of one corpus, computed on first use."""

    def __init__(self, docs, k: int):
        self.docs, self.k, self._sets, self._j = docs, k, {}, {}

    def __call__(self, i: int) -> np.ndarray:
        s = self._sets.get(i)
        if s is None:
            s = self._sets[i] = shingle_ids(self.docs[i], self.k)
        return s

    def j(self, a: int, b: int) -> float:
        key = (a, b) if a < b else (b, a)
        v = self._j.get(key)
        if v is None:
            v = self._j[key] = 1.0 if self.docs[a] == self.docs[b] else jaccard(self(a), self(b))
        return v


def true_pairs(family: np.ndarray, cache: ShingleCache, threshold: float) -> set:
    """Every pair inside a planted family whose true Jaccard clears the
    threshold.  Articles of different families are independent random
    bytes (true Jaccard ~0), so no pair outside a family can clear it."""
    groups: dict[int, list[int]] = {}
    for i, f in enumerate(family.tolist()):
        groups.setdefault(f, []).append(i)
    out = set()
    for members in groups.values():
        for x in range(len(members)):
            for y in range(x + 1, len(members)):
                a, b = members[x], members[y]
                if cache.j(a, b) >= threshold:
                    out.add((a, b))
    return out


def clusters_of(reps: np.ndarray) -> list[list[int]]:
    groups: dict[int, list[int]] = {}
    for i, r in enumerate(np.asarray(reps).tolist()):
        groups.setdefault(r, []).append(i)
    return [m for m in groups.values() if len(m) > 1]


def unchained(members: list[int], cache: ShingleCache, threshold: float) -> int:
    """Members of one merged cluster outside its largest component under
    edges of true Jaccard >= threshold: articles merged on no true edge."""
    m = len(members)
    if m > MAX_CLUSTER:
        return m - 1  # no planted family is this large: a broken run
    strong = np.eye(m, dtype=bool)
    for x in range(m):
        for y in range(x + 1, m):
            strong[x, y] = strong[y, x] = cache.j(members[x], members[y]) >= threshold
    left = np.ones(m, bool)
    biggest = 0
    while left.any():
        seen = np.zeros(m, bool)
        seen[int(np.flatnonzero(left)[0])] = True
        frontier = seen.copy()
        while frontier.any():
            nxt = strong[frontier].any(axis=0) & ~seen
            seen |= nxt
            frontier = nxt
        biggest = max(biggest, int(seen.sum()))
        left &= ~seen
    return m - biggest


def compare_reps(reps: np.ndarray, truth: set, cache: ShingleCache,
                 threshold: float) -> dict:
    """``missed_share``: true pairs the program did not put in one cluster,
    as a share of all true pairs; ``unchained_share``: articles the
    program merged on no true edge, as a share of all articles."""
    reps = np.asarray(reps)
    missed = sum(1 for a, b in truth if reps[a] != reps[b])
    bad = sum(unchained(ms, cache, threshold) for ms in clusters_of(reps))
    return {
        "true_pairs": len(truth),
        "missed": missed,
        "missed_share": missed / len(truth) if truth else 0.0,
        "unchained": bad,
        "unchained_share": bad / len(reps),
    }
