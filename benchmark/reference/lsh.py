"""Plain reference for the estimator-only LSH resolution of the sharded
dedup plane, independent of the program.

It imports nothing of ``advanced_scrapper_tpu`` and takes nothing the
program made.  From the configuration's seed and geometry alone:

- :func:`signatures`: every article's device-family MinHash
  (``reference/dedup.py``'s :func:`~benchmark.reference.dedup.signatures`),
  computed over a process pool across the host's cores;
- :func:`resolve`: the resolution the device states (``ops/lsh.py``
  ``band_keys``, ``duplicate_rep_bands``, ``resolve_rep_bands``): coarse
  and fine band keys, each the salted FNV-1a fold finalised by fmix32;
  invalid articles (fewer than k bytes) keyed ``U32_MAX``; per key
  column, articles sorted by (key, index) propose their run head, run
  predecessor and predecessor²; an edge where both ends are valid and
  the equal lanes over ``num_perm``, in float32, reach the threshold;
  each article's representative the smallest index of its connected
  component, by union-find run to the end.

Departures from the device's wording: the device labels components in a
fixed number of pointer-doubling rounds, this by union-find (a resolve
that stops short of the fixpoint differs); bucket padding rows are not
modelled (they sort after every real row and change no real row's
candidates); ``fine_margin`` must be 0.
"""

from __future__ import annotations

import multiprocessing as mp
import os
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from benchmark.reference import dedup as ref

U32_MAX = np.uint32(0xFFFFFFFF)
FNV_OFFSET = np.uint32(0x811C9DC5)
FNV_PRIME = np.uint32(0x01000193)
#: articles per task of the signature pool; fewer in all run inline
CHUNK = 1024


def band_salt(seed: int, num_perm: int, num_bands: int) -> np.ndarray:
    """The coarse band salts: the device family's stream
    (``RandomState((seed + 0x5F3759DF) mod 2**31)``) after its ``a`` and
    ``b`` draws, ``num_bands`` values in ``[1, 2**32)``."""
    gen = np.random.RandomState((seed + 0x5F3759DF) % (1 << 31))
    gen.randint(0, 1 << 32, size=num_perm, dtype=np.uint64)
    gen.randint(0, 1 << 32, size=num_perm, dtype=np.uint64)
    return gen.randint(1, 1 << 32, size=num_bands, dtype=np.uint64).astype(np.uint32)


def subband_salt(num: int, seed: int = 0x5B5C9A02) -> np.ndarray:
    """The fine sub-band salts: ``(i · 0x9E3779B97F4A7C15 + seed) mod 2**32``."""
    i = np.arange(num, dtype=np.uint64)
    return ((i * np.uint64(0x9E3779B97F4A7C15) + np.uint64(seed))
            & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def fmix32(h: np.ndarray) -> np.ndarray:
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def band_keys(sigs: np.ndarray, salts: np.ndarray) -> np.ndarray:
    """``uint32[N, len(salts)]``: each band's rows folded FNV-1a style,
    XOR its salt, fmix32."""
    n, p = sigs.shape
    nb = len(salts)
    rows = sigs.reshape(n, nb, p // nb)
    k = np.full((n, nb), FNV_OFFSET, np.uint32)
    for j in range(p // nb):
        k = (k ^ rows[:, :, j]) * FNV_PRIME
    return fmix32(k ^ salts[None, :])


def run_candidates(col: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``(row, candidate)`` pairs of one key column, self pairs dropped."""
    idx = np.arange(len(col))
    si = np.lexsort((idx, col))
    sk = col[si]
    start = np.r_[True, sk[1:] != sk[:-1]]
    run = np.cumsum(start) - 1
    head = si[start][run]
    pred = np.where(start, si, np.r_[si[:1], si[:-1]])
    pred2 = np.where(np.r_[False, False, run[2:] == run[:-2]], np.r_[si[:2], si[:-2]], si)
    rows = [si[cand != si] for cand in (head, pred, pred2)]
    cands = [cand[cand != si] for cand in (head, pred, pred2)]
    return np.concatenate(rows), np.concatenate(cands)


def resolve(sigs: np.ndarray, valid: np.ndarray, coarse_salt: np.ndarray,
            fine_salt: np.ndarray, threshold: float) -> np.ndarray:
    """``int32[N]`` representatives (see the module docstring)."""
    sigs = np.asarray(sigs, np.uint32)
    n, p = sigs.shape
    keys = band_keys(sigs, coarse_salt)
    if len(fine_salt):
        keys = np.concatenate([keys, band_keys(sigs, fine_salt)], axis=1)
    keys[~valid] = U32_MAX
    pairs = [run_candidates(keys[:, c]) for c in range(keys.shape[1])]
    a = np.concatenate([r for r, _ in pairs])
    b = np.concatenate([c for _, c in pairs])
    both = valid[a] & valid[b]
    edge = np.unique(np.minimum(a[both], b[both]).astype(np.int64) * n
                     + np.maximum(a[both], b[both]))
    lo, hi = edge // n, edge % n
    agree = np.empty(len(edge), np.float32)
    for s in range(0, len(edge), 65536):
        eq = sigs[lo[s : s + 65536]] == sigs[hi[s : s + 65536]]
        agree[s : s + 65536] = eq.sum(axis=1).astype(np.float32) / np.float32(p)
    keep = agree >= np.float32(threshold)
    parent = list(range(n))

    def find(x: int) -> int:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for x, y in zip(lo[keep].tolist(), hi[keep].tolist()):
        rx, ry = find(x), find(y)
        if rx != ry:
            parent[max(rx, ry)] = min(rx, ry)
    return np.array([find(i) for i in range(n)], dtype=np.int32)


def pool(workers: int | None = None) -> ProcessPoolExecutor:
    """Spawned workers (they import only numpy and this package)."""
    return ProcessPoolExecutor(max_workers=workers or os.cpu_count() or 1,
                               mp_context=mp.get_context("spawn"))


def signatures(corpora: list, a: np.ndarray, b: np.ndarray, k: int, *, bits: int = 32,
               executor: ProcessPoolExecutor | None = None) -> list[np.ndarray]:
    """Every article's signatures, one ``uint32[n, num_perm]`` per corpus
    of ``corpora`` (lists of ``bytes``); chunks of :data:`CHUNK` articles
    run on ``executor`` where one is given."""
    if executor is None:
        return [ref.signatures(docs, a, b, k, bits=bits) for docs in corpora]
    futs = [[executor.submit(ref.signatures, docs[s : s + CHUNK], a, b, k, bits=bits)
             for s in range(0, len(docs), CHUNK)] for docs in corpora]
    return [np.concatenate([f.result() for f in fs]) if fs
            else np.zeros((0, len(a)), np.uint32) for fs in futs]


def reference_reps(corpora: list, cfg: dict, *, bits: int = 32,
                   executor: ProcessPoolExecutor | None = None) -> list[np.ndarray]:
    """The reference's representatives of each corpus, for the
    configuration's ``dedup`` block (``fine_margin`` 0)."""
    if cfg.get("fine_margin", 0.0):
        raise ValueError("the reference models fine_margin 0 only")
    a, b = ref.device_family(cfg["seed"], cfg["num_perm"])
    coarse = band_salt(cfg["seed"], cfg["num_perm"], cfg["num_bands"])
    fine = subband_salt(cfg["cand_subbands"]) if cfg["cand_subbands"] else np.zeros(0, np.uint32)
    sigs = signatures(corpora, a, b, cfg["shingle_k"], bits=bits, executor=executor)
    out = []
    for docs, s in zip(corpora, sigs):
        valid = np.fromiter(map(len, docs), np.int64, count=len(docs)) >= cfg["shingle_k"]
        out.append(resolve(s, valid, coarse, fine, cfg["sim_threshold"]))
    return out


def shares(docs: list, family: np.ndarray, reps_list: list, k: int,
           threshold: float) -> list[dict]:
    """``reference/dedup.py``'s :func:`~benchmark.reference.dedup.compare_reps`
    of each representatives array in ``reps_list`` against the planted
    families' true pairs of one corpus (a pool task)."""
    cache = ref.ShingleCache(docs, k)
    truth = ref.true_pairs(family, cache, threshold)
    return [ref.compare_reps(r, truth, cache, threshold) for r in reps_list]
