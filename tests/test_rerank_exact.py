"""The precision tier's exact re-settle on sorted numpy shingle ids.

- ``exact_jaccard(shingle_ids(a, k), shingle_ids(b, k))`` equals the
  oracle's ``jaccard(shingle_set(a, k), shingle_set(b, k))`` as a float
  (``==``), across the Jaccard knee, for the packed (``k ≤ 8``) and the
  record (``k > 8``) ids, on ``str`` with non-ASCII text and lone
  surrogates, and below ``k``; the ids decode to the oracle's set;
- ``bottom_sketch`` is byte-identical to its formula before it took its
  ids from ``shingle_ids``;
- the whole tier, with margin pairs in both waves, rewrites the same
  matrix, provenance, evictions and stats as with the oracle's set path.
"""

from __future__ import annotations

import numpy as np
import pytest

from advanced_scrapper_tpu.config import DedupConfig
from advanced_scrapper_tpu.core.hashing import make_params
from advanced_scrapper_tpu.cpu.oracle import jaccard, mutate_to_jaccard, shingle_set
from advanced_scrapper_tpu.ops import rerank as oprr
from advanced_scrapper_tpu.pipeline.rerank import RerankTier


def _knee_pairs(k: int, seed: int, n: int = 24) -> list[tuple[bytes, bytes]]:
    """Random printable bases of 20 B–6 kB and mutants targeted at true
    Jaccard 0.55–0.85, plus unrelated pairs; a small alphabet for short
    k, so shingles repeat and collide across documents."""
    rng = np.random.RandomState(seed)
    hi = 32 + (4 if k <= 2 else 95)
    out = []
    for _ in range(n):
        base = rng.randint(32, hi, size=int(rng.randint(20, 6000)), dtype=np.uint8).tobytes()
        out.append((base, mutate_to_jaccard(rng, base, rng.uniform(0.55, 0.85))))
    for _ in range(4):
        a = rng.randint(32, hi, size=int(rng.randint(1, 500)), dtype=np.uint8).tobytes()
        b = rng.randint(32, hi, size=int(rng.randint(1, 500)), dtype=np.uint8).tobytes()
        out.append((a, b))
    return out


_TEXT = "naïve café — 日本語のニュース, Zürich ☃ \ud800 lone \udfff surrogates \U0001f600 "
_CASES = [
    *[pytest.param(k, _knee_pairs(k, 100 + k), id=f"knee-k{k}") for k in (1, 5, 8, 9, 16)],
    pytest.param(5, [(_TEXT * 3, _TEXT * 2 + "ж" * 40), (_TEXT, _TEXT[::-1])], id="str-k5"),
    pytest.param(9, [(_TEXT * 3, _TEXT * 2 + "\udc80" * 9), (_TEXT, _TEXT)], id="str-k9"),
    pytest.param(5, [(b"abcd", b""), ("ab", "\ud800")], id="both-empty-k5"),
    pytest.param(12, [(b"short", b"eleven-byte"), (b"", "x")], id="both-empty-k12"),
    pytest.param(5, [(b"abcdefgh", b"abc"), ("", "héllo wörld")], id="one-empty-k5"),
    pytest.param(16, [(b"a" * 15, bytes(range(40))), (bytes(range(40)), b"")], id="one-empty-k16"),
]


def _decode(ids: np.ndarray, k: int) -> set[bytes]:
    if k <= 8:
        return {int(x).to_bytes(8, "little")[:k] for x in ids}
    return {x.tobytes() for x in ids}


@pytest.mark.parametrize("k, pairs", _CASES)
def test_exact_jaccard_equals_oracle_set_path(k, pairs):
    for a, b in pairs:
        ia, ib = oprr.shingle_ids(a, k), oprr.shingle_ids(b, k)
        sa, sb = shingle_set(a, k), shingle_set(b, k)
        assert _decode(ia, k) == sa and _decode(ib, k) == sb
        assert ia.size == len(sa) and (np.unique(ia) == ia).all()
        assert oprr.exact_jaccard(ia, ib) == jaccard(sa, sb), (k, len(a), len(b))
        assert oprr.exact_jaccard(ib, ia) == jaccard(sb, sa)


def _bottom_sketch_before(text, k: int, size: int) -> np.ndarray:
    """``bottom_sketch`` as written before it took its ids from
    ``shingle_ids``."""
    raw = text.encode("utf-8", errors="replace") if isinstance(text, str) else bytes(text)
    out = np.full((size,), oprr.PAD, np.uint32)
    if len(raw) < k:
        return out
    win = np.lib.stride_tricks.sliding_window_view(np.frombuffer(raw, np.uint8), k)
    ids = np.zeros(win.shape[0], np.uint64)
    for j in range(min(k, 8)):
        ids |= win[:, j].astype(np.uint64) << np.uint64(8 * j)
    m64 = np.uint64(0xFFFFFFFFFFFFFFFF)
    for j in range(8, k):
        ids = ((ids * np.uint64(0x100000001B3)) & m64) ^ win[:, j].astype(np.uint64)
    h = (oprr._mix64(np.unique(ids)) >> np.uint64(32)).astype(np.uint32)
    h = np.unique(h)
    h = h[h != oprr.PAD]
    m = min(size, h.size)
    out[:m] = h[:m]
    return out


@pytest.mark.parametrize("k", (5, 12))
def test_bottom_sketch_byte_identical_to_pre_change_formula(k):
    rng = np.random.RandomState(7 + k)
    texts = [a for pair in _knee_pairs(k, 300 + k, n=12) for a in pair]
    texts += [_TEXT * 4, b"", b"abc", b"x" * k, bytes(range(256)) * 30]
    for size in (16, 256, 1024):
        for t in texts + [rng.bytes(int(rng.randint(0, 3000)))]:
            got = oprr.bottom_sketch(t, k, size)
            assert got.dtype == np.uint32
            assert got.tobytes() == _bottom_sketch_before(t, k, size).tobytes()


def _chain_inputs(seed: int = 11, families: int = 10):
    """The tier's inputs for chains ``a → b → c`` of 200-byte knee mutants
    (12-byte spans replaced, so J(a,b) ≈ J(b,c) ≈ 0.85 and J(a,c) ≈ 0.72)
    plus a mutant ``d`` of ``a`` with both spans replaced (J ≈ 0.72).

    The candidate matrix proposes ``(a,b)``, ``(b,c)`` and ``(a,d)`` only
    and the signatures share no band, so ``(a,d)`` is a borderline pair of
    the margin pass, and ``(a,c)``, which only the cluster brings, is a
    margin pair of wave 2.  At 256 sketch lanes these short documents'
    sketches are exact."""
    rng = np.random.RandomState(seed)

    def swap(doc: bytes, at: int) -> bytes:
        return doc[:at] + rng.randint(32, 127, 12, dtype=np.uint8).tobytes() + doc[at + 12 :]

    raw, cells = [], []
    for _ in range(families):
        a = rng.randint(32, 127, 200, dtype=np.uint8).tobytes()
        b = swap(a, 40)
        c = swap(b, 140)
        d = swap(swap(a, 30), 150)
        i = len(raw)
        raw += [a, b, c, d, rng.randint(32, 127, 200, dtype=np.uint8).tobytes()]
        cells += [(i + 1, i), (i + 2, i + 1), (i + 3, i)]
    order = rng.permutation(len(raw))
    where = np.argsort(order)
    raw = [raw[o] for o in order]
    n_bucket = len(raw) + 3  # pad rows, invalid
    rep_bands = np.tile(np.arange(n_bucket, dtype=np.int32)[:, None], (1, 2))
    for row, cand in cells:
        rep_bands[where[row], 0] = where[cand]
    sigs = rng.randint(0, 1 << 32, (n_bucket, 128), dtype=np.uint64).astype(np.uint32)
    valid = np.arange(n_bucket) < len(raw)
    return raw, sigs, rep_bands, valid


def _tier_result(tier, args):
    out = np.asarray(tier(*args))
    return out, dict(tier.last_provenance), set(tier.last_evicted), dict(tier.stats)


def test_tier_rewrites_same_matrix_as_oracle_set_path(monkeypatch):
    cfg = DedupConfig(rerank_tile_rows=64, rerank_sketch=256)
    params = make_params(
        num_perm=cfg.num_perm, num_bands=cfg.num_bands, shingle_k=cfg.shingle_k, seed=cfg.seed
    )
    args = _chain_inputs()
    got = _tier_result(RerankTier(cfg, params), args)
    # the tier as it was before: oracle sets, and sketches by the formula
    # that did not take its ids from shingle_ids
    monkeypatch.setattr(oprr, "shingle_ids", shingle_set)
    monkeypatch.setattr(oprr, "exact_jaccard", jaccard)
    monkeypatch.setattr(oprr, "bottom_sketch", _bottom_sketch_before)
    want = _tier_result(RerankTier(cfg, params), args)

    stats = got[3]
    # borderline pairs settled in the margin pass, and wave-2 margin pairs
    # beyond them, all under the cap
    assert 0 < stats["borderline"] < stats["exact_checks"] < cfg.rerank_exact_cap, stats
    assert set(got[1].values()) == {"margin"}
    assert got[0].tobytes() == want[0].tobytes()
    assert got[1:] == want[1:]
