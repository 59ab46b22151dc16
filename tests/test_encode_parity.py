"""Parity certification for the host-path overhaul (PR 2).

The vectorized bucketed encoder (one corpus blob + indexed native cuts,
bucketed tail batches), the kernel backends and the mesh-sharded plane
are pure performance work — every byte of output must match a reference:

- signatures / dedup_reps through the native encoder vs the pure-Python
  ``core.tokenizer.encode_blocks`` loop (the behavioural oracle);
- ``encode_blocks_ranges`` vs ``encode_blocks`` on each width group;
- ``bucket_widths`` vs the scalar ``bucket_len``;
- the scan and pallas backends vs the host twin of the device hash
  family (``cpu.oracle.device_family_signatures``) through banding;
- ``dedup_reps_sharded`` (the mesh-sharded packed plane) vs the
  single-device async engine's representatives.
"""

from __future__ import annotations

import numpy as np
import pytest

import advanced_scrapper_tpu.cpu.hostbatch as hb
from advanced_scrapper_tpu.core.tokenizer import (
    bucket_len,
    bucket_widths,
    encode_blocks,
)


def _ragged_corpus(rng: np.random.RandomState, n: int) -> list[bytes]:
    """Adversarial mix: empty docs, sub-shingle docs, exact power-of-two
    lengths (bucket edges), long blockwise docs, planted duplicates."""
    docs: list[bytes] = []
    specials = [0, 1, 4, 63, 64, 65, 128, 4096, 4097]
    for i in range(n):
        if i < len(specials):
            ln = specials[i]
        elif i >= 8 and rng.rand() < 0.25:
            docs.append(docs[rng.randint(0, i)])
            continue
        else:
            ln = int(rng.randint(5, 9000))
        docs.append(rng.randint(32, 127, size=ln, dtype=np.uint8).tobytes())
    return docs


def test_bucket_widths_matches_bucket_len():
    rng = np.random.RandomState(0)
    lens = np.r_[0, 1, 63, 64, 65, 4095, 4096, 4097,
                 rng.randint(0, 1 << 22, 5000)]
    got = bucket_widths(lens, max_bucket=4096)
    want = [bucket_len(max(int(x), 1), max_bucket=4096) for x in lens]
    assert got.tolist() == want


def test_encode_blocks_ranges_matches_encode_blocks():
    if hb.hostbatch_backend() != "native":
        pytest.skip("no C++ toolchain")
    rng = np.random.RandomState(3)
    docs = _ragged_corpus(rng, 64)
    lens = np.fromiter(map(len, docs), np.int64, count=len(docs))
    offsets = np.zeros((len(docs) + 1,), np.int64)
    np.cumsum(lens, out=offsets[1:])
    blob = b"".join(docs)
    for w, overlap in ((64, 4), (256, 4), (1024, 0)):
        sel = np.asarray(
            [i for i in range(len(docs)) if i % 3 == 0], np.int64
        )
        counts = hb.block_counts(lens[sel], w, overlap)
        tok_s, len_s, own_s = hb.encode_blocks_ranges(
            blob, offsets[sel], lens[sel], counts, w, overlap
        )
        tok_r, len_r, own_r = encode_blocks(
            [docs[i] for i in sel], w, overlap=overlap
        )
        assert (tok_s == tok_r).all()
        assert (len_s == len_r).all()
        assert (own_s == own_r).all()


def test_signatures_native_vs_python_paths(monkeypatch):
    """dedup_reps and signatures byte-identical between the native indexed
    encoder and the pure-Python loop (the real parity assertion)."""
    from advanced_scrapper_tpu.pipeline.dedup import NearDupEngine

    rng = np.random.RandomState(11)
    corpus = _ragged_corpus(rng, 96)
    sigs_native = NearDupEngine().signatures(corpus)
    reps_native = NearDupEngine().dedup_reps(corpus)

    # Patch the RANGE encoder (the one the ragged path actually calls) and
    # the blob encoder behind encode_blocks, so the oracle run is genuinely
    # the pure-Python loop.
    monkeypatch.setattr(hb, "encode_blocks_ranges", lambda *a, **k: None)
    monkeypatch.setattr(hb, "encode_blocks_native", lambda *a, **k: None)
    sigs_py = NearDupEngine().signatures(corpus)
    reps_py = NearDupEngine().dedup_reps(corpus)

    assert (sigs_native == sigs_py).all()
    assert (reps_native == reps_py).all()


def test_single_dispatch_backend_parity_through_banding():
    """scan vs pallas through the packed single-dispatch path,
    bit-identical THROUGH BANDING: signatures equal the host twin of the
    device hash family, and coarse+fine candidate keys (the fused
    epilogue) and resolved representatives agree across both backends —
    the backend-parity gate for the fused tile step."""
    from advanced_scrapper_tpu.config import DedupConfig
    from advanced_scrapper_tpu.cpu.oracle import device_family_signatures
    from advanced_scrapper_tpu.pipeline.dedup import NearDupEngine

    rng = np.random.RandomState(7)
    # pallas runs interpret-mode on CPU: keep the corpus/block small
    docs = []
    for i in range(48):
        if i >= 4 and rng.rand() < 0.3:
            docs.append(docs[rng.randint(0, i)])
        else:
            docs.append(
                rng.randint(32, 127, size=int(rng.randint(5, 2000)),
                            dtype=np.uint8).tobytes()
            )
    shape = dict(block_len=1024, batch_size=64)
    routes = {
        "scan": DedupConfig(backend="scan", **shape),
        "pallas": DedupConfig(backend="pallas", **shape),
    }
    outs = {}
    for name, cfg in routes.items():
        eng = NearDupEngine(cfg)
        sigs, keys = eng.signatures_and_keys(docs)
        outs[name] = (sigs, keys, eng.dedup_reps(docs))
    want_sigs = device_family_signatures(docs, eng.params)
    _, ref_keys, ref_reps = outs["scan"]
    for name, (sigs, keys, reps) in outs.items():
        assert (sigs == want_sigs).all(), name
        assert (keys == ref_keys).all(), name
        assert (reps == ref_reps).all(), name


def test_dedup_reps_sharded_matches_async_engine():
    """The production mesh path (NearDupEngine.dedup_reps_sharded → fused
    device combine) must resolve exactly like the single-device async
    engine on the same corpus."""
    import jax

    from advanced_scrapper_tpu.core.mesh import build_mesh
    from advanced_scrapper_tpu.pipeline.dedup import NearDupEngine

    rng = np.random.RandomState(9)
    texts: list[bytes] = []
    for i in range(80):
        if i >= 4 and rng.rand() < 0.3:
            texts.append(texts[rng.randint(0, i)])
        else:
            texts.append(
                rng.randint(32, 127, size=rng.randint(0, 9000),
                            dtype=np.uint8).tobytes()
            )
    eng = NearDupEngine()
    want = np.asarray(eng.dedup_reps_async(texts))[: len(texts)]
    mesh = build_mesh(len(jax.devices()), 1)
    got = eng.dedup_reps_sharded(texts, mesh)
    assert (got == want).all()
    # step cache: second corpus (same mesh, same article bucket) reuses
    # the compiled steps — no new cache entries
    n_entries = len(eng._sharded_steps)
    texts2 = texts[::-1]
    want2 = np.asarray(eng.dedup_reps_async(texts2))[: len(texts2)]
    assert (eng.dedup_reps_sharded(texts2, mesh) == want2).all()
    assert len(eng._sharded_steps) == n_entries
