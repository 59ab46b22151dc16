"""MinHash signature kernel.

The TPU successor of datasketch-style CPU MinHash (the oracle in
``cpu/oracle.py`` reproduces datasketch exactly; see ``core/hashing.py`` for
why the device uses a 32-bit multiply-add family instead of 61-bit Mersenne
arithmetic).  Configuration fixed by the north star (BASELINE.json): k=5 byte
shingles, 128 permutations.

Shape/memory strategy: the naive formulation materialises
``uint32[B, S, 128]`` (shingles × permutations).  We instead scan over
shingle-position chunks, keeping a running per-permutation minimum — peak
intermediate is ``[B, chunk, 128]`` and XLA fuses the multiply-add into the
min-reduction.  Long articles are handled *blockwise* upstream
(``core.tokenizer.encode_blocks``; k-1 byte overlap) and block signatures are
combined here with a segment-min — the same algebra lets sequence-parallel
shards combine partial signatures with ``lax.pmin`` over the mesh's ``seq``
axis (``parallel/sharded.py``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from advanced_scrapper_tpu.core.hashing import MinHashParams
from advanced_scrapper_tpu.ops.shingle import U32_MAX, shingle_hash


def scan_min_signature(
    h: jnp.ndarray,
    valid: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    chunk: int,
) -> jnp.ndarray:
    """Per-permutation minimum over shingle hashes, scanned in chunks.

    ``h/valid`` are ``[B, S]``; peak intermediate is ``[B, chunk, P]``
    (XLA fuses the multiply-add into the min-reduce).  Shared by the
    single-device kernel and the sequence-parallel shard kernel.
    """
    B, S = h.shape
    P = a.shape[0]
    # Pad shingle axis to a chunk multiple, transpose chunks to the scan axis.
    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    h = jnp.pad(h, ((0, 0), (0, pad)))
    valid = jnp.pad(valid, ((0, 0), (0, pad)))
    h_t = h.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
    v_t = valid.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

    def body(sig, xs):
        hc, vc = xs  # uint32[B, chunk], bool[B, chunk]
        ph = a[None, None, :] * hc[:, :, None] + b[None, None, :]
        ph = jnp.where(vc[:, :, None], ph, U32_MAX)
        return jnp.minimum(sig, ph.min(axis=1)), None

    init = jnp.full((B, P), U32_MAX, dtype=jnp.uint32)
    sig, _ = jax.lax.scan(body, init, (h_t, v_t))
    return sig


@partial(jax.jit, static_argnames=("k", "chunk"))
def _signatures_impl(
    tokens: jnp.ndarray,
    lengths: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    k: int,
    chunk: int,
) -> jnp.ndarray:
    h, valid = shingle_hash(tokens, lengths, k)
    return scan_min_signature(h, valid, a, b, chunk)


def minhash_signatures(
    tokens: jnp.ndarray,
    lengths: jnp.ndarray,
    params: MinHashParams,
    *,
    chunk: int = 128,
) -> jnp.ndarray:
    """Compute ``uint32[B, num_perm]`` MinHash signatures.

    Rows with fewer than k valid bytes yield all-``U32_MAX`` signatures;
    callers must mask them out of LSH (``lsh.duplicate_reps(valid=...)``).

    ``chunk=128`` is the measured-best scan granularity on v5e (2026-07
    sweep: ~845k articles/s full-step at [32768, 1024] vs ~715k at 512).
    The multiply-add per (shingle, permutation) is irreducible for the
    dense formulation and the MXU cannot help (min-reduce is not a
    matmul), but the kernel is NOT at VPU ceiling: the measured 778k
    articles/s works out to ~5% of the nominal v5e 32-bit VPU rate
    (~17% counting int32-multiply decomposition into 16-bit passes) and
    ~1.3% of HBM bandwidth — roofline arithmetic in DESIGN.md
    "Roofline", MFU field in bench JSON.  Headroom exists in principle;
    at 15.5× the 50k/s target it is not the binding constraint.  See
    ``ops/oph.py`` for the measured alternative that trades multiplies
    for a sort.

    ``ASTPU_MINHASH_BACKEND=pallas`` swaps in the fused Pallas kernel
    (``ops/pallas_minhash.py``) — bit-identical output, measured slower on
    v5e, kept as the hand-written reference for the op.
    """
    from advanced_scrapper_tpu.ops.pallas_minhash import (
        minhash_signatures_pallas,
        pallas_enabled,
    )

    if pallas_enabled() and params.num_perm == 128:
        return minhash_signatures_pallas(tokens, lengths, params)
    return _signatures_impl(
        tokens,
        lengths,
        jnp.asarray(params.a32),
        jnp.asarray(params.b32),
        k=params.shingle_k,
        chunk=chunk,
    )


def resolve_signature_fn(backend: str):
    """Single dispatch point for the signature backend.

    ``scan`` — the dense kernel (measured fastest on v5e); ``pallas`` —
    the fused hand-written kernel; ``oph`` — one-permutation hashing
    (densified; whole-document rows only — block/shard-split callers must
    use ``ops.oph.oph_raw_signatures`` and densify after the min-combine).
    Unknown names raise instead of silently running scan.
    """
    if backend == "scan":
        return minhash_signatures
    if backend == "pallas":
        from advanced_scrapper_tpu.ops.pallas_minhash import (
            minhash_signatures_pallas,
        )

        return minhash_signatures_pallas
    if backend == "oph":
        from advanced_scrapper_tpu.ops.oph import oph_signatures

        return oph_signatures
    raise ValueError(f"unknown signature backend {backend!r}; use scan|pallas|oph")


@partial(jax.jit, static_argnames=("num_articles",))
def combine_block_signatures(
    block_sigs: jnp.ndarray, owners: jnp.ndarray, *, num_articles: int
) -> jnp.ndarray:
    """Per-article signature = elementwise min over its blocks' signatures.

    MinHash is a min-reduction over the shingle set, and the blockwise split
    (with k-1 overlap) preserves the shingle set, so segment-min over blocks
    is *exact*, not an approximation.  TPU analogue of the reference's
    chunked streaming (``match_keywords.py:227-230``).
    """
    return jax.ops.segment_min(
        block_sigs, owners, num_segments=num_articles, indices_are_sorted=False
    )


def make_fused_tile_step(params: MinHashParams, backend: str):
    """Build the SINGLE-dispatch per-tile step of the packed dedup path:
    ``(running, packed) -> running'`` — unpack the one-buffer tile
    (``ops.pack``), compute block signatures, segment-min them per
    article, and fold into the DONATED running accumulator, all inside
    one jitted call.

    One dispatch per tile, not one for the block signatures and a
    second for the fold: each dispatch is a host round trip (SEDD's
    per-batch launch-minimisation argument — PAPERS.md).  ``running``
    is donated, so the device updates the accumulator in place, with
    no per-tile ``[num_articles, P]`` allocation.

    ``backend == "oph"`` uses the RAW OPH form (empty bins ``U32_MAX``)
    so the min-combine stays exact; callers densify once after the last
    tile (``ops/oph.py`` on why that order is load-bearing).  The
    params arrays are closure-captured (constant-folded into the
    compiled step), so cache the returned callable per (params,
    backend) — ``pipeline.dedup.NearDupEngine`` holds one per engine.

    SENTINEL CONTRACT: this builder returns the raw ``jax.jit`` object
    (exposing ``_cache_size``) — the pipeline layer wraps it in the
    recompile sentinel (``obs.devprof.instrument_jit``, counting every
    jit-cache miss on ``astpu_jit_compiles_total{kernel=
    "dedup_fused_tile"}``; ops may not import obs — layering).  Wrapping
    the step in anything that hides ``_cache_size`` silently blinds the
    sentinel AND the prewarm-set gate tests.
    """
    if backend == "oph":
        from advanced_scrapper_tpu.ops.oph import oph_raw_signatures

        block_fn = oph_raw_signatures
    else:
        block_fn = resolve_signature_fn(backend)

    from advanced_scrapper_tpu.ops.pack import unpack_tile

    @partial(
        jax.jit,
        static_argnames=("rows", "width", "num_articles"),
        donate_argnums=(0,),
    )
    def fused_tile_step(
        running: jnp.ndarray,
        packed: jnp.ndarray,
        *,
        rows: int,
        width: int,
        num_articles: int,
    ) -> jnp.ndarray:
        with jax.named_scope("minhash.fused_tile"):  # its ops on the trace
            tok, lens, owners = unpack_tile(packed, rows, width)
            sigs = block_fn(tok, lens, params)
            part = jax.ops.segment_min(
                sigs, owners, num_segments=num_articles,
                indices_are_sorted=False,
            )
            return jnp.minimum(running, part)

    return fused_tile_step
