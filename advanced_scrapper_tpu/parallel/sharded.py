"""Mesh-sharded dedup — the framework's distributed compute path.

The reference's only multi-node backend is a TCP master/worker star with
20-URL leases (``server1.py:102-138``, SURVEY.md §5.8).  Here distribution is
SPMD over a ``jax.sharding.Mesh`` with XLA collectives on ICI:

- **data axis (dp)** — the batch is sharded; each shard computes local
  MinHash signatures and band keys, then ``all_gather``\\ s the (small) band
  keys so every shard resolves first-seen-wins representatives against the
  *global* corpus.  Band keys are 16 uint32 per article — gathering them is
  64 bytes/article on ICI, three orders of magnitude less than gathering
  articles.
- **seq axis (sp)** — long articles are sharded along the byte axis; each
  shard hashes its slice (after a (k-1)-byte **halo exchange** with
  ``lax.ppermute`` so no shingle is lost at shard boundaries) and partial
  signatures combine with ``lax.pmin`` — MinHash's min-algebra makes
  sequence parallelism exact.
- the LSH bucket-count histogram merges across shards with ``lax.psum``
  (the collective the north star names for bucket merge).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from advanced_scrapper_tpu.core.hashing import MinHashParams
from advanced_scrapper_tpu.ops.lsh import (
    band_keys,
    bucket_histogram,
    candidate_keys,
    duplicate_rep_bands,
    fine_edge_thresholds,
    resolve_rep_bands,
)
from advanced_scrapper_tpu.ops.minhash import (
    minhash_signatures,
    resolve_signature_fn,
    scan_min_signature,
)
from advanced_scrapper_tpu.ops.shingle import shingle_hash


def _data_axis(mesh: Mesh) -> str:
    return mesh.axis_names[0]


def _seq_axis(mesh: Mesh) -> str:
    return mesh.axis_names[1] if len(mesh.axis_names) > 1 else None


def make_sharded_dedup(
    mesh: Mesh,
    params: MinHashParams,
    *,
    threshold: float = 0.7,
    jump_rounds: int = 16,
    hist_bins: int = 1 << 16,
    backend: str = "scan",
    cand_subbands: int | None = None,
    fine_margin: float | None = None,
):
    """Build the jitted batch-sharded dedup step for ``mesh``.

    Returns ``step(tokens, lengths) -> (rep, hist)`` where ``tokens`` is
    ``uint8[B, L]`` sharded on the data axis, ``rep`` is the replicated
    ``int32[B]`` global first-seen representative array, and ``hist`` the
    psum-merged bucket histogram.  ``backend="oph"`` swaps the dense
    signature kernel for one-permutation hashing (``ops/oph.py``) — data
    shards own whole rows, so densification is safe shard-local.

    Resolution is the same verified-candidate connected-components as the
    batch engine (``duplicate_rep_bands`` + ``resolve_rep_bands``, with
    ``cand_subbands`` fine candidate bands): the streamed path must not
    recall less than the certified one-shot path.
    """
    data = _data_axis(mesh)
    salt = jnp.asarray(params.band_salt)
    k = params.shingle_k
    _sig_fn = resolve_signature_fn(backend)
    if cand_subbands is None or fine_margin is None:
        # single source of the defaults: the certified engine's config
        from advanced_scrapper_tpu.config import DedupConfig

        if cand_subbands is None:
            cand_subbands = DedupConfig().cand_subbands
        if fine_margin is None:
            fine_margin = DedupConfig().fine_margin

    def local_step(tokens, lengths):
        # tokens: uint8[B/n, L] local shard
        sig = _sig_fn(tokens, lengths, params)
        keys = band_keys(sig, salt)
        valid = lengths >= k
        all_keys = candidate_keys(sig, salt, cand_subbands)
        # Cross-shard candidate resolution: gather the compact per-article
        # summaries (keys: 64-192 B, sig: 512 B per article) — never the text.
        g_keys = jax.lax.all_gather(all_keys, data, axis=0, tiled=True)
        g_sig = jax.lax.all_gather(sig, data, axis=0, tiled=True)
        g_valid = jax.lax.all_gather(valid, data, axis=0, tiled=True)
        rep_bands = duplicate_rep_bands(g_keys, g_valid)
        if cand_subbands and fine_margin:
            thr = fine_edge_thresholds(
                rep_bands, g_keys, threshold, fine_margin,
                num_coarse=params.num_bands,
            )
        else:
            thr = jnp.float32(threshold)
        rep = resolve_rep_bands(
            rep_bands, g_sig, g_valid, thr, jump_rounds=jump_rounds
        )
        # North-star bucket merge: psum of per-shard histograms over ICI.
        hist = bucket_histogram(keys, valid, nbins=hist_bins)
        hist = jax.lax.psum(hist, data)
        return rep, hist

    # Keep the minhash scan inside shard_map so XLA never sees the global
    # batch; outputs are replicated.
    spec_in = (P(data, None), P(data))
    spec_out = (P(None), P(None))
    sharded = jax.shard_map(
        local_step, mesh=mesh, in_specs=spec_in, out_specs=spec_out,
        check_vma=False,
    )
    return jax.jit(sharded)


def make_seq_sharded_signatures(
    mesh: Mesh,
    params: MinHashParams,
    block_len: int,
    *,
    chunk: int = 512,
):
    """Build the jitted sequence-parallel MinHash kernel for ``mesh``.

    Byte axis sharded over the mesh's seq axis, exactly equivalent to the
    single-device kernel: each shard hashes its byte slice extended by a
    (k-1)-byte halo fetched from the next shard via ``lax.ppermute``, masks
    shingle validity against *global* positions, scans permutation minima in
    ``chunk``-sized pieces (peak intermediate ``[B, chunk, 128]`` per shard),
    and combines partials with ``lax.pmin`` over the seq axis.  The
    wrap-around halo on the last shard is always masked out (global positions
    past the text end are invalid by construction).
    """
    data = _data_axis(mesh)
    seq = _seq_axis(mesh)
    if seq is None:
        raise ValueError("mesh has no seq axis")
    n_seq = mesh.shape[seq]
    a32 = jnp.asarray(params.a32)
    b32 = jnp.asarray(params.b32)
    k = params.shingle_k
    if block_len % n_seq:
        raise ValueError(f"block length {block_len} not divisible by seq={n_seq}")
    Ls = block_len // n_seq

    def kernel(tok_l, len_l):
        # tok_l: uint8[Bl, Ls]; len_l: int32[Bl] (full lengths, replicated on seq)
        idx = jax.lax.axis_index(seq)
        # halo: first k-1 bytes of the *next* shard (wraps; masked below)
        perm = [(i, (i - 1) % n_seq) for i in range(n_seq)]
        halo = jax.lax.ppermute(tok_l[:, : k - 1], seq, perm)
        ext = jnp.concatenate([tok_l, halo], axis=1)  # [Bl, Ls + k - 1]
        start = idx * Ls
        # valid shingle at local pos i  ⇔  global pos start+i ≤ len-k
        eff = jnp.clip(len_l - start, 0, Ls + k - 1).astype(jnp.int32)
        h, valid = shingle_hash(ext, eff, k)  # [Bl, Ls]
        partial_sig = scan_min_signature(h, valid, a32, b32, chunk)
        return jax.lax.pmin(partial_sig, seq)

    sharded = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(data, seq), P(data)),
        out_specs=P(data, None),
        check_vma=False,
    )
    return jax.jit(sharded)


# jit-cache for the convenience wrapper: keyed on mesh (hashable) and params
# identity (held strongly here, so the id cannot be recycled while cached).
_SEQ_KERNEL_CACHE: dict = {}


def seq_sharded_signatures(tokens, lengths, params: MinHashParams, mesh: Mesh, *, chunk: int = 512):
    """Convenience wrapper around :func:`make_seq_sharded_signatures`; reuses
    compiled kernels across calls with the same (mesh, params, shape)."""
    L = tokens.shape[-1]
    key = (mesh, id(params), L, chunk)
    entry = _SEQ_KERNEL_CACHE.get(key)
    if entry is None:
        entry = (make_seq_sharded_signatures(mesh, params, L, chunk=chunk), params)
        _SEQ_KERNEL_CACHE[key] = entry
    return entry[0](tokens, lengths)


def sharded_dedup_step(tokens, lengths, params: MinHashParams, mesh: Mesh, **kw):
    """One-shot convenience wrapper around :func:`make_sharded_dedup`."""
    step = make_sharded_dedup(mesh, params, **kw)
    return step(tokens, lengths)


def shard_batch(tokens, lengths, mesh: Mesh):
    """Place host arrays on the mesh with batch sharded over the data axis."""
    data = _data_axis(mesh)
    t = jax.device_put(tokens, NamedSharding(mesh, P(data, None)))
    l = jax.device_put(lengths, NamedSharding(mesh, P(data)))
    return t, l
