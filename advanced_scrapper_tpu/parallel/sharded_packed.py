"""Pod-scale packed dedup: the fused donated tile step over a device mesh.

This module is the single-dispatch plane (``ops.minhash.
make_fused_tile_step`` + ``pipeline/dispatch.py`` — ONE packed
``device_put`` and ONE fused donated dispatch per tile,
launch-count-asserted) *lifted into a shard_map/pjit call over the
mesh*.  The running accumulator is a global
``uint32[n_shards, num_articles, P]`` array sharded one row per device; a
tile group is a global ``uint8[n_shards, rows*(width+8)]`` packed buffer
assembled from per-shard ``jax.device_put``\\ s (one put per shard per
tile — each host puts only its local shards); the step unpacks, computes
block signatures, segment-mins per article, and folds into the DONATED
per-shard accumulator slice — all inside one partitioned dispatch, so each
device's per-tile traffic is exactly 1 put + 1 fused donated dispatch,
the same ledger contract the single-device plane certifies.  Donation
across the partitioned call is the hard part (SNIPPETS.md is pjit's
``donation_vector``/``rebase_donate_argnums`` internals — donation is
rebased per-shard under pjit, which is what makes the in-place
accumulator update survive partitioning); it is asserted per corpus via
``is_deleted()`` exactly like the single-device step.

The cross-shard combine happens ONCE, at end of corpus, in the resolve
epilogue: shard partials meet with ``lax.pmin`` over every mesh axis
(MinHash's min-algebra makes the blockwise + sharded combine exact, so
one combine per corpus suffices), then the standard LSH resolution runs
replicated.  Band
keys for the persistent-index plane come off the same combined signatures
(:func:`make_sharded_keys_epilogue`); the *cross-shard band-key merge*
itself rides the index fleet (``index/fleet.py``) as the host-side plane.

Layering: this module is device math only — jax + ``core``/``ops``.  The
host pipeline around it (encode chunker, the pipelined executor, device
ledger) lives in ``pipeline/dedup.py``; ``parallel`` must never import
``pipeline``/``net``/``index``/``runtime`` (``tools/lint_imports.py``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from advanced_scrapper_tpu.core.hashing import MinHashParams
from advanced_scrapper_tpu.ops.lsh import (
    band_keys,
    band_keys_wide,
    duplicate_rep_bands,
    fine_edge_thresholds,
    resolve_rep_bands,
)
from advanced_scrapper_tpu.ops.minhash import resolve_signature_fn
from advanced_scrapper_tpu.ops.pack import unpack_tile
from advanced_scrapper_tpu.ops.shingle import U32_MAX

__all__ = [
    "assemble_packed_tiles",
    "local_shard_rows",
    "make_sharded_accumulator_init",
    "make_sharded_fused_tile_step",
    "make_sharded_keys_epilogue",
    "make_sharded_resolve_epilogue",
    "mesh_num_shards",
    "shard_row_devices",
]


def _shard_axes(mesh: Mesh) -> tuple:
    """Every mesh axis, as the dim-0 partition spec: a shard is a DEVICE
    (data × seq both count), so an 8-device mesh always runs 8 per-shard
    accumulators regardless of its (dp, sp) factorisation."""
    return tuple(mesh.axis_names)


def mesh_num_shards(mesh: Mesh) -> int:
    """Device count of the mesh = number of accumulator shards."""
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names]))


def _row_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """Dim-0-sharded (one row per device), trailing dims replicated."""
    return NamedSharding(mesh, P(_shard_axes(mesh), *([None] * (ndim - 1))))


def shard_row_devices(mesh: Mesh) -> list:
    """The device owning each row-shard of a dim-0-sharded global array,
    in row order — derived FROM the sharding's index map, never assumed
    from device-list order, so per-shard ``device_put``\\ s always land on
    the device that will own that row."""
    n = mesh_num_shards(mesh)
    sharding = _row_sharding(mesh, 1)
    order: list = [None] * n
    for dev, idx in sharding.devices_indices_map((n,)).items():
        # a 1-shard mesh reports the trivial slice(None) — row 0
        order[idx[0].start or 0] = dev
    return order


def local_shard_rows(mesh: Mesh) -> list[int]:
    """Row-shard indices owned by THIS process ("each host packs tiles
    for its local shard(s)") — all of them on a single-controller host."""
    pi = jax.process_index()
    return [
        i for i, d in enumerate(shard_row_devices(mesh))
        if d.process_index == pi
    ]


def assemble_packed_tiles(mesh: Mesh, shards: list, nbytes: int):
    """Bind per-shard ``uint8[1, nbytes]`` device buffers (already put on
    their row's device — ``shard_row_devices`` order) into ONE global
    ``uint8[n_shards, nbytes]`` sharded array.  Pure metadata: no copy,
    no transfer — the puts already happened, one per shard."""
    return jax.make_array_from_single_device_arrays(
        (mesh_num_shards(mesh), nbytes), _row_sharding(mesh, 2), shards
    )


def make_sharded_accumulator_init(mesh: Mesh, num_perm: int):
    """``init(num_articles=...)`` → the all-``U32_MAX`` (min-identity)
    running accumulator ``uint32[n_shards, num_articles, num_perm]``,
    filled ON DEVICE under the row sharding (no H2D transfer — exactly
    like the single-device path's ``jnp.full``, so the per-shard put
    ledger stays tiles + 1)."""
    nsh = mesh_num_shards(mesh)
    sharding = _row_sharding(mesh, 3)

    @partial(jax.jit, static_argnames=("num_articles",), out_shardings=sharding)
    def init(*, num_articles: int):
        return jnp.full((nsh, num_articles, num_perm), U32_MAX, jnp.uint32)

    return init


def make_sharded_fused_tile_step(mesh: Mesh, params: MinHashParams, backend: str):
    """The PR 9 fused tile step lifted into a shard_map over ``mesh``:
    ``(running, packed) -> running'`` with ``running`` DONATED.

    ``running`` is ``uint32[n_shards, num_articles, P]`` sharded one row
    per device, ``packed`` the ``uint8[n_shards, rows*(width+8)]`` tile
    group (:func:`assemble_packed_tiles`).  Each shard — inside the ONE
    partitioned dispatch — unpacks its own tile, computes block
    signatures, segment-mins them per article, and folds into its OWN
    accumulator row in place (pjit rebases the donation per shard, so no
    per-tile ``[num_articles, P]`` allocation on any device).  No
    collective runs here: shard partials stay local until the
    end-of-corpus epilogue's ``pmin``, keeping the per-tile critical path
    free of cross-device synchronisation.

    ``backend == "oph"`` uses the RAW OPH form (empty bins ``U32_MAX``)
    so the min-combine stays exact across blocks AND shards; the
    epilogues densify once after the ``pmin`` (``ops/oph.py`` on why
    that order is load-bearing).  Cache the returned callable per
    (engine, mesh) — jit then caches per static (rows, width,
    num_articles), the same shape set the single-device chunker draws
    (``pipeline.dedup``'s ``_tile_bs``/``_tile_rows_options``).

    SENTINEL CONTRACT: the raw ``jax.jit`` object is returned (exposing
    ``_cache_size``) so ``pipeline.dedup._get_sharded_fused_step`` can
    wrap it in the recompile sentinel (``obs.devprof.instrument_jit`` →
    ``astpu_jit_compiles_total{kernel="sharded_fused_tile"}``; parallel
    may not grow an obs dependency for it — layering keeps the counting
    at the pipeline seam).
    """
    if backend == "oph":
        from advanced_scrapper_tpu.ops.oph import oph_raw_signatures

        block_fn = oph_raw_signatures
    else:
        block_fn = resolve_signature_fn(backend)
    axes = _shard_axes(mesh)
    spec_run = P(axes, None, None)
    spec_packed = P(axes, None)

    @partial(
        jax.jit,
        static_argnames=("rows", "width", "num_articles"),
        donate_argnums=(0,),
    )
    def sharded_tile_step(
        running: jnp.ndarray,
        packed: jnp.ndarray,
        *,
        rows: int,
        width: int,
        num_articles: int,
    ) -> jnp.ndarray:
        def local(run_l, packed_l):
            # run_l: uint32[1, num_articles, P]; packed_l: uint8[1, nbytes]
            # (the scope names the step's ops on the profiler trace)
            with jax.named_scope("sharded.tile"):
                tok, lens, owners = unpack_tile(packed_l[0], rows, width)
                sigs = block_fn(tok, lens, params)
                part = jax.ops.segment_min(
                    sigs, owners, num_segments=num_articles,
                    indices_are_sorted=False,
                )
                return jnp.minimum(run_l, part[None])

        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(spec_run, spec_packed),
            out_specs=spec_run,
            check_vma=False,
        )(running, packed)

    return sharded_tile_step


def make_sharded_resolve_epilogue(
    mesh: Mesh,
    params: MinHashParams,
    *,
    threshold: float,
    fine_margin: float,
    fine_salt: np.ndarray,
    backend: str,
):
    """``epilogue(running, valid, jump_rounds=...) -> rep`` — the ONE
    end-of-corpus dispatch of the sharded packed plane.

    This is where the cross-shard combine lives: shard partials meet with
    ``lax.pmin`` over every mesh axis (once per corpus, not per
    dispatch), the OPH densify runs once AFTER it, and the standard
    estimator-only LSH resolution (coarse+fine candidate keys → per-band
    candidates → optional per-edge fine bars → verified union-find)
    follows, replicated on every shard — identical math to
    ``ops.lsh.fused_resolve_epilogue``, so the replicated ``int32[N]``
    output is byte-identical to the single-device fused oracle.  ``valid``
    is the replicated host eligibility mask (the async path's
    ``_valid_device`` put, one per shard)."""
    use_oph = backend == "oph"
    axes = _shard_axes(mesh)
    salt = jnp.asarray(params.band_salt)
    fine = jnp.asarray(fine_salt)
    use_fine_margin = bool(fine_salt.shape[0] and fine_margin)

    @partial(jax.jit, static_argnames=("jump_rounds",))
    def sharded_resolve(running, valid, *, jump_rounds: int):
        def local(run_l, valid_l):
            # the scope names the cross-chip combine on the profiler trace
            with jax.named_scope("sharded.pmin"):
                sig = jax.lax.pmin(run_l[0], axes)
            if use_oph:
                from advanced_scrapper_tpu.ops.oph import densify

                sig = densify(sig)
            keys = band_keys(sig, salt)
            if fine.shape[0]:
                keys = jnp.concatenate([keys, band_keys(sig, fine)], axis=1)
            rep_bands = duplicate_rep_bands(keys, valid_l)
            if use_fine_margin:
                thr = fine_edge_thresholds(
                    rep_bands, keys, threshold, fine_margin,
                    num_coarse=params.num_bands,
                )
            else:
                thr = jnp.float32(threshold)
            return resolve_rep_bands(
                rep_bands, sig, valid_l, thr, jump_rounds=jump_rounds
            )

        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axes, None, None), P(None)),
            out_specs=P(None),
            check_vma=False,
        )(running, valid)

    return sharded_resolve


def make_sharded_keys_epilogue(mesh: Mesh, params: MinHashParams, backend: str):
    """``keys_epilogue(running) -> uint32[N, nb, 2]`` — the wide two-lane
    band keys (``ops.lsh.band_keys_wide``) off the pmin-combined sharded
    accumulator, one dispatch, replicated.  Feeds the persistent-index
    plane: the HOST then packs them 64-bit and fans them out per *index*
    shard through ``index.fleet.ShardedIndexClient`` — the cross-shard
    band-key merge is the fleet's consistent-hash ring, not a device
    collective, so a device-mesh shard count and an index-fleet shard
    count never have to agree."""
    use_oph = backend == "oph"
    axes = _shard_axes(mesh)
    salt = jnp.asarray(params.band_salt)

    @jax.jit
    def sharded_keys(running):
        def local(run_l):
            with jax.named_scope("sharded.pmin"):
                sig = jax.lax.pmin(run_l[0], axes)
            if use_oph:
                from advanced_scrapper_tpu.ops.oph import densify

                sig = densify(sig)
            return band_keys_wide(sig, salt)

        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axes, None, None),),
            out_specs=P(None),
            check_vma=False,
        )(running)

    return sharded_keys
