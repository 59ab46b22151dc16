"""Compile the main path's kernels for a TPU v5e, at real widths, here.

Nothing runs: the TPU compiler that ships with jaxlib compiles for a
described ``v5e:2x2`` topology (on-chip-measurement guide §2), which
catches what interpret mode cannot — VMEM over-use, unaligned slices,
programs that do not fit, kernels that cannot be partitioned — before a
chip minute is spent.  Widths are the production ones (``config.py``:
``block_len`` 4096, ``batch_size`` 1024; row counts from
``pipeline.dedup._tile_bs``).

The topology is described inside a module fixture only: describing it
loads libtpu, which one process at a time may hold, and every xdist
worker imports this file.  All cases live in this one file so a single
worker owns the library.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from advanced_scrapper_tpu.config import DedupConfig
from advanced_scrapper_tpu.core.hashing import make_params
from advanced_scrapper_tpu.core.tokenizer import bucket_len
from advanced_scrapper_tpu.ops.pack import packed_nbytes, pair_tile_nbytes
from advanced_scrapper_tpu.pipeline.dedup import _prewarm_widths, _tile_bs

CFG = DedupConfig()
PARAMS = make_params(
    num_perm=CFG.num_perm, num_bands=CFG.num_bands, shingle_k=CFG.shingle_k, seed=CFG.seed
)
#: article-axis bucket of chip_smoke's default corpus (32,768 articles)
N_ARTICLES = bucket_len(32768, min_bucket=64)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's compile can be written to the persistent cache
    # but never read back here: keep the cache off around these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("width", [4096, 64])
def test_fused_tile_step_compiles(one_chip, width):
    """The packed single-dispatch tile step at the widest bucket (1,024
    rows) and the narrowest (16,384 rows)."""
    from advanced_scrapper_tpu.ops.minhash import make_fused_tile_step

    assert width in _prewarm_widths(CFG)
    rows = _tile_bs(CFG, width)
    assert rows == {4096: 1024, 64: 16384}[width]
    step = make_fused_tile_step(PARAMS, CFG.backend)
    compiled = step.lower(
        _sds((N_ARTICLES, CFG.num_perm), jnp.uint32, one_chip),
        _sds((packed_nbytes(rows, width),), jnp.uint8, one_chip),
        rows=rows, width=width, num_articles=N_ARTICLES,
    ).compile()
    mem = compiled.memory_analysis()
    # the whole step must fit one v5e chip's 16 GB HBM with room to spare
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 4 << 30


def test_pallas_minhash_compiles_to_a_tpu_kernel(one_chip):
    """``ops/pallas_minhash.py`` lowered for the chip, not interpreted:
    the compiled program must hold the Mosaic custom call."""
    from advanced_scrapper_tpu.ops.pallas_minhash import LANE, _pallas_signatures

    rows, width = _tile_bs(CFG, CFG.block_len), CFG.block_len
    compiled = _pallas_signatures.lower(
        _sds((rows, width + LANE), jnp.uint8, one_chip),
        _sds((rows,), jnp.int32, one_chip),
        _sds((CFG.num_perm,), jnp.uint32, one_chip),
        _sds((CFG.num_perm,), jnp.uint32, one_chip),
        k=PARAMS.shingle_k, chunk=128, block_b=32, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_candidate_epilogue_compiles(one_chip):
    """The end-of-corpus candidate epilogue the default (rerank) path
    dispatches once per corpus, at chip_smoke's article bucket."""
    from advanced_scrapper_tpu.ops.lsh import fused_candidate_epilogue, subband_salt

    fine = subband_salt(CFG.cand_subbands)
    compiled = fused_candidate_epilogue.lower(
        _sds((N_ARTICLES, CFG.num_perm), jnp.uint32, one_chip),
        _sds((N_ARTICLES,), jnp.bool_, one_chip),
        np.asarray(PARAMS.band_salt),
        fine,
        densify_oph=False,
    ).compile()
    assert compiled.memory_analysis() is not None


def test_resolve_from_ok_compiles_to_one_loop(one_chip):
    """The one-shot resolve at a 16,384 bucket and the shipped 144
    candidate columns: the propagation rounds compile to one device
    loop that stops at its fixpoint, in one program."""
    from advanced_scrapper_tpu.ops.lsh import resolve_rep_bands_from_ok
    from advanced_scrapper_tpu.pipeline.dedup import _jump_rounds

    n, nc = 16384, 3 * (CFG.num_bands + CFG.cand_subbands)
    compiled = resolve_rep_bands_from_ok.lower(
        _sds((n, nc), jnp.int32, one_chip),
        _sds((n, nc), jnp.bool_, one_chip),
        _sds((n,), jnp.bool_, one_chip),
        jump_rounds=_jump_rounds(n),
    ).compile()
    assert "while" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 1 << 30


@pytest.fixture(scope="module")
def entity_index():
    from chip_smoke import entity_universe

    from advanced_scrapper_tpu.pipeline.matcher import EntityIndex, process_json_data

    return EntityIndex(process_json_data(entity_universe(0)))


@pytest.mark.parametrize("refine", [False, True], ids=["screen", "screen+bound"])
def test_screen_step_compiles(one_chip, entity_index, refine):
    """The matcher's packed screen step over a 500-ticker name table, in
    both modes the refine race picks between, at the 4 kB width bucket."""
    from advanced_scrapper_tpu.ops.match import SCREEN_PLANES, make_screen_step
    from advanced_scrapper_tpu.pipeline.matcher import (
        _match_cfg,
        _refine_candidates,
        _screen_tile_rows,
    )

    width = 4096
    rows = _screen_tile_rows(_match_cfg().screen_tile_bytes, width)
    bound = None
    if refine:  # the same tables pipeline.matcher._screen_steps builds
        cols, (masks, lens, ok) = _refine_candidates(entity_index)
        assert len(cols)
        bound = (masks, lens, ok, cols)
    step = make_screen_step(entity_index.screen_tables(), bound)
    compiled = step.lower(
        _sds((packed_nbytes(rows, width, SCREEN_PLANES),), jnp.uint8, one_chip),
        95.0,
        rows=rows, width=width,
    ).compile()
    assert compiled.memory_analysis() is not None


def test_rerank_tile_step_compiles(one_chip):
    """The rerank settle step at its full tile (1,024 pair rows of
    1,024-lane sketches)."""
    from advanced_scrapper_tpu.ops.rerank import make_rerank_tile_step

    rows, sketch = CFG.rerank_tile_rows, CFG.rerank_sketch
    step = make_rerank_tile_step(rows, sketch)
    compiled = step.lower(
        _sds((CFG.rerank_pair_cap,), jnp.int32, one_chip),
        _sds((pair_tile_nbytes(rows, sketch),), jnp.uint8, one_chip),
    ).compile()
    assert compiled.memory_analysis() is not None


def test_sharded_packed_step_compiles_over_four_chips(topo):
    """The pod-scale packed tile step partitioned over a 4-chip mesh: one
    accumulator row and one packed tile per chip, no collective in the
    per-tile program."""
    from advanced_scrapper_tpu.parallel.sharded_packed import make_sharded_fused_tile_step

    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "seq"))
    width = CFG.block_len
    rows = _tile_bs(CFG, width)
    step = make_sharded_fused_tile_step(mesh, PARAMS, CFG.backend)
    row_sharded = NamedSharding(mesh, P(("data", "seq")))
    compiled = step.lower(
        _sds((4, N_ARTICLES, CFG.num_perm), jnp.uint32, row_sharded),
        _sds((4, packed_nbytes(rows, width)), jnp.uint8, row_sharded),
        rows=rows, width=width, num_articles=N_ARTICLES,
    ).compile()
    text = compiled.as_text()
    assert "all-reduce" not in text and "all-gather" not in text
    assert compiled.memory_analysis() is not None
