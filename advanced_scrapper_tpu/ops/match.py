"""TPU q-gram screen for entity→article matching.

``match_keywords.py:159-180`` scans O(articles × tickers × names) strings on
CPU — regex word-boundary for ALL-CAPS names, ``rapidfuzz.partial_ratio >
threshold`` otherwise.  The TPU rerouting keeps the *decisions* on the host
(so CSV outputs stay byte-identical) but eliminates almost all of the
quadratic scanning with a device-side **no-false-negative screen**:

1. each article's q-gram set (over ``title\\ntext``) is hashed into a
   2¹⁵-bit bitmap on device;
2. each entity name's q-gram hash indices are gathered from every article's
   bitmap; an (article, name) pair survives only if enough name-grams are
   present.

Soundness ("enough" can never prune a true match).  Let ``m = |name|``,
``D`` the length of the matched part (text or title — matching tries both,
so the screen takes the *weakest* bound over the two), ``e = min(D, m)``,
``t`` the fuzzy threshold, and ``d_max(e) = ⌊2e(1 - t/100)⌋`` the largest
indel distance any window alignment can have at score > t
(``score = 100·2·LCS/(m+|w|)`` and ``|w| ≤ e``).  By the q-gram lemma one
indel destroys at most q gram occurrences, so:

- **exact/ALL-CAPS path**: a word-boundary hit means the name is a substring
  of a part with ``D ≥ m`` and ALL its grams appear → require every kept
  gram, and prune outright when both parts are shorter than the name;
- **fuzzy, part at least name-sized (D ≥ m)**: at most ``q·d_max(m)`` of
  the name's gram occurrences miss the window → at least
  ``kept - q·d_max(m)`` of the *kept* grams appear in the part;
- **fuzzy, short part (D < m)**: the window is a ``D``-length slice of the
  name; its ``D-q+1`` gram positions lose at most ``q·d_max(D)`` to edits →
  require ``(D-q+1) - q·d_max(D)``.  This is only valid when no grams were
  truncated (a tail window may avoid the kept prefix entirely), so
  truncated names with short parts are never screened;
- any bound ≤ 0 → the pair always survives to host verification.

Bloom collisions and part-concatenation only ADD candidates; host
verification kills them, so screened output is golden-equal to unscreened
(tested, including adversarial short-title and truncated-name cases).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from advanced_scrapper_tpu.core.hashing import gram_hashes_np
from advanced_scrapper_tpu.ops.shingle import shingle_hash

NBITS = 1 << 15
DEFAULT_Q = 3
MAX_GRAMS = 96


def prepare_names(
    names: list[bytes],
    q: int = DEFAULT_Q,
    *,
    fuzzy: np.ndarray | None = None,
    nbits: int = NBITS,
    max_grams: int = MAX_GRAMS,
) -> dict:
    """Host-side name tables for :func:`make_screen_step`.

    Returns arrays: ``grams int32[N, max_grams]`` (bit indices, -1 padded),
    ``kept/total int32[N]`` gram counts, ``name_len int32[N]``,
    ``fuzzy bool[N]``.
    """
    n = len(names)
    fuzzy = np.zeros(n, bool) if fuzzy is None else np.asarray(fuzzy, bool)
    grams = np.full((n, max_grams), -1, dtype=np.int32)
    kept = np.zeros(n, dtype=np.int32)
    total = np.zeros(n, dtype=np.int32)
    name_len = np.zeros(n, dtype=np.int32)
    for i, raw in enumerate(names):
        h = gram_hashes_np(raw, q)
        g = (h % nbits).astype(np.int32)[:max_grams]
        grams[i, : len(g)] = g
        kept[i] = len(g)
        total[i] = len(h)
        name_len[i] = len(raw)
    return {
        "grams": grams,
        "kept": kept,
        "total": total,
        "name_len": name_len,
        "fuzzy": fuzzy.copy(),
    }


def _screen_core(
    tokens,
    text_len,
    title_len,
    doc_len,
    grams,
    kept,
    total,
    name_len,
    fuzzy,
    threshold,
    *,
    nbits: int,
    q: int,
):
    """Traceable screen body of the packed single-dispatch
    :func:`make_screen_step` (where the name tables are closure
    constants folded into the compiled step).  ``tokens/doc_len``
    describe the combined ``title\\ntext`` byte rows;
    ``text_len``/``title_len`` are the raw part lengths the soundness
    bounds are computed from.  Returns ``bool[B, N]``: True where
    (article, name) survives the screen."""
    h, valid = shingle_hash(tokens, doc_len, q)
    idx = jnp.where(valid, (h % jnp.uint32(nbits)).astype(jnp.int32), nbits)
    B = tokens.shape[0]
    bitmap = jnp.zeros((B, nbits), dtype=bool)
    bitmap = jax.vmap(lambda bm, ix: bm.at[ix].set(True, mode="drop"))(bitmap, idx)
    safe = jnp.maximum(grams, 0)
    present = jax.vmap(lambda bm: bm[safe])(bitmap)          # [B, N, G]
    present = present & (grams >= 0)[None, :, :]
    count = present.sum(axis=-1).astype(jnp.int32)           # [B, N]

    frac = 2.0 * (1.0 - threshold / 100.0)
    m = name_len[None, :]                                    # [1, N]
    truncated = (kept < total)[None, :]

    def fuzzy_bound(D):                                      # D: [B] part lengths
        D = D[:, None]
        e = jnp.minimum(D, m)
        dmax = jnp.floor(e.astype(jnp.float32) * frac).astype(jnp.int32)
        dmax_m = jnp.floor(m.astype(jnp.float32) * frac).astype(jnp.int32)
        b_long = kept[None, :] - q * dmax_m                  # D >= m
        b_short = (D - q + 1) - q * dmax                     # D <  m, untruncated
        b_short = jnp.where(truncated, 0, b_short)
        return jnp.where(D >= m, b_long, b_short)

    req = jnp.minimum(fuzzy_bound(text_len), fuzzy_bound(title_len))
    fuzzy_keep = (req <= 0) | (count >= jnp.maximum(req, 1))

    part_max = jnp.maximum(text_len, title_len)[:, None]
    exact_keep = (count >= kept[None, :]) & (part_max >= m)

    return jnp.where(fuzzy[None, :], fuzzy_keep, exact_keep)


#: int32 trailer planes of a packed screen tile, in order: combined
#: ``title\ntext`` length, text length, title length, per-row flags
#: (:data:`FLAG_REFINE_OK`), row→article owner (−1 = tail padding).
SCREEN_PLANES = 5

#: flags-plane bit: the row's text side is refine-eligible (non-empty,
#: pure ASCII, not overlong) — the byte-level Myers bound is only sound
#: against the char-level oracle on ASCII text, and that test is host-only.
FLAG_REFINE_OK = 1

#: survivor-mask bits returned by :func:`make_screen_step` (uint8[B, N]):
#: bit 0 = the (article, name) pair survives the q-gram screen; bit 1 =
#: the name's TEXT-side fuzzy score is device-proven ≤ threshold (Myers
#: bound; only ever set on refine-candidate columns).
MASK_SCREEN_KEEP = 1
MASK_TEXT_PRUNED = 2


def make_screen_step(
    tables: dict,
    refine: tuple | None = None,
    *,
    nbits: int = NBITS,
    q: int = DEFAULT_Q,
    refine_block: int = 512,
):
    """Build the SINGLE-dispatch packed screen step of the matcher path:
    ``(packed, threshold) -> (mask uint8[rows, N], owners int32[rows])``
    — unpack the one-buffer tile (``ops.pack``, :data:`SCREEN_PLANES`
    trailer planes), run the q-gram screen, and (with ``refine``) fold
    the Myers alignment bound into the SAME dispatch, all inside one
    jitted call.

    The survivor mask never leaves the device between the two stages: the
    bound consumes it in-kernel and overwrites the refine-candidate
    columns with the prune verdict (:data:`MASK_TEXT_PRUNED`), so a tile
    is exactly 1 put + 1 dispatch — the matcher half of the PR 9
    launch-count ledger.

    ``refine = (masks uint32[K,256], plens int32[K], ok bool[K],
    cols int64[K])`` is a prebuilt ``editdist.build_pattern_masks``
    result plus the entry-column index of each refine candidate; the
    bound runs ALL (row, candidate) pairs via the shared-text kernel
    (``editdist.semiglobal_dist_shared`` — no ``B×K`` text
    materialisation) over the combined ``title\\ntext`` row.  Scanning
    the combined row only ever LOWERS the distance (more substrings), so
    the bound stays an upper bound on the text-side ``partial_ratio``
    and pruning on it stays sound; device-side gates (text strictly
    longer than the pattern, pattern ``ok``) mirror
    ``editdist.prune_mask_tables``, host-only gates ride the flags
    plane.  ``refine=None`` builds the screen-only variant — the
    refine-race controller (``pipeline.matcher.RefineController``) picks
    between the two compiled MODES, not between separate kernels.

    The name tables are closure-captured (constant-folded into the
    compiled step) so no per-tile table transfer exists; cache the
    returned callable per index (``pipeline.matcher`` holds one pair per
    ``EntityIndex``).  Compiled per static ``(rows, width)`` — callers
    keep both bucketed (O(log) shapes; ``pipeline.matcher``'s tile
    chunker and prewarm share one derivation).

    SENTINEL CONTRACT: the raw ``jax.jit`` object is returned (exposing
    ``_cache_size``) so ``pipeline.matcher._screen_steps`` can wrap it in
    the recompile sentinel (``obs.devprof.instrument_jit`` →
    ``astpu_jit_compiles_total{kernel="matcher_screen_step"}``; ops may
    not import obs — layering).
    """
    from advanced_scrapper_tpu.ops.pack import unpack_tile_planes

    grams = np.asarray(tables["grams"])
    kept = np.asarray(tables["kept"])
    total = np.asarray(tables["total"])
    name_len = np.asarray(tables["name_len"])
    fuzzy = np.asarray(tables["fuzzy"])
    if refine is not None and len(refine[3]) == 0:
        refine = None
    if refine is not None:
        r_masks, r_lens, r_ok, r_cols = (np.asarray(a) for a in refine)

    @partial(jax.jit, static_argnames=("rows", "width"))
    def screen_step(packed, threshold, *, rows: int, width: int):
        tok, planes = unpack_tile_planes(packed, rows, width, SCREEN_PLANES)
        doc_len, text_len, title_len, flags, owners = planes
        keep = _screen_core(
            tok, text_len, title_len, doc_len, grams, kept, total,
            name_len, fuzzy, threshold, nbits=nbits, q=q,
        )
        mask = keep.astype(jnp.uint8)
        if refine is not None:
            from advanced_scrapper_tpu.ops.editdist import (
                semiglobal_dist_shared,
            )

            d = semiglobal_dist_shared(
                r_masks, r_lens, tok, doc_len, block=refine_block
            )                                            # [rows, K]
            # bound = 100·(1 − d/(2m)) ≤ threshold, cleared of the
            # division: 100·d ≥ 2m·(100 − threshold).  Every operand is
            # a small-int product (d, m ≤ a few hundred), exact in f32.
            bound_pruned = (
                d.astype(jnp.float32) * 100.0
                >= 2.0 * r_lens[None, :].astype(jnp.float32)
                * (100.0 - threshold)
            )
            prunable = (
                r_ok[None, :]
                & (text_len[:, None] > r_lens[None, :])
                & ((flags & FLAG_REFINE_OK) != 0)[:, None]
                & bound_pruned
            )
            # the survivor mask is consumed and overwritten in-kernel:
            # refine-candidate columns gain the prune bit in place
            mask = mask.at[:, r_cols].set(
                mask[:, r_cols]
                | (prunable.astype(jnp.uint8) << 1)
            )
        return mask, owners

    return screen_step
