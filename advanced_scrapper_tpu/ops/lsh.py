"""16-band LSH bucketing and device-side first-seen-wins deduplication.

The reference dedups with pandas ``drop_duplicates(keep='first')``
(``yahoo_links_selenium.py:79,174``) — a hash-table walk on one CPU core.
The TPU formulation turns "same bucket" into a *sort*: for every band,
rows are sorted by (band key, row index); equal-key runs are bucket
collisions, and the run head (smallest row index — i.e. first seen) becomes
every member's candidate representative.  A signature-agreement check
verifies candidates, and at most log₂(B) rounds of pointer jumping
(fewer where the labels settle sooner) resolve chains so the final
representative array has union-find semantics — all without leaving the
device or introducing data-dependent shapes.

Sorting is the idiomatic XLA substitute for hash tables: ``lax.sort`` is a
fused multi-operand bitonic sort that tiles well on TPU, whereas scattered
hash-table updates would serialise.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from advanced_scrapper_tpu.ops.shingle import FNV_OFFSET, FNV_PRIME, U32_MAX, fmix32


# second-lane constants for the wide (64-bit-entropy) band keys: a distinct
# FNV-style offset/prime pair so the two lanes are independent hashes of the
# same band content (TPUs have no native uint64 — the packing happens on
# host).  numpy (not jnp) scalars: a module-level jnp constant would
# initialise the backend at import time, breaking jax.distributed ordering.
import numpy as _np

_WIDE_OFFSET = _np.uint32(0xCBF29CE4)
_WIDE_PRIME = _np.uint32(0x01000197)


def _fold_bands(sig: jnp.ndarray, nb: int, offset, prime) -> jnp.ndarray:
    """FNV-1a fold of each band's signature rows → uint32[B, nb] (unsalted).

    Single source of the fold used by BOTH :func:`band_keys` and lane 0/1
    of :func:`band_keys_wide`, so their documented equivalence is
    structural, not maintained by parallel editing.
    """
    B, P = sig.shape
    r = P // nb
    rows = sig.reshape(B, nb, r)
    k = jnp.full((B, nb), offset, dtype=jnp.uint32)
    for j in range(r):
        k = (k ^ rows[:, :, j]) * prime
    return k


@jax.jit
def band_keys(sig: jnp.ndarray, band_salt: jnp.ndarray) -> jnp.ndarray:
    """Fold each band's rows into one salted uint32 bucket key.

    ``sig`` is ``uint32[B, num_perm]``; returns ``uint32[B, num_bands]``.
    The north-star config is 16 bands × 8 rows (BASELINE.json).
    """
    nb = band_salt.shape[0]
    k = _fold_bands(sig, nb, FNV_OFFSET, FNV_PRIME)
    return fmix32(k ^ band_salt[None, :])


@jax.jit
def band_keys_wide(sig: jnp.ndarray, band_salt: jnp.ndarray) -> jnp.ndarray:
    """uint32[B, num_bands, 2]: two independent 32-bit keys per band.

    Lane 0 is exactly :func:`band_keys` (same fold, same salt).  Lane 1
    folds the same band rows with different constants and a rotated salt.
    Packed to uint64 on host (``utils.bloom.pack_keys64``) this gives band
    keys whose accidental collision rate is ~n·num_bands/2⁶⁴ — required by
    the unattributed Bloom stream index, where a key collision is an
    unverifiable false drop (32-bit keys lose ~n/2³² of unique docs, ~4%
    at 10M scale).
    """
    nb = band_salt.shape[0]
    lo = _fold_bands(sig, nb, FNV_OFFSET, FNV_PRIME)
    hi = _fold_bands(sig, nb, _WIDE_OFFSET, _WIDE_PRIME)
    salt = band_salt[None, :]
    rot = (salt << jnp.uint32(13)) | (salt >> jnp.uint32(19))
    return jnp.stack([fmix32(lo ^ salt), fmix32(hi ^ rot)], axis=-1)


def _run_head_per_band(
    kt: jnp.ndarray, idxb: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """For each band row (axis 1 = batch): sorted keys → run-head,
    run-predecessor, and run-predecessor² indices,
    ``(si, head_sorted, pred_sorted, pred2_sorted)``.

    The predecessor² link (two sorted positions back WITHIN the run, self
    otherwise) exists to jump one failing intermediate: in a bucket run
    ``[d, x, e, y]`` where the decoys ``d``/``e`` verify against nobody,
    head and pred links leave ``x``—``y`` unconnected even though both are
    bucket members (datasketch candidacy) with agreement ≥ threshold —
    pred² links ``y`` straight to ``x``.  Measured on the hardened knee
    corpus this closes most of the co-bucketed recall the fine-only
    bridge edges used to carry (tools/sweep_fine_margin.py, DESIGN.md).
    """
    nb, B = kt.shape
    sk, si = jax.lax.sort((kt, idxb), dimension=1, num_keys=2)
    seg_start = jnp.concatenate(
        [jnp.ones((nb, 1), dtype=bool), sk[:, 1:] != sk[:, :-1]], axis=1
    )
    seg_id = jnp.cumsum(seg_start, axis=1) - 1  # int32 [nb, B], < B
    # si is ascending within each equal-key run, so the run head (first-seen
    # row) is the segment minimum of si, and the run predecessor is si
    # shifted one sorted position (self at run starts).
    run_min = jax.vmap(
        lambda s, g: jax.ops.segment_min(s, g, num_segments=B)
    )(si, seg_id)
    head_sorted = jnp.take_along_axis(run_min, seg_id, axis=1)
    pred_sorted = jnp.where(
        seg_start, si, jnp.concatenate([si[:, :1], si[:, :-1]], axis=1)
    )
    shift2 = jnp.concatenate([si[:, :2], si[:, :-2]], axis=1)
    same_run2 = jnp.concatenate(
        [jnp.zeros((nb, 2), dtype=bool), seg_id[:, 2:] == seg_id[:, :-2]],
        axis=1,
    )
    pred2_sorted = jnp.where(same_run2, shift2, si)
    return si, head_sorted, pred_sorted, pred2_sorted


@jax.jit
def duplicate_reps(keys: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Candidate representative per row: smallest earlier row sharing any band.

    Args:
      keys: ``uint32[B, num_bands]`` band bucket keys.
      valid: ``bool[B]`` — rows with no shingles (or batch padding) are
        excluded and map to themselves.

    Returns ``int32[B]`` with ``rep[i] <= i``; ``rep[i] == i`` means no
    earlier collision.  Candidates still need signature verification
    (:func:`resolve_reps`) — band collisions can be accidental.
    """
    B, nb = keys.shape
    idx = jnp.arange(B, dtype=jnp.int32)
    keys = jnp.where(valid[:, None], keys, U32_MAX)
    kt = keys.T
    idxb = jnp.broadcast_to(idx, (nb, B))
    si, rep_sorted, _pred, _pred2 = _run_head_per_band(kt, idxb)
    rep_band = jax.vmap(
        lambda s, r: jnp.zeros((B,), dtype=jnp.int32).at[s].set(r)
    )(si, rep_sorted)
    rep = rep_band.min(axis=0)
    # Invalid rows were all assigned key U32_MAX and may have grouped with
    # each other; sever them (and protect the pathological valid row that
    # really hashes to U32_MAX) by self-assignment.
    return jnp.where(valid, rep, idx)


@jax.jit
def duplicate_rep_bands(keys: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Per-band candidate representatives: ``int32[B, 3*nb]`` (run head +
    run predecessor + run predecessor² per band).

    Unlike :func:`duplicate_reps` (which min-reduces across bands BEFORE
    verification), this keeps every band's candidates independent so the
    verifier can test all of them.  The min-first scheme loses verified
    pairs to shadowing: if row i shares band 3 with its true near-dup j
    but band 7 accidentally collides with an unrelated earlier row h < j,
    min picks h, verification fails, and i reverts to self even though j
    would have verified (measured: 54 of 133 recall-certification misses
    were this exact shape).
    """
    B, nb = keys.shape
    idx = jnp.arange(B, dtype=jnp.int32)
    keys = jnp.where(valid[:, None], keys, U32_MAX)
    kt = keys.T
    idxb = jnp.broadcast_to(idx, (nb, B))
    # Head links alone under-connect a run — i and j may verify against
    # each other but not against the head (datasketch's union-find merges
    # any pairwise path); predecessor links chain consecutive run members
    # so those pairs survive, and predecessor² links jump one failing
    # intermediate (see _run_head_per_band).
    si, head_sorted, pred_sorted, pred2_sorted = _run_head_per_band(kt, idxb)
    cands = []
    for cand_sorted in (head_sorted, pred_sorted, pred2_sorted):
        cand = jax.vmap(
            lambda s, r: jnp.zeros((B,), dtype=jnp.int32).at[s].set(r)
        )(si, cand_sorted)
        cands.append(jnp.where(valid[None, :], cand, idxb).T)
    return jnp.concatenate(cands, axis=1)  # int32[B, 3*nb]


@partial(jax.jit, static_argnames=("jump_rounds",))
def resolve_rep_bands(
    rep_bands: jnp.ndarray,
    sig: jnp.ndarray,
    valid: jnp.ndarray,
    threshold: float,
    *,
    jump_rounds: int,
) -> jnp.ndarray:
    """Verify EVERY band candidate by signature agreement, keep the smallest
    verified one, then pointer-jump chains to the fixpoint.

    The multi-candidate twin of :func:`resolve_reps`: ``rep_bands`` is
    ``int32[B, nc]`` from :func:`duplicate_rep_bands` (callers may
    concatenate extra candidate sets along axis 1).  Each verified
    (row, candidate) pair is an undirected edge; the result is the
    connected-component minimum — exactly datasketch's union-find over
    verified pairs.  Single-parent min-hooking (keep only the smallest
    verified candidate, then pointer-jump) is NOT equivalent: a row with
    two verified edges keeps one, the discarded edge can bridge two
    clusters, and backward-only edges never pull a cluster's later rows
    down to its final label (measured: 30 of 74 certification misses had
    pairwise agreement ≥ threshold yet landed in different clusters).
    Label propagation: pull the min label along edges, push it back with a
    scatter-min, then pointer-double — symmetric, monotone, and fixpoint =
    component min within ``jump_rounds`` ≥ ceil(log2(B)) rounds.  The
    loop stops after the first round that changes no label (the fixpoint)
    or after ``jump_rounds`` rounds, whichever comes first.
    A merge still requires signature agreement — candidates that fail
    verification contribute no edge.  ``threshold`` may be a scalar, a
    per-candidate-COLUMN ``float32[nc]`` vector, or a per-EDGE
    ``float32[B, nc]`` array (:func:`fine_edge_thresholds`): fine-only
    edges — pairs sharing no
    coarse band, which datasketch's banding never proposes — verify
    against a higher bar, recovering the precision their extra candidacy
    gives up (measured sweep in DESIGN.md).
    """
    B, nc = rep_bands.shape
    idx = jnp.arange(B, dtype=jnp.int32)
    thr = jnp.asarray(threshold, jnp.float32)
    thr = jnp.broadcast_to(thr, (nc,) if thr.ndim < 2 else (B, nc))
    # Verify in candidate-axis chunks: the full [B, nc, P] gather would be
    # ~nc× the signature footprint (51 GB at nc=96 over a 2^20 bucket);
    # chunked, the peak transient stays at [B, 8, P] — the same order as
    # the signatures themselves.
    ok_parts = []
    for c0 in range(0, nc, 8):
        cand_sig = jnp.take(sig, rep_bands[:, c0 : c0 + 8], axis=0)
        agree = (sig[:, None, :] == cand_sig).mean(axis=2)
        thr_c = thr[..., c0 : c0 + 8]
        ok_parts.append(agree >= (thr_c if thr_c.ndim == 2 else thr_c[None, :]))
    # an edge needs BOTH endpoints valid: invalid rows (padding, sub-k
    # texts) must neither merge nor be merged into, structurally — not
    # just because their all-U32_MAX signatures happen to disagree
    ok = (
        jnp.concatenate(ok_parts, axis=1)
        & valid[:, None]
        & jnp.take(valid, rep_bands)
    )
    return _label_components(rep_bands, ok, valid, jump_rounds)[0]


def _label_components(rep_bands, ok, valid, jump_rounds: int):
    """Connected-component min labels over the ``ok`` edge set (the shared
    back half of :func:`resolve_rep_bands` / :func:`resolve_rep_bands_from_ok`):
    ``(labels int32[B], rounds int32[])``, the rounds the loop ran (the
    entry points return the labels alone).

    A ``lax.while_loop`` of propagation rounds that stops after the first
    round leaving every label unchanged, or after ``jump_rounds`` rounds.
    Each round is a deterministic function of the labels, so a round that
    changes nothing is the fixpoint and every later round would be a
    no-op: the labels equal those of exactly ``jump_rounds`` rounds, and
    the test stays on the device (one dispatch, no host round trip).
    Star clusters and short candidate runs settle in one round and
    confirm in the second, far below the cap.
    """
    B, nc = rep_bands.shape
    idx = jnp.arange(B, dtype=jnp.int32)
    cand = jnp.where(ok, rep_bands, idx[:, None])  # self-edges are no-ops

    def cond(state):
        _lab, rounds, changed = state
        return changed & (rounds < jump_rounds)

    def body(state):
        lab0, rounds, _changed = state
        # the scopes name each phase's ops on the profiler trace
        with jax.named_scope("resolve.pull"):
            pulled = jnp.take(lab0, cand, axis=0).min(axis=1)
            lab = jnp.minimum(lab0, pulled)
        with jax.named_scope("resolve.scatter_min"):
            lab = lab.at[cand.reshape(-1)].min(
                jnp.broadcast_to(lab[:, None], (B, nc)).reshape(-1)
            )
        with jax.named_scope("resolve.jump"):
            lab = jnp.take(lab, lab)  # pointer doubling
        return lab, rounds + 1, jnp.any(lab != lab0)

    lab, rounds, _changed = jax.lax.while_loop(
        cond, body, (idx, jnp.int32(0), jnp.bool_(True))
    )
    return jnp.where(valid, lab, idx), rounds


@partial(jax.jit, static_argnames=("jump_rounds",))
def resolve_rep_bands_from_ok(
    rep_bands: jnp.ndarray,
    ok: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    jump_rounds: int,
) -> jnp.ndarray:
    """:func:`resolve_rep_bands` with the verified-edge matrix supplied.

    For callers that already computed the agreement pass (e.g.
    :func:`borderline_edge_mask`) and edited it on host (the exact-verify
    stage kills refuted edges) — re-running the chunked signature gathers
    would double the heaviest device op on the one-shot path.
    """
    return _label_components(rep_bands, ok, valid, jump_rounds)[0]


def subband_salt(num: int, seed: int = 0x5B5C9A02) -> _np.ndarray:
    """Deterministic uint32[num] salts for sub-band candidate keys —
    derived, not stored in MinHashParams, so any sub-band count works
    against the frozen north-star params."""
    x = (_np.arange(num, dtype=_np.uint64) * _np.uint64(0x9E3779B97F4A7C15)
         + _np.uint64(seed)) & _np.uint64(0xFFFFFFFF)
    return x.astype(_np.uint32)


def candidate_keys(
    sig: jnp.ndarray, band_salt, cand_subbands: int
) -> jnp.ndarray:
    """Coarse + fine candidate band keys: ``uint32[B, nb + cand_subbands]``.

    The single construction shared by the batch engine, the sharded step,
    and the driver entry — their resolutions must stay identical (the
    streamed path may not recall less than the certified one-shot path), so
    the key scheme lives in exactly one place.  Fine sub-bands (fewer rows
    per key) give near-certain candidacy at the threshold knee; merges
    still require signature-agreement verification, so precision is
    unchanged.  ``cand_subbands=0`` yields the plain 16-band keys.
    """
    keys = band_keys(sig, jnp.asarray(band_salt))
    if not cand_subbands:
        return keys
    num_perm = sig.shape[-1]
    if num_perm % cand_subbands:
        raise ValueError(
            f"cand_subbands {cand_subbands} must divide num_perm {num_perm} "
            "(each sub-band folds num_perm/cand_subbands signature rows)"
        )
    fine = band_keys(sig, jnp.asarray(subband_salt(cand_subbands)))
    return jnp.concatenate([keys, fine], axis=1)


def _maybe_densify(sig: jnp.ndarray, densify_oph: bool) -> jnp.ndarray:
    """OPH accumulators arrive RAW (empty bins ``U32_MAX``) so the
    streamed min-combine stays exact; the epilogues densify once, after
    the combine, inside their own dispatch."""
    if not densify_oph:
        return sig
    from advanced_scrapper_tpu.ops.oph import densify

    return densify(sig)


def _coarse_fine_keys(
    sig: jnp.ndarray, band_salt: jnp.ndarray, fine_salt: jnp.ndarray
) -> jnp.ndarray:
    """:func:`candidate_keys`' fold with the fine salts passed as an
    array — a zero-length ``fine_salt`` (static shape under trace)
    yields the plain coarse keys.  Shared by the fused epilogues so the
    key scheme still lives in exactly one construction."""
    keys = band_keys(sig, band_salt)
    if fine_salt.shape[0]:
        keys = jnp.concatenate([keys, band_keys(sig, fine_salt)], axis=1)
    return keys


@partial(jax.jit, static_argnames=("densify_oph",))
def fused_candidate_epilogue(
    sig_acc: jnp.ndarray,
    valid: jnp.ndarray,
    band_salt: jnp.ndarray,
    fine_salt: jnp.ndarray,
    *,
    densify_oph: bool,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """ONE-dispatch corpus epilogue: ``(sigs, keys, rep_bands)`` from the
    device-resident signature accumulator.

    Folds what used to be separate jitted calls — OPH densify,
    :func:`candidate_keys` (itself two ``band_keys`` dispatches when
    sub-bands are on) and :func:`duplicate_rep_bands` — into a single
    step, so a full corpus through the packed dedup path is
    ``tiles × 1`` dispatches plus this epilogue (ISSUE 9 / SEDD's
    launch-count argument).  ``fine_salt`` is ``subband_salt(cand_subbands)``
    or a zero-length array (its static shape selects the variant).
    """
    sig = _maybe_densify(sig_acc, densify_oph)
    keys = _coarse_fine_keys(sig, band_salt, fine_salt)
    return sig, keys, duplicate_rep_bands(keys, valid)


@partial(
    jax.jit,
    static_argnames=(
        "densify_oph", "num_coarse", "jump_rounds", "use_fine_margin",
    ),
)
def fused_resolve_epilogue(
    sig_acc: jnp.ndarray,
    valid: jnp.ndarray,
    band_salt: jnp.ndarray,
    fine_salt: jnp.ndarray,
    base,
    fine_margin,
    *,
    densify_oph: bool,
    num_coarse: int,
    jump_rounds: int,
    use_fine_margin: bool,
) -> jnp.ndarray:
    """The WHOLE estimator-only resolution as one dispatch: OPH densify →
    coarse+fine keys → per-band candidates → (optional) per-edge fine
    bars → verification + union-find labels.

    The async/firehose path (``dedup_reps_async`` with no rerank hook)
    rides this, so a full corpus is exactly ``tiles × 1`` dispatches plus
    this single epilogue — the ISSUE 9 launch-count shape.  A rerank hook
    needs the candidate matrix on the host boundary between candidates
    and resolution, so hooked engines fall back to the two-stage
    :func:`fused_candidate_epilogue` + :func:`resolve_rep_bands` split
    (identical math, one extra dispatch).
    """
    sig = _maybe_densify(sig_acc, densify_oph)
    keys = _coarse_fine_keys(sig, band_salt, fine_salt)
    rep_bands = duplicate_rep_bands(keys, valid)
    if use_fine_margin:
        thr = fine_edge_thresholds(
            rep_bands, keys, base, fine_margin, num_coarse=num_coarse
        )
    else:
        thr = base
    return resolve_rep_bands(
        rep_bands, sig, valid, thr, jump_rounds=jump_rounds
    )


@partial(jax.jit, static_argnames=("densify_oph", "wide"))
def fused_keys_epilogue(
    sig_acc: jnp.ndarray,
    band_salt: jnp.ndarray,
    fine_salt: jnp.ndarray,
    *,
    densify_oph: bool,
    wide: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """ONE-dispatch ``(sigs, keys)`` epilogue for callers that join on
    host (the streaming batch backend) or feed a persistent index.

    ``wide=False`` returns :func:`candidate_keys`-equivalent coarse+fine
    keys; ``wide=True`` returns :func:`band_keys_wide`'s two-lane keys
    (``fine_salt`` ignored).  Replaces the old shape where the backend
    synced host signatures and passed them BACK through ``band_keys*`` —
    a D2H → re-H2D bounce plus extra dispatches per batch.
    """
    sig = _maybe_densify(sig_acc, densify_oph)
    if wide:
        return sig, band_keys_wide(sig, band_salt)
    return sig, _coarse_fine_keys(sig, band_salt, fine_salt)


def _fine_only_chunks(rep_bands, keys, num_coarse):
    """Yield ``(c0, cand_slice, fine_only_slice)`` in 8-column chunks:
    ``fine_only[b, c]`` is True when column c's candidate for row b shares
    NO coarse band with row b (i.e. the pair is outside datasketch's
    candidacy class).  Chunked so the gathered-coarse transient stays
    ``[B, 8, nb]``."""
    B, ncols = rep_bands.shape
    nbands = keys.shape[1]
    assert ncols % nbands == 0, (ncols, nbands)
    coarse = keys[:, :num_coarse]
    is_fine = _np.tile(_np.arange(nbands) >= num_coarse, ncols // nbands)
    for c0 in range(0, ncols, 8):
        cand = rep_bands[:, c0 : c0 + 8]
        fine_cols = is_fine[c0 : c0 + 8]
        if not fine_cols.any():
            yield c0, cand, jnp.zeros(cand.shape, bool)
            continue
        cand_coarse = jnp.take(coarse, cand, axis=0)  # [B, <=8, nbc]
        shared = (coarse[:, None, :] == cand_coarse).any(axis=2)
        yield c0, cand, ~shared & jnp.asarray(fine_cols)[None, :]


@partial(jax.jit, static_argnames=("num_coarse",))
def borderline_edge_mask(
    rep_bands: jnp.ndarray,
    sig: jnp.ndarray,
    keys: jnp.ndarray,
    valid: jnp.ndarray,
    base: float,
    band: float,
    *,
    num_coarse: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(need bool[B, nc], ok bool[B, nc])``: edges that pass estimator
    verification, and which of them should be confirmed by EXACT Jaccard
    before resolution.

    An edge needs exact confirmation (``need``) when its agreement clears
    ``base`` (it would merge) AND it is statistically fragile: either
    **fine-only** (outside datasketch's candidacy class — proposed by a
    fine sub-band with no shared coarse band, any agreement), or
    **coarse-borderline** (agreement < ``band``, where estimator noise
    σ≈0.04 at 128 perms makes true-J<threshold merges likely).
    Non-edges (self-candidates, invalid endpoints) are never flagged.
    ``ok`` is the full verified-edge matrix at ``base`` — callers edit it
    with the exact verdicts and resolve via
    :func:`resolve_rep_bands_from_ok`, so the chunked agreement gathers
    (the heaviest op in the resolve path) run ONCE.  See
    ``pipeline.dedup.NearDupEngine`` for the host exact-verify stage
    (measured budget: DESIGN.md §2e).
    """
    B, nc = rep_bands.shape
    idx = jnp.arange(B, dtype=jnp.int32)
    need_parts = []
    ok_parts = []
    for c0, cand, fine_only in _fine_only_chunks(rep_bands, keys, num_coarse):
        cand_sig = jnp.take(sig, cand, axis=0)
        agree = (sig[:, None, :] == cand_sig).mean(axis=2)
        is_edge = (
            (cand != idx[:, None])
            & valid[:, None]
            & jnp.take(valid, cand)
            & (agree >= base)
        )
        need_parts.append(is_edge & (fine_only | (agree < band)))
        ok_parts.append(
            (agree >= base) & valid[:, None] & jnp.take(valid, cand)
        )
    return (
        jnp.concatenate(need_parts, axis=1),
        jnp.concatenate(ok_parts, axis=1),
    )


@partial(jax.jit, static_argnames=("num_coarse",))
def fine_edge_thresholds(
    rep_bands: jnp.ndarray,
    keys: jnp.ndarray,
    base: float,
    fine_margin: float,
    *,
    num_coarse: int,
) -> jnp.ndarray:
    """Per-edge verification bars: ``float32[B, nc]`` for
    :func:`resolve_rep_bands`.

    Fine sub-bands serve two distinct edge classes and the precision
    budget (VERDICT r4 item 4) needs them separated:

    - **coarse-co-bucketed** fine edges — the row and its candidate share
      ≥1 coarse band, i.e. the pair is in datasketch's own candidacy
      class; the fine run merely recovered linkage the coarse
      run-head/predecessor scheme under-connects (≥3 interleaved bucket
      members).  These verify at ``base``: dropping or raising them costs
      exactly the knee recall the sub-bands exist to provide.
    - **fine-only** edges — no shared coarse band: pairs datasketch never
      proposes.  Some are true transitive bridges (high agreement), many
      are estimator noise just over the bar (the r4 ~3.2-point precision
      giveback — σ≈0.04 at 128 perms).  These verify at
      ``base + fine_margin``: strong bridges survive, noise dies.
      (Measured: gating them out entirely overshoots — precision −0.003
      vs oracle but recall 0.9255; ``tools/sweep_fine_margin.py``.)

    ``rep_bands`` is ``int32[B, 3·(nb+cs)]`` over :func:`candidate_keys`
    output (run heads for all bands, then run predecessors, then run
    predecessors²); ``keys`` the same ``uint32[B, nb+cs]`` the candidates
    came from; ``num_coarse`` = nb.  Gathers are chunked like
    :func:`resolve_rep_bands`'s so the transient stays ``[B, 8, nb]``.
    """
    base = jnp.float32(base)
    strict = jnp.float32(base + fine_margin)
    out = [
        jnp.where(fine_only, strict, base)
        for _c0, _cand, fine_only in _fine_only_chunks(
            rep_bands, keys, num_coarse
        )
    ]
    return jnp.concatenate(out, axis=1)


@partial(jax.jit, static_argnames=("jump_rounds",))
def resolve_reps(
    rep: jnp.ndarray,
    sig: jnp.ndarray,
    valid: jnp.ndarray,
    threshold: float,
    *,
    jump_rounds: int,
) -> jnp.ndarray:
    """Verify candidates by signature agreement, then resolve chains.

    ``agreement = mean(sig_i == sig_rep)`` is the standard unbiased MinHash
    Jaccard estimate; candidates below ``threshold`` revert to self.
    ``jump_rounds`` should be ≥ ceil(log2(B)) — pointer jumping over a
    monotone parent array reaches the fixpoint in log rounds.
    """
    B = rep.shape[0]
    idx = jnp.arange(B, dtype=jnp.int32)
    agree = (sig == jnp.take(sig, rep, axis=0)).mean(axis=1)
    rep = jnp.where((agree >= threshold) & valid, rep, idx)
    for _ in range(jump_rounds):
        rep = jnp.take(rep, rep)
    return rep


@partial(jax.jit, static_argnames=("nbins",))
def bucket_histogram(
    keys: jnp.ndarray, valid: jnp.ndarray, *, nbins: int = 1 << 16
) -> jnp.ndarray:
    """Histogram of band keys over ``nbins`` — the psum-able dense summary
    used for cross-shard bucket-merge statistics (north star names
    ``lax.psum`` for this merge; see ``parallel/sharded.py``)."""
    flat = (keys % jnp.uint32(nbins)).astype(jnp.int32).reshape(-1)
    w = jnp.broadcast_to(valid[:, None], keys.shape).reshape(-1).astype(jnp.int32)
    return jnp.zeros((nbins,), dtype=jnp.int32).at[flat].add(w)


def keep_mask(rep: jnp.ndarray) -> jnp.ndarray:
    """True for rows that are their own representative (first seen)."""
    return rep == jnp.arange(rep.shape[0], dtype=rep.dtype)
