"""North-star metric test: near-dup recall of the TPU engine vs the
datasketch-algorithm CPU oracle (BASELINE.json: ≥ 0.95).

Builds a synthetic corpus with planted near-duplicates (character edits at
controlled rates), computes the oracle's near-dup pair set, and requires the
device engine to cluster ≥95% of those pairs together.
"""

import numpy as np
import pytest

from advanced_scrapper_tpu.core.hashing import make_params
from advanced_scrapper_tpu.core.tokenizer import encode_batch
from advanced_scrapper_tpu.cpu.oracle import (
    jaccard,
    oracle_near_dup_pairs,
    oracle_signature,
    shingle_set,
)
from advanced_scrapper_tpu.ops.lsh import band_keys, duplicate_reps, resolve_reps
from advanced_scrapper_tpu.ops.minhash import minhash_signatures

PARAMS = make_params(num_perm=128, num_bands=16, shingle_k=5, seed=1)


def _mutate(rng, text: bytes, n_edits: int) -> bytes:
    b = bytearray(text)
    for _ in range(n_edits):
        pos = rng.randint(0, len(b))
        op = rng.randint(3)
        ch = rng.randint(32, 127)
        if op == 0:
            b[pos] = ch
        elif op == 1:
            b.insert(pos, ch)
        elif len(b) > 50:
            del b[pos]
    return bytes(b)


def _corpus(n_base=40, dup_per_base=2, length=400, seed=7):
    rng = np.random.RandomState(seed)
    texts = []
    for _ in range(n_base):
        base = bytes(rng.randint(32, 127, size=length, dtype=np.uint8))
        texts.append(base)
        for _ in range(dup_per_base):
            texts.append(_mutate(rng, base, n_edits=rng.randint(1, 8)))
    order = rng.permutation(len(texts))
    return [texts[i] for i in order]


def _device_clusters(texts, threshold=0.7):
    tok, ln = encode_batch(texts, block_len=512)
    sig = minhash_signatures(tok, ln, PARAMS)
    keys = band_keys(sig, PARAMS.band_salt)
    valid = np.asarray(ln) >= PARAMS.shingle_k
    rep = duplicate_reps(keys, valid)
    rep = np.asarray(
        resolve_reps(rep, sig, valid, threshold, jump_rounds=8)
    )
    return rep


def test_oracle_signature_sanity():
    """Oracle signature agreement tracks true Jaccard (MinHash property)."""
    rng = np.random.RandomState(3)
    a = bytes(rng.randint(32, 127, size=500, dtype=np.uint8))
    b = _mutate(rng, a, 5)
    true_j = jaccard(shingle_set(a, 5), shingle_set(b, 5))
    sa, sb = oracle_signature(a, PARAMS), oracle_signature(b, PARAMS)
    est = float(np.mean(sa == sb))
    assert true_j > 0.8
    assert abs(est - true_j) < 0.15


def test_near_dup_recall_vs_oracle():
    texts = _corpus()
    oracle_pairs = oracle_near_dup_pairs(texts, PARAMS, threshold=0.7)
    assert len(oracle_pairs) >= 30, "corpus should contain planted near-dups"
    rep = _device_clusters(texts, threshold=0.7)
    hit = sum(1 for i, j in oracle_pairs if rep[i] == rep[j])
    recall = hit / len(oracle_pairs)
    assert recall >= 0.95, f"near-dup recall {recall:.3f} < 0.95 ({hit}/{len(oracle_pairs)})"


def test_near_dup_recall_certification_hardened():
    """The round-3 hardened certification (VERDICT r2 item 4): 2048 docs
    with ragged lengths (100 B – 100 kB, forcing the blockwise segment-min
    combine), near-dup pairs planted ACROSS the Jaccard 0.6–0.8 knee where
    LSH candidacy is genuinely probabilistic, measured against datasketch
    oracle semantics.  The engine must recover ≥95% of oracle pairs while
    never merging unrelated docs (checked separately below)."""
    from advanced_scrapper_tpu.cpu.oracle import (
        build_certification_corpus,
        measured_recall,
    )
    from advanced_scrapper_tpu.pipeline.dedup import NearDupEngine

    rng = np.random.RandomState(7)
    texts = build_certification_corpus(rng, 512)
    assert len(texts) == 2048
    assert max(len(t) for t in texts) >= 100_000  # blockwise combine forced
    from advanced_scrapper_tpu.cpu.oracle import oracle_near_dup_pairs

    reps = NearDupEngine().dedup_reps(texts)
    # one oracle pair computation feeds recall AND the precision comparator
    pairs = oracle_near_dup_pairs(texts, PARAMS, 0.7, fast=True)
    recall, n_pairs = measured_recall(texts, reps, PARAMS, 0.7, pairs=pairs)
    assert n_pairs >= 900, "corpus must plant a statistically meaningful pair set"
    assert recall >= 0.95, f"hardened recall {recall:.4f} < 0.95 ({n_pairs} pairs)"

    # Precision on the SAME run: every engine merge judged by true
    # shingle-set Jaccard.  Transitive closure legitimately merges
    # mutant-mutant pairs below threshold (as datasketch + union-find
    # would), so the hard bar is chain validity: every cluster member
    # reachable through edges the estimator can plausibly accept.
    from advanced_scrapper_tpu.cpu.oracle import measured_precision

    precision, n_merged, n_unchained = measured_precision(
        texts, reps, PARAMS.shingle_k, 0.7
    )
    assert n_merged >= 900, "engine must have merged a meaningful pair set"
    assert n_unchained == 0, f"{n_unchained} members merged without a strong chain"
    assert precision >= 0.80, f"precision {precision:.4f} ({n_merged} pairs)"

    # Comparator + budget (VERDICT r4 item 4): the engine must hold
    # precision ≥ oracle − 0.01 at recall ≥ 0.95 (asserted above).  The
    # r5 exact-verify stage (DedupConfig.exact_verify_band) is what makes
    # this reachable: estimator-only margins cannot — borderline false
    # merges and genuine cross-estimator bridge edges ride the same
    # agreement band (measured frontier in tools/sweep_fine_margin.py and
    # DESIGN.md §2e) — so statistically fragile edges are confirmed by
    # exact shingle-set Jaccard before resolution (measured here:
    # oracle +0.0098 at recall 0.9524, ~130 exact checks).
    from advanced_scrapper_tpu.cpu.oracle import oracle_reps

    o_precision, o_merged, o_unchained = measured_precision(
        texts,
        oracle_reps(texts, PARAMS, 0.7, pairs=pairs),
        PARAMS.shingle_k,
        0.7,
    )
    assert o_merged >= 900
    assert precision >= o_precision - 0.01, (
        f"engine precision {precision:.4f} below oracle comparator "
        f"{o_precision:.4f} − 0.01 budget"
    )


def test_recall_precision_distribution_over_seeds():
    """ROADMAP item 2 satellite: the quality bar as a DISTRIBUTION, not
    the single certification seed.  Five independently-seeded knee-heavy
    certification corpora (160 bases → 640 ragged docs each, pairs
    planted across the Jaccard 0.6–0.8 knee where LSH candidacy is
    genuinely probabilistic); the engine must hold

    - pooled recall ≥ 0.95 (the BASELINE bar, over ~1.6k oracle pairs),
      with no single seed below 0.92 (per-seed noise at ~320 pairs is
      ±1.2% 1σ — a seed at 0.93 is the bar holding, a seed at 0.85 is a
      regression this test now catches and the old single-seed test
      couldn't);
    - per-seed precision ≥ its own oracle comparator − 0.02 and pooled
      precision ≥ 0.90, with zero unchained merges anywhere.

    Measured at introduction (jax 0.4.x CPU): per-seed recall
    0.936–0.969, pooled 0.9513; engine precision beat the oracle
    comparator on all five seeds.
    """
    from advanced_scrapper_tpu.config import DedupConfig
    from advanced_scrapper_tpu.cpu.oracle import (
        build_certification_corpus,
        measured_precision,
        measured_recall,
        oracle_reps,
    )
    from advanced_scrapper_tpu.pipeline.dedup import NearDupEngine

    engine = NearDupEngine(DedupConfig())
    params = make_params()
    seeds = (101, 211, 307, 401, 503)
    hits = pairs_total = 0
    precisions: list[float] = []
    per_seed: list[tuple[int, float, float, float]] = []
    for seed in seeds:
        rng = np.random.RandomState(seed)
        texts = build_certification_corpus(rng, 160, n_long=8)
        reps = engine.dedup_reps(texts)
        opairs = oracle_near_dup_pairs(texts, params, 0.7, fast=True)
        recall, n = measured_recall(texts, reps, params, 0.7, pairs=opairs)
        assert n >= 250, f"seed {seed}: corpus planted only {n} oracle pairs"
        prec, merged, unchained = measured_precision(
            texts, reps, params.shingle_k, 0.7
        )
        oprec, _om, _ou = measured_precision(
            texts,
            oracle_reps(texts, params, 0.7, pairs=opairs),
            params.shingle_k,
            0.7,
        )
        assert unchained == 0, f"seed {seed}: {unchained} unchained merges"
        assert recall >= 0.92, f"seed {seed}: recall {recall:.4f} < 0.92"
        assert prec >= oprec - 0.02, (
            f"seed {seed}: precision {prec:.4f} below oracle comparator "
            f"{oprec:.4f} − 0.02"
        )
        hits += round(recall * n)
        pairs_total += n
        precisions.append(prec)
        per_seed.append((seed, recall, prec, oprec))
    pooled_recall = hits / pairs_total
    pooled_precision = float(np.mean(precisions))
    assert pooled_recall >= 0.95, (
        f"pooled recall {pooled_recall:.4f} < 0.95 over {pairs_total} "
        f"pairs; per-seed: {per_seed}"
    )
    assert pooled_precision >= 0.90, (
        f"pooled precision {pooled_precision:.4f} < 0.90; per-seed: {per_seed}"
    )


def test_rerank_tier_recall_precision_over_seeds():
    """Satellite bar for the device-batched precision tier: with the
    rerank hook default-installed, five independently-seeded
    representative certification corpora (knee_frac=0.2 — pairs mostly
    clear of the 0.6–0.8 knee, the production-shaped mix) must pool to
    recall ≥ 0.95 AND precision ≥ 0.95 — both bars at once, which the
    estimator-only paths cannot reach (the hookless engine measured
    pooled 0.9768 / 0.9509 on this mix; the tier's settled true-Jaccard
    verdicts + op-mass-priced eviction measured 0.9809 / 0.9613, worst
    seed 0.9736 / 0.9601).

    The adversarial knee-heavy mix keeps its own distribution test above
    (0.95 recall / 0.90 precision): there every bad merge lives in a
    3-cluster whose separation necessarily drops a near-threshold true
    pair, so (0.95, 0.95) is structurally unreachable regardless of
    tier policy — the tier still Pareto-dominates the hookless baseline
    on that mix (0.9632/0.9281 vs 0.9516/0.9212)."""
    from advanced_scrapper_tpu.config import DedupConfig
    from advanced_scrapper_tpu.cpu.oracle import (
        build_certification_corpus,
        measured_precision,
        measured_recall,
    )
    from advanced_scrapper_tpu.pipeline.dedup import NearDupEngine

    engine = NearDupEngine(DedupConfig())
    assert engine.rerank_hook is not None, "tier must be default-installed"
    params = make_params()
    seeds = (101, 211, 307, 401, 503)
    hits = pairs_total = 0
    precisions: list[float] = []
    per_seed: list[tuple[int, float, float]] = []
    for seed in seeds:
        rng = np.random.RandomState(seed)
        texts = build_certification_corpus(rng, 160, n_long=8, knee_frac=0.2)
        reps = engine.dedup_reps(texts)
        opairs = oracle_near_dup_pairs(texts, params, 0.7, fast=True)
        recall, n = measured_recall(texts, reps, params, 0.7, pairs=opairs)
        assert n >= 250, f"seed {seed}: corpus planted only {n} oracle pairs"
        prec, merged, unchained = measured_precision(
            texts, reps, params.shingle_k, 0.7
        )
        assert unchained == 0, f"seed {seed}: {unchained} unchained merges"
        hits += round(recall * n)
        pairs_total += n
        precisions.append(prec)
        per_seed.append((seed, recall, prec))
    pooled_recall = hits / pairs_total
    pooled_precision = float(np.mean(precisions))
    assert pooled_recall >= 0.95, (
        f"rerank-active pooled recall {pooled_recall:.4f} < 0.95 over "
        f"{pairs_total} pairs; per-seed: {per_seed}"
    )
    assert pooled_precision >= 0.95, (
        f"rerank-active pooled precision {pooled_precision:.4f} < 0.95; "
        f"per-seed: {per_seed}"
    )


def test_skip_rerank_brownout_equals_hookless_baseline():
    """The skip_rerank brownout step must bypass the DEFAULT tier
    counted-and-reversibly: under the armed step the default engine's
    reps equal a hookless (rerank=False) engine's reps element-for-
    element, each bypass increments the degradation-effects ledger, and
    dropping the ladder restores the tier (its per-corpus stats prove it
    ran again)."""
    from advanced_scrapper_tpu.config import DedupConfig
    from advanced_scrapper_tpu.cpu.oracle import build_certification_corpus
    from advanced_scrapper_tpu.obs import telemetry
    from advanced_scrapper_tpu.pipeline.dedup import NearDupEngine
    from advanced_scrapper_tpu.runtime.admission import (
        DegradationLadder,
        LadderStep,
    )

    def _effects(ladder) -> float:
        total = 0.0
        for c in telemetry.REGISTRY.find("astpu_degraded_effects_total"):
            if (
                c.labels.get("ladder") == ladder.name
                and c.labels.get("step") == "skip_rerank"
            ):
                total += c.value
        return total

    rng = np.random.RandomState(31)
    texts = build_certification_corpus(rng, 24, n_long=2)
    hookless = NearDupEngine(DedupConfig(rerank=False))
    assert hookless.rerank_hook is None
    want = np.asarray(hookless.dedup_reps(texts))

    eng = NearDupEngine(DedupConfig())
    ladder = DegradationLadder(
        [LadderStep("skip_rerank", 0.5, 0.2)], dwell_s=0.0
    )
    ladder.observe(1.0)
    ladder.observe(1.0)
    assert ladder.active("skip_rerank")
    eng.ladder = ladder
    e0 = _effects(ladder)
    got = np.asarray(eng.dedup_reps(texts))
    assert (got == want).all(), "brownout output must equal hookless baseline"
    assert _effects(ladder) == e0 + 1, "bypass must be counted"
    assert not eng._rerank_applied
    # reversible: ladder removed → the tier settles the next corpus
    eng.ladder = None
    eng.dedup_reps(texts)
    assert eng._rerank_applied
    assert eng.rerank_tier.stats.get("pairs", 0) > 0


def test_resolve_rep_bands_is_union_find_over_verified_edges():
    """Connected-component semantics: a pairwise-verified edge must merge
    its endpoints even when neither endpoint verifies against the other's
    smallest candidate (single-parent min-hooking drops such bridges)."""
    import jax.numpy as jnp

    from advanced_scrapper_tpu.ops.lsh import resolve_rep_bands

    P = 128
    base = np.arange(P).astype(np.uint32)
    sig1 = base.copy()
    sig1[:32] += 10_000          # agree(1, 0) = 96/128 = 0.75
    sig2 = sig1.copy()
    sig2[32:64] += 20_000        # agree(2, 1) = 0.75 but agree(2, 0) = 0.5
    sigs = jnp.asarray(np.stack([base, sig1, sig2]))
    valid = jnp.ones((3,), bool)
    # row 2's candidates: head 0 (fails verify) AND predecessor 1 (verifies)
    rep_bands = jnp.asarray(np.array([[0, 0], [0, 0], [0, 1]], np.int32))
    out = np.asarray(
        resolve_rep_bands(rep_bands, sigs, valid, 0.7, jump_rounds=4)
    )
    assert out.tolist() == [0, 0, 0]


def test_resolve_rep_bands_symmetric_push_pulls_late_rows_down():
    """Backward-only edges: row 2 holds BOTH verified edges (2→0 and 2→1).
    Pulling alone gives row 2 label 0 but leaves row 1 stuck at 1 — row 1
    has no edge of its own; only the scatter-min PUSH along edge 2→1 can
    drag row 1 down to 0.  Deleting the push in resolve_rep_bands must turn
    this red."""
    import jax.numpy as jnp

    from advanced_scrapper_tpu.ops.lsh import resolve_rep_bands

    P = 128
    a = np.arange(P).astype(np.uint32)
    b = a.copy(); b[:16] += 10_000     # agree(b, a) = 0.875
    c = a.copy(); c[16:32] += 20_000   # agree(c, a) = 0.875; agree(c, b) = 0.75
    sigs = jnp.asarray(np.stack([a, b, c]))
    valid = jnp.ones((3,), bool)
    # row 0 and row 1 propose only themselves; row 2 proposes 0 and 1
    rep_bands = jnp.asarray(np.array([[0, 0], [1, 1], [0, 1]], np.int32))
    out = np.asarray(
        resolve_rep_bands(rep_bands, sigs, valid, 0.7, jump_rounds=4)
    )
    assert out.tolist() == [0, 0, 0]


def test_no_false_merges_of_unrelated_texts():
    rng = np.random.RandomState(11)
    texts = [bytes(rng.randint(32, 127, size=300, dtype=np.uint8)) for _ in range(64)]
    rep = _device_clusters(texts, threshold=0.7)
    assert (rep == np.arange(64)).all()


def test_fast_oracle_bit_identical_to_slow_oracle():
    """The vectorised oracle (ground truth for the hardened certification
    and bench's recall field) must stay bit-identical to the per-shingle
    datasketch-algorithm oracle — including u64 wraparound semantics."""
    from advanced_scrapper_tpu.cpu.oracle import (
        oracle_signatures,
        oracle_signatures_fast,
    )

    rng = np.random.RandomState(5)
    docs = [
        rng.randint(0, 256, size=int(n), dtype=np.uint8).tobytes()
        for n in (0, 1, 4, 5, 6, 37, 400, 5000, 20000)
    ]
    docs.append("ünïcode — mixed œntênt".encode())
    slow = oracle_signatures(docs, PARAMS)
    fast = oracle_signatures_fast(docs, PARAMS)
    assert slow.shape == fast.shape
    assert (slow == fast).all()


def test_resolve_rep_bands_fuzzed_vs_union_find_oracle():
    """Device CC resolution must equal a brute-force union-find over the
    verified edge set on arbitrary candidate graphs — including invalid
    rows, which structurally may neither merge nor be merged into."""
    import jax.numpy as jnp

    from advanced_scrapper_tpu.ops.lsh import resolve_rep_bands

    def oracle_cc(rep_bands, sigs, valid, thr):
        B, _ = rep_bands.shape
        parent = list(range(B))

        def find(i):
            while parent[i] != i:
                parent[i] = parent[parent[i]]
                i = parent[i]
            return i

        for i in range(B):
            if not valid[i]:
                continue
            for c in rep_bands[i]:
                c = int(c)
                if c == i or not valid[c]:
                    continue
                if (sigs[i] == sigs[c]).mean() >= thr:
                    ra, rb = find(i), find(c)
                    if ra != rb:
                        parent[max(ra, rb)] = min(ra, rb)
        return np.array([find(i) if valid[i] else i for i in range(B)])

    rng = np.random.RandomState(123)
    for _ in range(40):
        B = int(rng.randint(3, 48))
        nc = int(rng.randint(1, 7))
        protos = rng.randint(0, 1 << 31, (max(2, B // 4), 128)).astype(np.uint32)
        sigs = protos[rng.randint(0, protos.shape[0], B)].copy()
        noise = rng.rand(B, 128) < rng.uniform(0, 0.5)
        sigs[noise] = rng.randint(0, 1 << 31, int(noise.sum())).astype(np.uint32)
        rep_bands = np.stack(
            [rng.randint(0, i + 1, nc) for i in range(B)]
        ).astype(np.int32)
        # invalid rows keep their random candidate lists: the source-side
        # half of the both-endpoints guard (an invalid row may not merge
        # OUT either) must be fuzzed, not neutralised before dispatch
        valid = rng.rand(B) > 0.15
        thr = float(rng.choice([0.5, 0.7, 0.9]))
        got = np.asarray(
            resolve_rep_bands(
                jnp.asarray(rep_bands), jnp.asarray(sigs), jnp.asarray(valid),
                thr, jump_rounds=8,
            )
        )
        want = oracle_cc(rep_bands, sigs, valid, thr)
        assert (got == want).all()


# -- label propagation: stops at its fixpoint, bit-equal to the fixed loop ----


def _fixed_round_labels(rep_bands, ok, valid, jump_rounds):
    """The resolve's label propagation as it was before it learned to stop:
    exactly ``jump_rounds`` unrolled rounds of pull, scatter-min and
    pointer jump — the reference the early-stopping loop must equal."""
    import jax.numpy as jnp

    B, nc = rep_bands.shape
    idx = jnp.arange(B, dtype=jnp.int32)
    cand = jnp.where(ok, rep_bands, idx[:, None])
    lab = idx
    for _ in range(jump_rounds):
        pulled = jnp.take(lab, cand, axis=0).min(axis=1)
        lab = jnp.minimum(lab, pulled)
        lab = lab.at[cand.reshape(-1)].min(
            jnp.broadcast_to(lab[:, None], (B, nc)).reshape(-1)
        )
        lab = jnp.take(lab, lab)
    return jnp.where(valid, lab, idx)


def _edgeless(rng, B, nc):
    rep_bands = rng.randint(0, B, (B, nc)).astype(np.int32)
    return rep_bands, np.zeros((B, nc), bool)


def _stars(rng, B, nc):
    """Clusters whose head is their smallest member, every other member
    proposing the head (the run-head column of ``duplicate_rep_bands``)."""
    labels = np.sort(rng.randint(0, max(2, B // 5), B))
    heads = np.searchsorted(labels, labels)  # first row of each cluster
    rep_bands = np.tile(np.arange(B, dtype=np.int32)[:, None], (1, nc))
    rep_bands[:, 0] = heads
    ok = np.zeros((B, nc), bool)
    ok[:, 0] = heads != np.arange(B)
    return rep_bands, ok


def _exact_copy_runs(rng, B, nc):
    """Runs of exact copies scattered through the batch, linked as
    ``duplicate_rep_bands`` links a run: head, predecessor and
    predecessor² columns, every link verified."""
    members = rng.randint(0, max(2, B // 4), B)
    rep_bands = np.tile(np.arange(B, dtype=np.int32)[:, None], (1, nc))
    for m in np.unique(members):
        run = np.flatnonzero(members == m)
        for k, i in enumerate(run):
            links = (run[0], run[max(k - 1, 0)], run[max(k - 2, 0)])
            rep_bands[i] = [links[c % 3] for c in range(nc)]
    return rep_bands, rep_bands != np.arange(B)[:, None]


def _long_path(rng, B, nc):
    """One chain ``i → i - 1`` through the whole batch: the component
    needs several rounds, so a small cap binds before the fixpoint."""
    rep_bands = np.tile(np.arange(B, dtype=np.int32)[:, None], (1, nc))
    rep_bands[1:, int(rng.randint(nc))] = np.arange(B - 1)
    return rep_bands, rep_bands != np.arange(B)[:, None]


def _label_components_jit():
    """The resolve's label propagation with the rounds it ran: the
    entry points return the labels alone."""
    import jax

    from advanced_scrapper_tpu.ops.lsh import _label_components

    return jax.jit(_label_components, static_argnames=("jump_rounds",))


def _fuzzed(rng, B, nc):
    rep_bands = np.stack([rng.randint(0, B, nc) for _ in range(B)]).astype(np.int32)
    return rep_bands, rng.rand(B, nc) < rng.uniform(0.0, 0.3)


@pytest.mark.parametrize(
    "graph", [_edgeless, _stars, _exact_copy_runs, _long_path, _fuzzed],
    ids=["edgeless", "stars", "exact_copy_runs", "long_path", "fuzzed"],
)
def test_label_components_stops_at_fixpoint_bit_equal_to_fixed_rounds(graph):
    """The early-stopping label propagation equals the fixed-count loop
    bit for bit at every cap — where the fixpoint arrives first and where
    the cap binds first — and never reports more rounds than its cap."""
    import jax
    import jax.numpy as jnp

    from advanced_scrapper_tpu.ops.lsh import resolve_rep_bands_from_ok

    fixed = jax.jit(_fixed_round_labels, static_argnames=("jump_rounds",))
    labels = _label_components_jit()
    rng = np.random.RandomState(2026)
    B, nc = 64, 6
    for _ in range(6):
        rep_bands, ok = graph(rng, B, nc)
        valid = rng.rand(B) > 0.1
        args = (jnp.asarray(rep_bands), jnp.asarray(ok), jnp.asarray(valid))
        for cap in (1, 2, 3, 6):
            rep, rounds = labels(*args, jump_rounds=cap)
            want = np.asarray(fixed(*args, jump_rounds=cap))
            assert np.array_equal(np.asarray(rep), want), (graph.__name__, cap)
            # the entry point returns the same labels
            entry = resolve_rep_bands_from_ok(*args, jump_rounds=cap)
            assert np.array_equal(np.asarray(entry), want), (graph.__name__, cap)
            assert np.asarray(rounds).dtype == np.int32
            assert 1 <= int(rounds) <= cap


def test_label_components_reports_rounds_run():
    """One round on an edgeless matrix (it changes nothing), two on star
    clusters (the head's label arrives in one, the second confirms it),
    and the whole cap on a long path the cap cuts short."""
    import jax.numpy as jnp

    labels = _label_components_jit()
    rng = np.random.RandomState(7)
    B, nc = 64, 6
    valid = jnp.ones((B,), bool)
    for graph, cap, want in ((_edgeless, 6, 1), (_stars, 6, 2), (_long_path, 2, 2)):
        rep_bands, ok = graph(rng, B, nc)
        rep, rounds = labels(
            jnp.asarray(rep_bands), jnp.asarray(ok), valid, jump_rounds=cap
        )
        assert int(rounds) == want, graph.__name__
    # the cut path has not reached its component minimum yet
    assert np.asarray(rep).max() > 0
    # uncut, it settles on label 0 and stops before a generous cap
    rep, rounds = labels(
        jnp.asarray(rep_bands), jnp.asarray(ok), valid, jump_rounds=16
    )
    assert int(rounds) < 16 and not np.asarray(rep).any()


def test_label_components_invalid_row_keeps_its_own_index():
    """A ``valid=False`` row reports itself even when edges touch it; the
    valid rows around it still merge."""
    import jax.numpy as jnp

    from advanced_scrapper_tpu.ops.lsh import resolve_rep_bands_from_ok

    rep_bands = jnp.asarray(np.array([[0, 0], [0, 0], [1, 1], [0, 2]], np.int32))
    ok = rep_bands != jnp.arange(4)[:, None]
    valid = jnp.asarray(np.array([True, True, False, True]))
    rep = resolve_rep_bands_from_ok(rep_bands, ok, valid, jump_rounds=2)
    assert np.asarray(rep).tolist() == [0, 0, 2, 0]
    _, rounds = _label_components_jit()(rep_bands, ok, valid, jump_rounds=2)
    assert 1 <= int(rounds) <= 2
