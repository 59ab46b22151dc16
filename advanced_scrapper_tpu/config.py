"""Typed configuration for every subsystem.

The reference has no config system at all — every knob is a module-level
constant (``constant_rate_scrapper.py:17-28``, ``client1.py:17-24``,
``03_worker_multi.py:31``; SURVEY.md §5.6).  Here each subsystem gets a
frozen dataclass whose *defaults are the reference's operating points*, with
overrides from environment variables (``ASTPU_<FIELD>``) and from the CLI.
"""

from __future__ import annotations

import dataclasses
import os
import typing
from dataclasses import dataclass, field, fields
from typing import Any, Type, TypeVar

T = TypeVar("T")

_ENV_PREFIX = "ASTPU_"


def _coerce(raw: str, typ: Any) -> Any:
    if typ is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    if typ is int:
        return int(raw)
    if typ is float:
        return float(raw)
    if typ is tuple or typing.get_origin(typ) is tuple:
        return tuple(float(part) for part in raw.split(",") if part.strip())
    return raw


def from_env(cls: Type[T], section: str = "", **overrides: Any) -> T:
    """Build a config dataclass from ``ASTPU_<SECTION>_<FIELD>`` env vars.

    The section prefix keeps same-named fields in different subsystems
    independent (``ASTPU_DEDUP_BATCH_SIZE`` vs ``ASTPU_FEED_BATCH_SIZE``).
    """
    kwargs: dict[str, Any] = {}
    # PEP 563 postponed annotations make ``field.type`` a string; resolve the
    # real types so _coerce's identity checks work.
    hints = typing.get_type_hints(cls)
    prefix = _ENV_PREFIX + (section.upper() + "_" if section else "")
    for f in fields(cls):  # type: ignore[arg-type]
        env_key = prefix + f.name.upper()
        if env_key in os.environ:
            kwargs[f.name] = _coerce(os.environ[env_key], hints.get(f.name, str))
    kwargs.update({k: v for k, v in overrides.items() if v is not None})
    return cls(**kwargs)  # type: ignore[call-arg]


@dataclass(frozen=True)
class ScraperConfig:
    """Constant-rate acquisition engine (ref constant_rate_scrapper.py:17-28)."""

    website: str = "yfin"
    input_csv: str = "yfin_urls.csv"
    desired_request_rate: float = 5.8   # ref :17
    max_threads: int = 16               # ref :20
    stats_time_window: float = 10.0     # ref :23
    rate_limit_wait: float = 200.0      # ref :28
    page_load_timeout: float = 30.0     # ref :139
    ready_state_timeout: float = 10.0   # ref :151
    result_timeout: float = 60.0        # ref :439
    transport: str = "auto"  # auto|selenium|firefox-wire|chrome-wire|
    #   stealth-chrome|requests|mock ("auto" = selenium → firefox-wire →
    #   requests; the wire flavours need only a driver binary, no selenium)
    out_dir: str = "."


@dataclass(frozen=True)
class HarvestConfig:
    """CDX URL-discovery shard sweep (ref yahoo_links_selenium.py:19-34)."""

    num_workers: int = 10               # ref :19
    shard_dir: str = "yahoo_links_1"    # ref :29
    output_csv: str = "yfin_urls.csv"   # ref :178
    cdx_base: str = "http://web.archive.org/cdx/search/"
    target_pattern: str = "https://www.finance.yahoo.com/news/{prefix}*"
    ready_state_timeout: float = 3.0    # ref :43
    transport: str = "auto"


@dataclass(frozen=True)
class EnrichConfig:
    """Wikidata SPARQL enrichment (ref ticker_symbol_query*.py)."""

    endpoint: str = "https://query.wikidata.org/sparql"
    symbols_csv: str = "sp500list.csv"  # ref ticker_symbol_query.py:196
    out_dir: str = "info/ticker"        # ref :191
    hardened: bool = True
    max_retries: int = 5                # ref protected :34
    base_delay: float = 5.0             # ref protected :34
    connect_timeout: float = 15.0       # ref protected :212
    read_timeout: float = 60.0          # ref protected :212
    progress_file: str = "progress.json"  # ref protected :340
    crypto_symbols_csv: str = "crypto_list.csv"   # crypto flow symbol source
    crypto_out_dir: str = "info/crypto"           # beside info/ticker (SURVEY §L4)
    crypto_progress_file: str = "progress_crypto.json"
    cooldown_every3: tuple = (15.0, 25.0)   # ref protected :419-421
    cooldown_every10: tuple = (60.0, 120.0)  # ref protected :423-426


@dataclass(frozen=True)
class MatchConfig:
    """Entity→article matching (ref match_keywords.py)."""

    source_name: str = "yahoo"          # ref :222
    info_dir: str = "info/Icahn_filter"  # ref :223
    articles_csv: str = "datasets/yahoo_articles_all.csv"
    chunk_size: int = 20000             # ref :227
    fuzzy_threshold: float = 95.0       # ref :175 (partial_ratio > 95)
    use_tpu: bool = True
    out_dir_suffix: str = "_ticker_matched_articles"  # ref :129
    verify_workers: int = 0  # exact-verify process fan-out; 0 = cpu_count
    #                          (the ref's mp.Pool width, :231-238); 1 = inline
    dispatch_window: int = 0  # depth-N in-flight screen-tile window in the
    #   pipelined executor (staged-edge capacity; 0 = auto:
    #   max(2, put_workers) — same semantics as the dedup knob)
    put_workers: int = 0     # H2D put threads for screen tiles (0 = 1)
    screen_tile_bytes: int = 1 << 21  # byte budget per packed screen tile:
    #   rows-per-tile ≈ budget // row width (power-of-two bucketed, like the
    #   dedup encoder), so narrow news corpora screen thousands of rows per
    #   dispatch while 64 kB rows still tile shallowly
    prewarm: int = 0         # compile the packed screen-step shape set at
    #   run start (pipeline.matcher.prewarm_screen): every width bucket's
    #   full tile plus its power-of-two tail chunks, screen-only AND fused
    #   modes.  0 = off (tests must not pay the compile set); the
    #   persistent compile cache makes the warmup survive restarts


@dataclass(frozen=True)
class DedupConfig:
    """MinHash+LSH near-dup engine (BASELINE.json north star)."""

    shingle_k: int = 5       # k=5 byte shingles
    num_perm: int = 128      # 128 permutations
    num_bands: int = 16      # 16-band LSH
    block_len: int = 4096    # bytes per device block (bucketed padding)
    batch_size: int = 1024
    sim_threshold: float = 0.70  # signature-agreement verification threshold
    cand_subbands: int = 32  # extra fine candidate bands (128/32 = 4 rows:
    #   near-certain candidacy at the threshold knee; 0 disables.  Merges
    #   still require signature-agreement verification.
    fine_margin: float = 0.0  # extra estimator bar on FINE-ONLY edges
    #   (candidate pairs sharing no coarse band — outside datasketch's
    #   candidacy class; ops.lsh.fine_edge_thresholds) in the paths that
    #   CANNOT exact-verify (async firehose, streaming backend — old-side
    #   texts are gone there).  Estimator-only margins cannot meet the
    #   precision budget (measured frontier: tools/sweep_fine_margin.py);
    #   the certified one-shot path uses exact_verify_band instead.
    exact_verify_band: float = 0.72  # one-shot dedup_reps: every fine-only
    #   edge, and every coarse edge with agreement < this band, is
    #   confirmed by EXACT shingle-set Jaccard on host before resolution
    #   (borderline estimator verdicts are noise, σ≈0.04 at 128 perms).
    #   Measured (DESIGN.md §2e): recall 0.952, precision oracle+0.01 on
    #   the hardened corpus at ~130 exact checks per 2048 docs.  0 disables.
    exact_verify_cap: int = 8192  # max exact-Jaccard checks per corpus —
    #   beyond it remaining borderline edges keep their estimator verdict
    #   (a pathological all-borderline corpus must not degrade to O(n²))
    rerank: bool = True      # install the device-batched precision tier
    #   (pipeline/rerank.py) on RERANK_HOOK_EDGE at engine init: candidate
    #   pairs are settled by a vmap'd bottom-sketch Jaccard kernel in
    #   packed device tiles (1 put + 1 dispatch per tile through the
    #   dispatch executor, verdicts folded on-device and read back once
    #   per corpus), then clusters are precision-evicted to the ≥0.95 bar.
    #   ASTPU_DEDUP_RERANK=0 opts out (rerank_hook=None, the pre-tier
    #   hookless paths, byte-identical); the skip_rerank brownout bypasses
    #   it counted-and-reversibly without uninstalling.
    rerank_sketch: int = 1024  # bottom-S sketch lanes per document: the
    #   settle estimator's σ≈√(J(1−J)/S) (≈0.014 at 1024, 3× tighter than
    #   the 128-perm signature) and EXACT when |shingle union| ≤ S.  Pair
    #   rows are 8·S bytes on the wire; 2·S lanes per sort keeps the
    #   kernel aligned to 128-lane tiles.
    rerank_margin: float = 0.04  # half-width of the borderline band
    #   around sim_threshold: settled pairs with |J − thr| < margin are
    #   re-settled on host (exact shingle Jaccard up to rerank_exact_cap,
    #   then the persistent index's ANN re-probe when attached, else the
    #   sketch verdict stands).  ~3σ of the sketch estimator.
    rerank_precision_target: float = 0.96  # predicted merged-pair
    #   precision the greedy eviction walk stops at (ops.rerank.
    #   evict_for_precision; measured 5-seed operating points: pooled
    #   0.981 recall / 0.961 precision on the representative mix, and
    #   0.963 / 0.928 — a strict Pareto win over the hookless baseline's
    #   0.952 / 0.921 — on the adversarial knee-heavy suite, where the
    #   recall floor binds before the target is reached)
    rerank_recall_floor: float = 0.955  # hard predicted-recall guard:
    #   eviction never crosses below this fraction of the candidate
    #   work-list's expected oracle-recall mass (ops.rerank.op_weight
    #   prices each settled pair's probability of being counted by the
    #   estimator oracle), keeping the measured ≥0.95 recall bar with
    #   margin for estimator drift — on adversarial mixes this floor,
    #   not the target, is what stops eviction
    rerank_exact_cap: int = 8192  # max host exact-Jaccard re-settles per
    #   corpus (borderline band + wave-2 residue); beyond it borderline
    #   pairs fall to the ANN re-probe / sketch verdict — a pathological
    #   all-borderline corpus must not degrade to O(n²) host work
    rerank_tile_rows: int = 1024  # pair rows per full settle tile; the
    #   tile shape set is tile_rows_options(rerank_tile_rows) — shared
    #   with the engine prewarm derivation so the PR 15 recompile
    #   sentinel stays zero in steady state
    rerank_pair_cap: int = 1 << 16  # fold-buffer slots: max device-settled
    #   pairs per corpus (256 KiB int32 on device).  Overflow pairs keep
    #   their estimator verdict and are counted in the tier stats.
    seed: int = 1            # datasketch's default seed for oracle parity
    backend: str = "scan"    # scan (dense, datasketch-parity) | oph | pallas
    put_workers: int = 0     # H2D put threads INSIDE the pipelined
    #   dispatch executor (pipeline/dispatch.py — the encode→pack→put→
    #   dispatch pipeline every signature corpus now rides).
    #   0 = one put thread; >1 overlaps per-put round trips;
    #   order-independent min-combine makes any arrival order exact.
    #   Pre-PR-9 this knob also selected the inline put→accumulate loop
    #   at 1 — the executor is now always on (MIGRATION.md).
    dispatch_window: int = 0  # depth-N in-flight dispatch window: tiles
    #   resident between the H2D put stage and the accumulate dispatch
    #   (the executor's staged-edge capacity; total in-flight device
    #   tiles ≈ window + put_workers + 1 accumulating).  0 = auto:
    #   max(2, put_workers) — double buffering on local backends, a
    #   put-worker-deep window on serializing transports.
    packed_h2d: bool = True  # each tile's (tokens, lengths, owners) cross
    #   H2D as ONE contiguous buffer (ops/pack.py) into ONE fused jitted
    #   dispatch with the accumulator donated (ops.minhash.
    #   make_fused_tile_step).  The only transport: True is the one legal
    #   value (kept as a field so stated configurations still read back);
    #   False raises (MIGRATION.md).
    prewarm: int = 0         # compile the packed tile-step shape set at
    #   engine init (NearDupEngine.prewarm): every width bucket's full
    #   tile plus its O(log bs) power-of-two tail chunks.  0 = off
    #   (default: cold compile of the full set costs tens of seconds on
    #   CPU, which tests must not pay); 1 = prewarm for one batch_size
    #   corpus; >1 = the EXPECTED ARTICLE COUNT per corpus — the fused
    #   step is compiled per bucketed article axis, so prewarming the
    #   wrong bucket recompiles everything on the first real corpus
    #   anyway.  The persistent XLA compilation cache
    #   (core.mesh.maybe_enable_compile_cache) makes the warmup survive
    #   process restarts.
    stream_index: str = "exact"  # exact (attributed, grows with stream) |
    #   bloom (LSHBloom: fixed memory, no attribution) |
    #   persist (index/ subsystem: durable log-structured postings on disk,
    #   bounded resident memory, doc-id attribution, cross-RUN dedup)
    bloom_bits: int = 1 << 24    # bits per band filter (bloom mode)
    bloom_hashes: int = 4
    index_dir: str = ""          # persist mode: postings directory ("" →
    #   the caller derives one, e.g. the scraper uses
    #   <out_dir>/stream_index_<website>/)
    index_cut_postings: int = 1 << 16  # persist mode: memtable postings per
    #   segment cut (the WAL→segment cadence; RAM between cuts is bounded
    #   by this × ~80 B)
    index_compact_segments: int = 8    # persist mode: live-segment count
    #   that triggers background compaction (0 disables)
    index_fleet: str = ""        # persist mode: remote index fleet spec
    #   ("host:port|host:port;host:port|..." — ';' separates shards, '|'
    #   separates a shard's primary/replica; see index/fleet.py).  Empty =
    #   local single-node PersistentIndex (the PR 4 behaviour).  When set,
    #   the stream_index="persist" path talks to IndexShardServer nodes
    #   through ShardedIndexClient: consistent-hashed band-key space,
    #   synchronous replication, lease-TTL-style failover with
    #   health-checked promotion, journaled local spill when a shard is
    #   fully dark.
    index_fleet_timeout: float = 5.0   # per-RPC deadline (seconds)
    index_fleet_retries: int = 2       # transport retries per call (same
    #   request id; the shard's idempotency nets make redelivery safe)
    index_fleet_health_checks: int = 2  # consecutive pings a replica must
    #   answer before being promoted to write target
    ckpt_every_batches: int = 16  # stream-index checkpoint cadence, in
    #   device batches: the scraper persists the dedup index every N
    #   processed batches (persist: WAL fsync + due segment cut — O(new
    #   postings); exact/bloom: a FULL atomic npz rewrite — O(index), so
    #   raise N as the corpus grows, or 0 to checkpoint only at run end,
    #   the pre-knob behaviour) — previously an inline end-of-run-only
    #   constant in pipeline/scraper.py

    def __post_init__(self) -> None:
        if self.packed_h2d is not True:
            raise ValueError(
                "DedupConfig.packed_h2d must be True: the unpacked tile "
                "transport was removed (ASTPU_DEDUP_PACKED_H2D=0 no longer "
                "selects anything; see MIGRATION.md)"
            )


@dataclass(frozen=True)
class MeshConfig:
    """Device mesh layout (v5e-8 target: 8 chips, 1 axis of data parallelism
    plus optional sequence-parallel axis for long articles)."""

    data_axis: str = "data"
    seq_axis: str = "seq"
    data_parallel: int = -1  # -1: all devices
    seq_parallel: int = 1


@dataclass(frozen=True)
class FeedConfig:
    """Host feed scheduler / distributed lease protocol
    (ref server1.py:20,102-138, client1.py:17-24,209-234)."""

    host: str = "localhost"
    port: int = 8000                  # ref server1.py:18
    max_clients: int = 5              # ref server1.py:20
    batch_size: int = 20              # ref client1.py:23
    min_queue_length: int = 10        # ref client1.py:24
    client_threads: int = 8           # ref client1.py:21
    client_rate: float = 8.0          # ref client1.py:18
    lease_ttl: float = 30.0           # seconds without any complete frame
    #   (heartbeats count) before a client's leases are requeued and its
    #   connection cut — a hung-but-connected worker must not strand its
    #   urls until TCP notices.  0 disables (disconnect-only reclaim, the
    #   pre-fleet behaviour).
    heartbeat_interval: float = 0.0   # client heartbeat cadence; 0 = auto
    #   (lease_ttl / 4, never more than once a second of idleness)
    max_frame_bytes: int = 16 << 20   # NDJSON line-reassembly cap: a peer
    #   that never sends a newline is cut off here instead of growing the
    #   buffer without bound (the drop is counted in telemetry)
    connect_retries: int = 5          # LeaseClient initial-connect attempts
    connect_backoff: float = 0.05     # backoff base (capped exponential
    #   with jitter, cap 2 s) between connect attempts


@dataclass(frozen=True)
class Config:
    scraper: ScraperConfig = field(default_factory=ScraperConfig)
    harvest: HarvestConfig = field(default_factory=HarvestConfig)
    enrich: EnrichConfig = field(default_factory=EnrichConfig)
    match: MatchConfig = field(default_factory=MatchConfig)
    dedup: DedupConfig = field(default_factory=DedupConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    feed: FeedConfig = field(default_factory=FeedConfig)

    def replace(self, **kw: Any) -> "Config":
        return dataclasses.replace(self, **kw)


def default_config() -> Config:
    return Config(
        scraper=from_env(ScraperConfig, "scraper"),
        harvest=from_env(HarvestConfig, "harvest"),
        enrich=from_env(EnrichConfig, "enrich"),
        match=from_env(MatchConfig, "match"),
        dedup=from_env(DedupConfig, "dedup"),
        mesh=from_env(MeshConfig, "mesh"),
        feed=from_env(FeedConfig, "feed"),
    )
