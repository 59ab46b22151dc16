"""Per-stage wall-clock counters for the host→device path.

The bench JSON's ``stage_ms`` breakdown (encode / h2d / kernel / resolve /
matcher_build) comes from here: hot paths wrap their stage work in
:func:`timed` (or call :func:`add` directly), the bench resets before a
regime and snapshots after.  Attribution is **by call site**, not by a
global timeline: the pipelines overlap stages on purpose (that is the whole
point of the async design), so the per-stage sums can legitimately exceed
the end-to-end wall clock, and device "kernel" time is the time the host
spent *waiting* on device results (dispatch is async; a fully-hidden kernel
contributes ~0).  The numbers answer "where would another millisecond of
host work hurt", which is what the next PR needs — not a scheduler trace.

Since the telemetry plane landed this module is a thin VIEW over the
process registry: every :func:`add` lands in the always-on
``astpu_stage_seconds`` histogram (``obs/telemetry.py``), so the bench's
``stage_ms`` and the live ``/metrics`` stage series are the same numbers
by construction.  The histograms are cumulative (Prometheus-style);
:func:`reset` snapshots per-stage baselines and :func:`snapshot_ms`
reports the delta since — a windowed read over shared state, so two
concurrent windowed readers would see each other's time (the bench runs
its regimes serially; live scrapes read the cumulative series instead).

Thread-safe (the H2D put pool and DeviceFeed workers time from their own
threads); overhead is one ``perf_counter`` pair and a histogram update per
*batch*, noise against millisecond-scale stages.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

from advanced_scrapper_tpu.obs import telemetry, trace

_lock = threading.Lock()
_hists: dict[str, telemetry.Histogram] = {}
_baseline: dict[str, float] = {}  # per-stage cumulative sum at last reset()

#: canonical stage names (call sites may add others; these are the bench's)
STAGES = (
    "encode",
    "h2d",
    "kernel",
    "resolve",
    # the precision tier's sub-stages: they tile the rerank hook
    "rerank_readback",
    "rerank_worklist",
    "rerank_sketch",
    "rerank_settle",
    "rerank_exact",
    "rerank_cluster",
    "rerank_evict",
    # the one-shot resolve after the hook returns
    "resolve_wait",
    "decisions",
    # the mesh-sharded plane: its whole tile loop, and the cross-chip
    # epilogue dispatch with its readback
    "sharded_tiles",
    "sharded_epilogue",
    "matcher_build",
    "matcher_rows",
    "matcher_screen",
    "matcher_screen_wait",  # nested in matcher_screen
    "matcher_verify",
    "matcher_verify_busy",  # summed over the verify workers
    "matcher_verify_queue",  # summed over the verify slices
)


def _hist(stage: str) -> telemetry.Histogram:
    # local cache so the per-batch path skips the registry lock/lookup
    h = _hists.get(stage)
    if h is None:
        h = telemetry.stage_histogram(stage)
        with _lock:
            _hists[stage] = h
    return h


def add(stage: str, seconds: float) -> None:
    _hist(stage).observe(seconds)


@contextmanager
def timed(stage: str):
    t0 = time.perf_counter()
    try:
        yield
    finally:
        add(stage, time.perf_counter() - t0)


class Tally:
    """The stage clocks of one unit of work (a corpus, a chunk), each
    added to its histogram once when the unit ends (:meth:`flush`), never
    per item inside it.  :meth:`timed` runs its body inside the trace
    span of the same scope (``obs/trace.py``), so the clock and the
    profiler's ``astpu.<span>`` interval cover the same work."""

    def __init__(self):
        self.seconds: dict[str, float] = {}

    @contextmanager
    def timed(self, stage: str, span: str):
        with trace.span(span):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                self.seconds[stage] = self.seconds.get(stage, 0.0) + dt

    def flush(self) -> None:
        for stage, seconds in self.seconds.items():
            add(stage, seconds)
        self.seconds.clear()


def reset() -> None:
    """Start a measurement window: nothing is cleared (the live series
    stays cumulative); per-stage baselines are snapshotted instead."""
    with _lock:
        _baseline.clear()
        for h in telemetry.stage_histograms():
            _baseline[h.labels["stage"]] = h.sum


def snapshot_ms() -> dict[str, float]:
    """Cumulative per-stage milliseconds since the last :func:`reset`."""
    out: dict[str, float] = {}
    with _lock:
        for h in telemetry.stage_histograms():
            stage = h.labels["stage"]
            out[stage] = round((h.sum - _baseline.get(stage, 0.0)) * 1e3, 1)
    return dict(sorted(out.items()))


# -- device-traffic counters ---------------------------------------------
#
# Always-on (like the stage histograms): dispatch-count wins are gated
# NUMERICALLY — tier-1 tests assert the packed dedup AND matcher paths'
# per-tile traffic is 1 put + 1 dispatch, and the bench emits per-regime
# deltas — so the counters must exist whether or not telemetry is
# enabled.  The ``regime`` label names the instrumented call-site plane
# ("dedup" = the NearDupEngine hot path, "feed" = DeviceFeed staging,
# "matcher" = the entity-screen tile plane); bench maps the cumulative
# deltas onto its own regime keys.  Only EXPLICIT device traffic is
# counted: ``jax.device_put`` calls and jitted-step dispatches in the
# instrumented pipelines — implicit transfers (numpy passed straight to
# a jit) are exactly the shape the packed paths exist to avoid, and
# counting them would hide that.

_DEV_NAMES = (
    "astpu_device_puts_total",
    "astpu_device_dispatches_total",
    "astpu_h2d_bytes_total",
)
_dev_counters: dict[tuple[str, str, str | None], telemetry.Counter] = {}


def _dev(name: str, regime: str, shard: str | None = None) -> telemetry.Counter:
    c = _dev_counters.get((name, regime, shard))
    if c is None:
        labels = {"regime": regime}
        if shard is not None:
            # the mesh-sharded planes label traffic per device shard, so
            # the per-shard 1-put/1-dispatch contract is a ledger fact
            labels["shard"] = shard
        c = telemetry.event_counter(
            name,
            {
                "astpu_device_puts_total": "explicit jax.device_put calls",
                "astpu_device_dispatches_total": "jitted device dispatches",
                "astpu_h2d_bytes_total": "host→device bytes shipped by puts",
                "astpu_sharded_tile_slots_total":
                    "tile slots of the sharded plane's partitioned dispatches",
                "astpu_sharded_pad_tiles_total":
                    "zero tiles padded into a leftover sharded tile group",
            }[name],
            **labels,
        )
        with _lock:
            _dev_counters[(name, regime, shard)] = c
    return c


def count_device_put(
    nbytes: int, regime: str = "dedup", *, shard: int | str | None = None
) -> None:
    """Record one explicit ``jax.device_put`` of ``nbytes`` (``shard``:
    the mesh row-shard the buffer landed on, for the sharded planes)."""
    shard = None if shard is None else str(shard)
    _dev("astpu_device_puts_total", regime, shard).inc()
    _dev("astpu_h2d_bytes_total", regime, shard).inc(nbytes)


def count_dispatch(
    regime: str = "dedup", n: int = 1, *, shard: int | str | None = None
) -> None:
    """Record ``n`` jitted device dispatches (``shard``: the mesh
    row-shard that executed them — one partitioned launch executes once
    per device, so the sharded planes count it once per shard)."""
    _dev(
        "astpu_device_dispatches_total", regime,
        None if shard is None else str(shard),
    ).inc(n)


def device_counters() -> dict[str, float]:
    """Cumulative device-traffic totals, summed across ``regime`` labels:
    ``{"device_puts", "device_dispatches", "h2d_bytes"}``.  Subtract two
    snapshots to window a regime (the bench does)."""
    out = {"device_puts": 0.0, "device_dispatches": 0.0, "h2d_bytes": 0.0}
    short = {
        "astpu_device_puts_total": "device_puts",
        "astpu_device_dispatches_total": "device_dispatches",
        "astpu_h2d_bytes_total": "h2d_bytes",
    }
    for name, key in short.items():
        for c in telemetry.REGISTRY.find(name):
            out[key] += c.value
    return out


def regime_device_counters(regime: str) -> dict[str, float]:
    """Cumulative device-traffic totals for ONE regime label:
    ``{"device_puts", "device_dispatches", "h2d_bytes"}`` — the
    per-regime twin of :func:`device_counters`.  The rerank launch-count
    gate windows this (subtract two snapshots) to assert a settled
    corpus cost exactly ``tiles + 1`` puts and ``tiles + 1`` dispatches
    on the ``"rerank"`` plane regardless of what the dedup plane did in
    between."""
    short = {
        "astpu_device_puts_total": "device_puts",
        "astpu_device_dispatches_total": "device_dispatches",
        "astpu_h2d_bytes_total": "h2d_bytes",
    }
    out = {"device_puts": 0.0, "device_dispatches": 0.0, "h2d_bytes": 0.0}
    for name, key in short.items():
        for c in telemetry.REGISTRY.find(name):
            if c.labels.get("regime") == regime:
                out[key] += c.value
    return out


def count_sharded_tile_slot(shard: int | str, *, padded: bool) -> None:
    """Record one tile slot of a partitioned sharded dispatch on
    ``shard``; ``padded``: the slot holds a zero tile that pads a
    leftover group (``NearDupEngine._sharded_tile_groups``)."""
    shard = str(shard)
    _dev("astpu_sharded_tile_slots_total", "sharded", shard).inc()
    if padded:
        _dev("astpu_sharded_pad_tiles_total", "sharded", shard).inc()


def sharded_tile_counters() -> dict[str, dict[str, float]]:
    """Per-shard cumulative ``{shard: {"tile_slots", "pad_tiles"}}``:
    tile slots dispatched and, of them, zero tiles padded in.  Subtract
    two snapshots to window a corpus or a benchmark window."""
    return _per_shard({
        "astpu_sharded_tile_slots_total": "tile_slots",
        "astpu_sharded_pad_tiles_total": "pad_tiles",
    }, "sharded")


def sharded_device_counters(regime: str = "sharded") -> dict[str, dict[str, float]]:
    """Per-shard cumulative device-traffic totals for one regime:
    ``{shard: {"device_puts", "device_dispatches", "h2d_bytes"}}`` —
    only shard-labelled series count (the single-device planes never
    carry the label).  Subtract two snapshots to window a corpus; the
    sharded launch-count gates (tier-1 and the MULTICHIP dryrun) assert
    every shard's delta is exactly tiles + 1 / tiles + 1."""
    return _per_shard({
        "astpu_device_puts_total": "device_puts",
        "astpu_device_dispatches_total": "device_dispatches",
        "astpu_h2d_bytes_total": "h2d_bytes",
    }, regime)


def _per_shard(short: dict[str, str], regime: str) -> dict[str, dict[str, float]]:
    """``{shard: {key: total}}`` of the shard-labelled counters named in
    ``short`` (counter name → key) for one regime."""
    out: dict[str, dict[str, float]] = {}
    for name, key in short.items():
        for c in telemetry.REGISTRY.find(name):
            shard = c.labels.get("shard")
            if shard is None or c.labels.get("regime") != regime:
                continue
            per = out.setdefault(shard, dict.fromkeys(short.values(), 0.0))
            per[key] += c.value
    return out


def record_sharded_put_skew(
    baseline: dict | None = None, regime: str = "sharded"
) -> float:
    """Max−min per-shard put count across the shard-labelled ledger,
    recorded on the always-on ``astpu_sharded_put_skew`` gauge — the
    bench's SLO hook: a balanced sharded plane (every shard exactly
    tiles + 1 puts) reads 0, and the declared ``gauge_max`` objective
    turns any imbalance into a machine-readable verdict.

    ``baseline`` — a prior :func:`sharded_device_counters` snapshot —
    windows the computation to the work since that snapshot, and only
    shards ACTIVE in the window count: cumulative totals would read a
    permanent false skew in any process that ran corpora on meshes with
    different shard counts (an 8-shard corpus then a 4-shard one leaves
    shards 4-7 forever behind, with every corpus perfectly balanced)."""
    per = sharded_device_counters(regime)
    base = baseline or {}
    puts = [
        v["device_puts"] - base.get(s, {}).get("device_puts", 0.0)
        for s, v in per.items()
    ]
    puts = [p for p in puts if p > 0]  # shards active in the window
    skew = (max(puts) - min(puts)) if puts else 0.0
    telemetry.REGISTRY.gauge(
        "astpu_sharded_put_skew",
        "max-min per-shard device_put count (0 = balanced sharded ledger)",
        always=True,
    ).set(skew)
    return skew


def _clear_for_tests() -> None:
    """Drop the handle cache and baselines after a registry reset, or
    cached handles would keep feeding histograms (and device counters)
    the registry no longer exports.  Runs AUTOMATICALLY on every
    ``telemetry.REGISTRY.reset()`` via the reset hook below — the manual
    call-it-yourself contract was a real test-ordering trap (an early
    test's reset silently zeroed every later test's device-ledger
    deltas)."""
    with _lock:
        _hists.clear()
        _baseline.clear()
        _dev_counters.clear()


telemetry.REGISTRY.add_reset_hook(_clear_for_tests)
