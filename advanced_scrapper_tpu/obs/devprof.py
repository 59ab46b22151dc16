"""Device dispatch latency ledger + recompile sentinel (the time domain).

The launch-count ledgers (``obs/stages.py`` device counters) answer "how
many puts and dispatches did a tile cost"; this module answers "where did
dispatch *time* go, and did anything recompile when it shouldn't" — the
two questions a chip run needs answered before any sweep number means
anything.

Three always-on instruments (like the stage histograms, they are gated
NUMERICALLY by tier-1 tests and bench deltas, so they exist whether or
not telemetry is enabled):

- **``astpu_dispatch_latency_seconds{kernel, shape[, shard]}``** — one
  observation per device dispatch, labeled by the kernel seam
  (``dedup_fused_tile``, ``matcher_screen_tile``, ``sharded_fused_tile``)
  and the tile shape (``RxW`` — bounded
  cardinality: the chunkers only emit the O(log bs)-per-width prewarmed
  set).  The clock stops when the dispatch call returns, i.e. it
  measures the submission/queueing cost on the host (what an async
  pipeline actually pays per tile; a fully-hidden kernel reads ~0,
  exactly like the ``kernel`` stage histogram).  Device time per
  program comes from the profiler trace, not from this histogram.
- **``astpu_dispatch_queue_lag_seconds{graph}``** — the staged-pop gap
  through ``pipeline/dispatch.py``: how long a transferred tile sat in
  the staged window before the caller's thread popped it for dispatch.
  Near-zero lag = the dispatch loop is the bottleneck (tiles are
  consumed the moment they land); large lag = H2D runs ahead and the
  window is absorbing it (the dispatch side is the bottleneck).
- **``astpu_jit_compiles_total{kernel}``** — the recompile sentinel:
  :func:`instrument_jit` wraps a jitted step at the builder seams
  (``ops/minhash.py`` / ``ops/match.py`` / ``parallel/sharded_packed.py``
  steps, applied where the pipeline layer fetches them — the ops layer
  never imports obs) and counts jit-cache growth per call.  Prewarm and
  first-corpus compiles are EXPECTED counts; a steady-state increment is
  the exact failure mode prewarm exists to prevent (an unprewarmed shape,
  a silently-changed static arg) — a 44-second stall that used to be
  invisible is now a counted, SLO-alertable event, tier-1-asserted at
  zero across the packed dedup, matcher and sharded planes.
  ``astpu_jit_compile_seconds`` (fed by a ``jax.monitoring`` backend-
  compile listener, installed with the first instrumented step) carries
  the wall-clock of EVERY XLA backend compile in the process — including
  epilogues and steps no seam wraps — so "zero steady-state compiles"
  can be asserted globally, not just per instrumented kernel.  (A
  persistent-cache hit, ``core.mesh.maybe_enable_compile_cache``, never
  backend-compiles and correctly does not count: a cache load is not a
  stall.)

Cost model: one ``perf_counter`` pair + a histogram observe per *tile*
dispatch, and two C-level jit-cache-size reads per instrumented call —
noise against millisecond-scale dispatches (regression-gated with the
profiler's overhead test).  This module never imports jax at module
scope (jax-free processes — shard servers, tool parents — import obs
freely); the compile listener imports it lazily inside a call path that
only exists when jax is already loaded.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

from advanced_scrapper_tpu.obs import telemetry

__all__ = [
    "DISPATCH_HISTOGRAM",
    "QUEUE_LAG_HISTOGRAM",
    "JIT_COMPILES",
    "COMPILE_SECONDS",
    "dispatch_span",
    "queue_lag_histogram",
    "instrument_jit",
    "jit_compiles_total",
    "jit_compiles_by_kernel",
    "compile_seconds_count",
]

DISPATCH_HISTOGRAM = "astpu_dispatch_latency_seconds"
QUEUE_LAG_HISTOGRAM = "astpu_dispatch_queue_lag_seconds"
JIT_COMPILES = "astpu_jit_compiles_total"
COMPILE_SECONDS = "astpu_jit_compile_seconds"

_lock = threading.Lock()
_hists: dict[tuple, telemetry.Histogram] = {}
_lag_hists: dict[str, telemetry.Histogram] = {}
_compile_counters: dict[str, telemetry.Counter] = {}
_listener_installed = False


def _latency_hist(kernel: str, shape: str, shard: str | None):
    key = (kernel, shape, shard)
    h = _hists.get(key)
    if h is None:
        labels = {"kernel": kernel, "shape": shape}
        if shard is not None:
            labels["shard"] = shard
        h = telemetry.REGISTRY.histogram(
            "astpu_dispatch_latency_seconds",
            "per-dispatch wall clock by kernel/tile-shape (async-submit "
            "timing: the host's cost of the call)",
            always=True,
            **labels,
        )
        with _lock:
            _hists[key] = h
    return h


@contextmanager
def dispatch_span(
    kernel: str,
    *,
    rows: int | None = None,
    width: int | None = None,
    shard: int | str | None = None,
    trace: str | None = None,
):
    """Time one device dispatch into the latency ledger.

    ::

        with devprof.dispatch_span("dedup_fused_tile", rows=r, width=w):
            out = step(running, dev, ...)

    Only successful dispatches are observed (an OOM-backoff retry must
    not pollute the distribution with its failed parent attempt).
    ``trace`` attaches a slow-call exemplar when the observation lands in
    the histogram's top bucket.
    """
    shape = f"{rows}x{width}" if rows is not None and width is not None else ""
    t0 = time.perf_counter()
    yield
    # reached only when the body returned: a raising dispatch skips this
    _latency_hist(kernel, shape, None if shard is None else str(shard)).observe(
        time.perf_counter() - t0, trace=trace
    )


def queue_lag_histogram(graph: str) -> telemetry.Histogram:
    """The staged-pop lag series for one dispatch graph (always-on;
    ``pipeline/dispatch.py`` stamps tiles as the put pool stages them and
    observes the gap when the caller pops)."""
    h = _lag_hists.get(graph)
    if h is None:
        h = telemetry.REGISTRY.histogram(
            "astpu_dispatch_queue_lag_seconds",
            "staged-tile wait between h2d completion and the caller's "
            "dispatch pop (pipeline/dispatch.py staged window)",
            always=True,
            graph=graph,
        )
        with _lock:
            _lag_hists[graph] = h
    return h


# -- recompile sentinel -------------------------------------------------------


def _compiles(kernel: str) -> telemetry.Counter:
    c = _compile_counters.get(kernel)
    if c is None:
        c = telemetry.REGISTRY.counter(
            "astpu_jit_compiles_total",
            "jit-cache compiles per instrumented kernel seam (steady "
            "state must stay flat — prewarm exists to front-load these)",
            always=True,
            kernel=kernel,
        )
        with _lock:
            _compile_counters[kernel] = c
    return c


def _install_compile_listener() -> None:
    """Feed every XLA *backend* compile's duration into the always-on
    ``astpu_jit_compile_seconds`` histogram via ``jax.monitoring`` —
    installed once, with the first instrumented step (so jax is already
    importable there).  The handle is looked up per event, not cached:
    compiles are rare, and a registry reset (tests) must not orphan it.
    """
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        _listener_installed = True
    try:
        from jax import monitoring
    except Exception:
        return

    def _on_duration(name: str, value: float, **_kw) -> None:
        if not name.endswith("backend_compile_duration"):
            return
        try:
            telemetry.REGISTRY.histogram(
                "astpu_jit_compile_seconds",
                "wall clock of every XLA backend compile in this process "
                "(persistent-cache hits do not compile and do not count)",
                always=True,
            ).observe(float(value))
        except Exception:
            pass  # a metrics fault must never break a compile

    try:
        monitoring.register_event_duration_secs_listener(_on_duration)
    except Exception:
        pass


def instrument_jit(fn, kernel: str):
    """Wrap a jitted step so every jit-cache miss is counted on the
    always-on ``astpu_jit_compiles_total{kernel}`` sentinel.

    Applied at the pipeline layer where the ``ops``/``parallel`` step
    builders' results are fetched and cached (the builders themselves may
    not import obs — layering).  The wrapper is transparent: same call
    surface, and ``_cache_size`` passes through so prewarm-set gate tests
    keep asserting on it.  A non-jit callable (or a jax too old to expose
    ``_cache_size``) passes through unwrapped — the sentinel degrades to
    the global compile histogram, never to an error.

    Concurrency note: the before/after cache-size read pair is not
    atomic across threads; two threads compiling the same kernel
    concurrently may attribute both compiles to one call.  The TOTAL per
    kernel stays exact (cache size is monotone), which is what the
    steady-state-zero assertion needs.
    """
    cache_size = getattr(fn, "_cache_size", None)
    if cache_size is None:
        return fn
    _install_compile_listener()

    def wrapped(*args, **kwargs):
        before = cache_size()
        out = fn(*args, **kwargs)
        grew = cache_size() - before
        if grew > 0:
            _compiles(kernel).inc(grew)
            from advanced_scrapper_tpu.obs import trace

            trace.record("event", "jit.compile", kernel=kernel, n=int(grew))
        return out

    wrapped.__name__ = getattr(fn, "__name__", kernel)
    wrapped.__qualname__ = getattr(fn, "__qualname__", kernel)
    wrapped.__wrapped__ = fn
    wrapped._cache_size = cache_size
    wrapped._sentinel_kernel = kernel
    return wrapped


# -- windowed reads -----------------------------------------------------------


def jit_compiles_by_kernel() -> dict[str, float]:
    """Cumulative sentinel counts per kernel label (subtract two
    snapshots to window a regime — bench does)."""
    out: dict[str, float] = {}
    for c in telemetry.REGISTRY.find(JIT_COMPILES):
        k = c.labels.get("kernel", "")
        out[k] = out.get(k, 0.0) + c.value
    return out


def jit_compiles_total() -> float:
    """Cumulative sentinel count across every instrumented kernel."""
    return sum(jit_compiles_by_kernel().values())


def compile_seconds_count() -> tuple[int, float]:
    """``(count, sum_seconds)`` of the global backend-compile histogram —
    the catch-everything half of the steady-state-zero assertion."""
    n, s = 0, 0.0
    for h in telemetry.REGISTRY.find(COMPILE_SECONDS):
        n += h.count
        s += h.sum
    return n, s


def _clear_for_tests() -> None:
    """Registry-reset hook: drop cached handles so a reset never leaves
    orphaned series being fed outside the registry's view (the
    obs/stages.py lesson)."""
    with _lock:
        _hists.clear()
        _lag_hists.clear()
        _compile_counters.clear()


telemetry.REGISTRY.add_reset_hook(_clear_for_tests)
