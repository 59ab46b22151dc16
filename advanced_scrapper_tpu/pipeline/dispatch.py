"""Depth-N pipelined device-dispatch executor for the tile hot paths.

Every device-tile plane in the tree is the same four-stage pipeline —
**encode** (host rows → width-group tiles), **pack** (one contiguous
buffer per tile, ``ops/pack.py``), **put** (``jax.device_put``), and
**dispatch** (a fused jitted step) — and throughput on a
transfer-bound link comes from keeping all four saturated at once.
``pipeline/dedup.py`` used to hand-roll this twice (an inline loop at
``put_workers == 1``, a locked-generator stage graph above it); this
module is the ONE executor, expressed on the PR 7 runtime, and it is
deliberately workload-blind: the dedup signature plane
(``pipeline/dedup.py``, donated running accumulator), the matcher
screen plane (``pipeline/matcher.py``, independent per-tile masks) and
the MESH-SHARDED dedup plane (a sharded source on the same graph: each
"tile" is a per-shard group whose ``put`` issues one ``device_put`` per
shard and whose dispatch is one partitioned fused step —
``parallel/sharded_packed.py``) ride the same three stages —
``pack``/``put`` are caller-supplied callables, the executor knows
nothing of any workload:

- the ``pack`` stage draws tiles off the encode generator
  (``StageGraph``'s ``source_iter`` wraps it in a locked puller) and
  packs them on a worker thread, overlapping the next tile's encode
  with the previous tile's transfer;
- the ``h2d`` stage (``put_workers`` threads) issues the device puts —
  on transports where each put is a serialized round trip (DESIGN.md
  §5) concurrent puts overlap that latency;
- the caller's thread drains the ``staged`` edge and dispatches (the
  caller owns the dispatch because donation needs a single buffer
  owner — the dedup accumulator — and because matcher mask results
  must stay with the chunk's thread); the edge's capacity is the
  **dispatch window** (``ASTPU_DEDUP_DISPATCH_WINDOW`` /
  ``ASTPU_MATCH_DISPATCH_WINDOW``) — how many transferred tiles may
  wait in flight ahead of the dispatch.  Total resident tiles are
  bounded at ``window + put_workers + 1`` (buffered + transferring +
  dispatching) plus at most two packed host buffers awaiting transfer,
  so backpressure — not the encode rate — sets host memory.

Every staged tile is stamped as the put pool finishes it and the gap to
the caller's pop lands on the always-on
``astpu_dispatch_queue_lag_seconds{graph}`` histogram (``obs/devprof.py``):
near-zero lag means the dispatch loop consumes tiles the moment they
land (dispatch is the bottleneck — deepen nothing), sustained lag means
H2D runs ahead and the window absorbs it (the transport is the
bottleneck — the knob sweeps have headroom).  The stamp is internal:
callers still iterate exactly what their ``put`` returned.

Out-of-order arrival from the put pool never matters to either rider
(the dedup min-combine is order-independent; matcher tiles carry their
row→article owners); a worker error closes every edge and re-raises at
the consumer (the runtime's error fan-out).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Iterable, Iterator

from advanced_scrapper_tpu.runtime import DONE, StageGraph

__all__ = [
    "DispatchTimeout",
    "OOM_FLOOR_ROWS",
    "PipelinedDispatcher",
    "dispatch_with_oom_backoff",
    "is_resource_exhausted",
    "resolve_dispatch_window",
    "resolve_watchdog_s",
]


class DispatchTimeout(RuntimeError):
    """The dispatch watchdog tripped: no tile made progress inside the
    wall-clock budget.  The graph is already torn down and the flight
    recorder already dumped when this reaches the caller — a counted,
    debuggable failure instead of a silent wedge."""


def resolve_watchdog_s(watchdog_s: float | None = None) -> float:
    """Effective per-tile watchdog budget: explicit value wins, else
    ``ASTPU_DISPATCH_WATCHDOG_S`` (seconds; 0 = off, the default — first
    tiles legitimately pay multi-second XLA compiles, so the budget is
    an operator's declaration, not a guess)."""
    if watchdog_s is not None and watchdog_s > 0:
        return float(watchdog_s)
    try:
        return float(os.environ.get("ASTPU_DISPATCH_WATCHDOG_S", "0") or 0)
    except ValueError:
        return 0.0


def resolve_dispatch_window(window: int, put_workers: int) -> int:
    """Effective staged-edge capacity: explicit ``window`` wins, else
    ``max(2, put_workers)`` — double buffering on local backends, a
    put-pool-deep window on serializing transports (where puts complete
    out of order and a shallow edge would stall the pool)."""
    if window and window > 0:
        return window
    return max(2, put_workers)


class PipelinedDispatcher:
    """Run ``tiles → pack → put`` as a stage graph and iterate the staged
    results in the caller's thread, which owns the dispatch step (a
    donated accumulator must only ever be touched from one thread; a
    per-chunk mask drain must stay with its chunk).

    Iteration yields whatever ``put`` returned, ends when the encode
    iterator is exhausted and every staged tile was handed over, and
    re-raises the first worker error (with the original as cause).
    """

    def __init__(
        self,
        tiles: Iterable,
        *,
        pack: Callable,
        put: Callable,
        put_workers: int = 1,
        window: int = 0,
        name: str = "dedup.h2d",
        watchdog_s: float | None = None,
    ):
        from advanced_scrapper_tpu.obs import devprof

        window = resolve_dispatch_window(window, put_workers)
        self._watchdog_s = resolve_watchdog_s(watchdog_s)
        self._beat = time.monotonic()
        self._finished = threading.Event()
        self._lag_hist = devprof.queue_lag_histogram(name)

        def stamped_put(item, _put=put):
            # the staged-pop lag clock starts the instant the transfer
            # completes (stamp taken AFTER _put returns — stamping first
            # would fold the whole H2D into "lag" and invert the
            # bottleneck diagnostic); __iter__ unwraps, so riders never
            # see the stamp
            staged = _put(item)
            return (time.perf_counter(), staged)

        self._graph = StageGraph(name)
        # the packed edge is a FIXED two-deep buffer (pack is cheap next
        # to put+dispatch; two keeps the put pool fed across a pop) — it
        # must NOT scale with the window, or total resident tiles would
        # double past the documented window + put_workers + 1 bound
        packed = self._graph.edge("packed", capacity=2)
        self._staged = self._graph.edge("staged", capacity=window)
        self._graph.stage(
            "pack", source_iter=tiles, fn=pack, out_edge=packed
        )
        self._graph.stage(
            "h2d",
            in_edge=packed,
            fn=stamped_put,
            out_edge=self._staged,
            workers=max(1, put_workers),
        )
        self._graph.start()
        if self._watchdog_s > 0:
            t = threading.Thread(
                target=self._watch, daemon=True, name=f"astpu-{name}-watchdog"
            )
            t.start()

    # -- watchdog ----------------------------------------------------------

    def beat(self) -> None:
        """Progress heartbeat.  The iterator beats on every staged pop
        and on every re-entry (i.e. after the caller's dispatch of the
        previous tile returned) — so a hang ANYWHERE on the tile path
        (encode, pack, put, or the caller's device dispatch) leaves the
        beat stale and trips the watchdog."""
        self._beat = time.monotonic()

    def _watch(self) -> None:
        budget = self._watchdog_s
        tick = max(0.01, min(0.25, budget / 4))
        while not self._finished.wait(tick):
            if time.monotonic() - self._beat <= budget:
                continue
            from advanced_scrapper_tpu.obs import telemetry, trace

            # counted timeout → flight-recorder dump (the fault hooks
            # land every live graph's drain snapshot in the ring first)
            # → whole-graph teardown.  The blocked consumer wakes on the
            # closed staged edge and re-raises; a consumer stuck INSIDE
            # a hung device call cannot be unwedged from here, but the
            # dump + teardown make the hang visible and bounded instead
            # of silent.
            telemetry.event_counter(
                "astpu_dispatch_watchdog_trips_total",
                "dispatch tiles that blew their wall-clock budget "
                "(graph torn down with a flight-recorder dump)",
            ).inc()
            trace.record(
                "event", "dispatch.watchdog",
                graph=self._graph.name, budget_s=budget,
            )
            trace.dump_on_fault(
                f"dispatch watchdog: no tile progress in {budget:.3g}s "
                f"on graph '{self._graph.name}'"
            )
            self._graph.fail(
                DispatchTimeout(
                    f"no tile progress in {budget:.3g}s "
                    f"(graph '{self._graph.name}')"
                )
            )
            return
        # clean finish: nothing to do

    @property
    def error(self) -> BaseException | None:
        return self._graph.error

    def __iter__(self) -> Iterator:
        while True:
            self.beat()  # re-entered: the caller's dispatch made progress
            item = self._staged.pop()
            self.beat()  # popped: the put pool made progress
            if item is DONE:
                self._finished.set()
                if self._graph.error is not None:
                    err = self._graph.error
                    if isinstance(err, DispatchTimeout):
                        raise err
                    raise RuntimeError(
                        "pipelined dispatch worker died mid-corpus"
                    ) from err
                return
            staged_ts, payload = item
            self._lag_hist.observe(time.perf_counter() - staged_ts)
            yield payload

    def close(self, timeout: float = 30.0) -> None:
        """Stop the graph (idempotent; safe mid-iteration on error paths)."""
        self._finished.set()
        self._graph.stop()
        self._graph.join(timeout=timeout, raise_error=False)


# -- device-OOM tile backoff --------------------------------------------------

#: halving floor: tiles never shrink below this row count (it is also the
#: chunker's minimum tail tile — ``core.tokenizer.tile_rows_options`` —
#: so every backoff shape is already in the prewarmed set and a backoff
#: ladder can never recompile-storm).  At the floor, a still-exhausted
#: device is a real capacity failure and the error propagates cleanly.
OOM_FLOOR_ROWS = 64

_OOM_MARKERS = ("resource_exhausted", "resource exhausted", "out of memory")


def is_resource_exhausted(exc: BaseException) -> bool:
    """Does this exception smell like a device allocation failure?  XLA
    raises ``XlaRuntimeError`` with a ``RESOURCE_EXHAUSTED:`` status
    prefix; match on the message (the exception class moved modules
    across jaxlib versions, the status string never did)."""
    s = f"{type(exc).__name__}: {exc}".lower()
    return any(m in s for m in _OOM_MARKERS)


_chaos_oom_lock = threading.Lock()
_chaos_oom_used = 0


def reset_chaos_oom() -> None:
    """Re-arm the ``ASTPU_CHAOS_DISPATCH_OOM`` budget (tests)."""
    global _chaos_oom_used
    with _chaos_oom_lock:
        _chaos_oom_used = 0


def maybe_inject_oom(plane: str) -> None:
    """Chaos seam: ``ASTPU_CHAOS_DISPATCH_OOM=N`` makes the next N
    dispatch attempts raise a synthetic ``RESOURCE_EXHAUSTED`` (counted
    on the shared fault-injection ledger) — how tier-1 certifies the
    halving ladder on hardware that never actually OOMs."""
    spec = os.environ.get("ASTPU_CHAOS_DISPATCH_OOM", "")
    if not spec:
        return
    try:
        budget = int(spec)
    except ValueError:
        return
    if budget <= 0:
        return
    global _chaos_oom_used
    with _chaos_oom_lock:
        if _chaos_oom_used >= budget:
            return
        _chaos_oom_used += 1
    from advanced_scrapper_tpu.obs import telemetry

    telemetry.event_counter(
        "astpu_fault_injected_total",
        "chaos faults injected, by plane and kind",
        plane="dispatch", kind="oom",
    ).inc()
    raise RuntimeError(
        "RESOURCE_EXHAUSTED: injected device OOM (ASTPU_CHAOS_DISPATCH_OOM)"
    )


def dispatch_with_oom_backoff(
    fn: Callable,
    carry,
    item,
    *,
    split: Callable,
    rows_of: Callable,
    floor: int = OOM_FLOOR_ROWS,
    plane: str = "dedup",
):
    """Run one device dispatch ``fn(carry, item) -> carry`` with
    automatic tile-size backoff on device OOM.

    ``RESOURCE_EXHAUSTED`` (or the injected chaos equivalent) halves the
    tile: ``split(item)`` re-packs it as two half-row sub-tiles (paying
    one D2H + two H2D, all counted on the device ledger) and each half
    retries recursively — so a transient memory squeeze converges to the
    same fold, byte-identical, at smaller dispatch granularity.  Tiles
    are power-of-two rows, so every backoff shape is in the prewarmed
    O(log bs) set (no recompile storm).  At ``floor`` rows the error
    propagates — a clean, attributable failure, never a wedge.  Any
    non-OOM error propagates untouched.
    """
    try:
        maybe_inject_oom(plane)
        return fn(carry, item)
    except Exception as e:
        if not is_resource_exhausted(e):
            raise
        rows = int(rows_of(item))
        if rows <= floor or rows < 2:
            raise
        from advanced_scrapper_tpu.obs import telemetry, trace

        telemetry.event_counter(
            "astpu_dispatch_oom_backoff_total",
            "device-OOM tile halvings (re-packed and retried)",
            plane=plane,
        ).inc()
        trace.record(
            "event", "dispatch.oom_backoff", plane=plane,
            rows=rows, halved_to=rows // 2,
        )
        for sub in split(item):
            carry = dispatch_with_oom_backoff(
                fn, carry, sub,
                split=split, rows_of=rows_of, floor=floor, plane=plane,
            )
        return carry
