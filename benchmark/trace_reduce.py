"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData``.  Device planes are the
``/device:TPU:<n>`` planes; on each, the ``XLA Modules`` line holds one
event per program run (named ``jit_<function>(<id>)``) and ``XLA Ops``
one per operation.  Host planes hold the benchmark's own
``TraceAnnotation`` spans (names starting ``bench.``) on the clock the
device events are placed on.

- busy: the union of the device-operation intervals inside the window,
  averaged over the chips used; idle share = 1 - busy / window;
- per-program device time: the summed durations of a program's module
  events, by jitted-function name;
- breakdown: the programs that took most device time, and the longest
  idle gaps, each labelled by the innermost benchmark span open on the
  host at the gap's middle (``other_host`` when none is).
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

_MODULE_ID = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    """``jit_fused_tile_step(123)`` → ``fused_tile_step``."""
    name = _MODULE_ID.sub("", event_name.strip())
    return name[4:] if name.startswith("jit_") else name


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclass
class Reduction:
    window_s: float
    busy_s: float                       # averaged over the device planes
    devices: int
    program_s: dict = field(default_factory=dict)   # name -> device seconds
    gaps: list = field(default_factory=list)        # [(label, seconds)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 else 0.0

    def seconds_of(self, fragment: str) -> float:
        """Device seconds of every program whose name contains ``fragment``."""
        return sum(s for n, s in self.program_s.items() if fragment in n)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.program_s.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in self.gaps[:top]],
        }


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def reduce_trace(path: str, window_span: str = "bench.window",
                 span_prefix: str = "bench.") -> Reduction:
    """Reduce the trace at ``path``.  The window is the host span named
    ``window_span``; without one, the extent of all device events."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans: list[tuple[float, float, str]] = []
    devices: list[tuple[list, list]] = []  # per device: (module events, op intervals)
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in plane.name:
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods += [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
                elif line.name == "XLA Ops":
                    ops += [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
            if mods or ops:
                devices.append((mods, ops or [(s, e) for _n, s, e in mods]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    win = [(s, e) for s, e, n in spans if n == window_span]
    if win:
        w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    else:
        ends = [iv for _m, ops in devices for iv in ops]
        w0 = min((s for s, _ in ends), default=0.0)
        w1 = max((e for _, e in ends), default=0.0)
    window_ns = max(w1 - w0, 0.0)
    inner = [(s, e, n) for s, e, n in spans if n != window_span]
    program_ns: dict[str, float] = {}
    busy_ns = 0.0
    gaps: list[tuple[str, float]] = []
    for mods, ops in devices:
        for name, s, e in mods:
            lo, hi = max(s, w0), min(e, w1)
            if hi > lo:
                key = program_name(name)
                program_ns[key] = program_ns.get(key, 0.0) + (hi - lo)
        busy = _union([(max(s, w0), min(e, w1)) for s, e in ops if min(e, w1) > max(s, w0)])
        busy_ns += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps.append((_label(inner, (g0 + g1) / 2), (g1 - g0) / 1e9))
    n = max(len(devices), 1)
    gaps.sort(key=lambda g: -g[1])
    return Reduction(
        window_s=window_ns / 1e9,
        busy_s=busy_ns / n / 1e9,
        devices=len(devices),
        program_s={k: v / n / 1e9 for k, v in program_ns.items()},
        gaps=gaps,
    )


def _label(spans: list[tuple[float, float, str]], t: float) -> str:
    """The innermost (shortest) benchmark span open at ``t``."""
    open_ = [(e - s, n) for s, e, n in spans if s <= t <= e]
    return min(open_)[1] if open_ else "other_host"


def describe(path: str, per_line: int = 3) -> str:
    """Planes, lines and a few events of a trace: for reading one by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(evs)}")
            for e in evs[:per_line]:
                out.append(f"    {e.name!r} start_ns={e.start_ns} dur_ns={e.duration_ns}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    p = find_xplane(sys.argv[1]) if os.path.isdir(sys.argv[1]) else sys.argv[1]
    print(describe(p))
    r = reduce_trace(p)
    print(r, r.breakdown())
