"""Benchmark harness: ``python benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.

Driven by data.  The cell's entry in ``BENCHMARK.json`` names its
configuration (``configs[].file``, whose ``entry`` names the driver
``benchmark/entries/<entry>.py``) and its traffic mix
(``benchmark/traffic/<traffic>.json``); the limits of its correctness
check are ``benchmark/limits/<cell>.json``; each per-layer metric is read
by ``benchmark/metrics/<metric>.py`` (or, for ``<stem>.<kind>``, by the
shared ``benchmark/metrics/<stem>.py``).  A later PR adds any of these as
new files and ``BENCHMARK.json`` entries, without editing this file.

One process holds the chip and does, in order: set-up (the driver builds
the system and its traffic, then runs every work item of the pool once,
so every shape the window uses is compiled or loaded from the
persistent cache), the closed-loop window (items cycled from the pool
until ``--seconds`` have passed, the last item begun before then run to
its end), then the correctness check against the plain reference.  The
last line of standard output is the result; the numbers compared, each
beside its limit, are the last lines on standard error and the last key
of the result.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.  ``--rehearse`` runs on whatever backend JAX has at
the mix's rehearsal size, reports no metric, and exits 3: a rehearsal is
never a chip run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
EXIT_NO_CHIP = 2
EXIT_REHEARSAL = 3


def say(msg: str, **fields) -> None:
    """One progress line on standard error (never the result)."""
    line = f"bench: {msg}"
    if fields:
        line += " " + json.dumps(fields, sort_keys=True, default=str)
    print(line, file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """Everything ``BENCHMARK.json`` and the cell's files say about it."""

    def __init__(self, name: str, rehearse: bool):
        bench = load_json(ROOT, "BENCHMARK.json")
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise SystemExit(f"unknown workload {name!r}; known: {sorted(by_name)}")
        self.name = name
        self.workload = by_name[name]
        conf = next(c for c in bench["configs"] if c["name"] == self.workload["config"])
        self.config = load_json(ROOT, conf["file"])
        self.traffic = load_json(HERE, "traffic", self.workload["traffic"] + ".json")
        self.limits = load_json(HERE, "limits", name + ".json")
        if rehearse:
            self.traffic = {**self.traffic, **self.traffic.get("rehearse", {})}
            self.config = {**self.config, **self.config.get("rehearse", {})}

        def applies(m: dict) -> bool:
            return name in m["workloads"] if "workloads" in m else True

        self.end_to_end = [m for m in bench["end_to_end"] if applies(m)]
        e2e_names = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m else m["moves"] in e2e_names)
        ]


class Window:
    """The closed loop: items cycled from the pool until ``seconds`` have
    passed; the last item begun before then runs to its end."""

    def __init__(self, entry, seconds: float):
        self.entry, self.seconds = entry, seconds
        self.results: list = []  # (item index, result)
        self.articles = 0
        self.t0 = self.t1 = 0.0

    def run(self) -> None:
        from jax.profiler import TraceAnnotation

        entry = self.entry
        n = entry.pool_size()
        k = 0
        with TraceAnnotation("bench.window"):
            self.t0 = time.perf_counter()
            while k == 0 or time.perf_counter() - self.t0 < self.seconds:
                i = k % n
                with TraceAnnotation(entry.SPAN):
                    self.results.append((i, entry.run(i)))
                self.articles += entry.size(i)
                k += 1
            self.t1 = time.perf_counter()

    @property
    def elapsed(self) -> float:
        return self.t1 - self.t0


def device_facts(chips: int, rehearse: bool) -> dict | None:
    """The device stamp, or None when this is no run on a chip."""
    import jax

    devs = jax.devices()
    facts = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    say("device", jax=jax.__version__, **facts)
    if rehearse:
        return facts
    if facts["platform"] != "tpu":
        say(f"no TPU: JAX reports platform {facts['platform']!r}; refusing to run")
        return None
    if facts["count"] < chips:
        say(f"the cell asks for {chips} chips, JAX reports {facts['count']}; refusing to run")
        return None
    return facts


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


def metric_reader(name: str):
    """``metrics/<name>.py``, or where there is none the reader of the
    quantity that ``name`` splits by cell kind: ``metrics/<stem>.py`` for
    ``<stem>.<kind>``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.exists(path):
        path = os.path.join(HERE, "metrics", name.split(".")[0] + ".py")
    return load_module(path, "bench_metric_" + name.replace(".", "_"))


def compare(values: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}``; a number with no limit is an error."""
    missing = sorted(set(values) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing} in limits/<cell>.json")
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}


def main(argv=None, out=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="any backend, the mix's rehearsal size, no metrics, exit 3")
    args = p.parse_args(argv)
    out = out or sys.stdout
    cell = Cell(args.workload, args.rehearse)

    # the persistent compile cache: fixed, inside the checkout, unless the
    # driver gives one; set before JAX is imported (the program reads it)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)  # the system under test is the checkout's
    import jax

    devices = device_facts(cell.workload["chips"], args.rehearse)
    if devices is None:
        return EXIT_NO_CHIP
    entry_mod = load_module(os.path.join(HERE, "entries", cell.config["entry"] + ".py"),
                            "bench_entry_" + cell.config["entry"])
    watch = CompileWatch()
    entry = entry_mod.Entry(cell, args.seed, say)
    entry.warm()
    # the pool and everything set-up made live through the window: keep
    # them out of the collector's full passes, whose timing would
    # otherwise vary from run to run with the harness's own heap
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    say("set-up done", setup_s=setup_s, compile_cache=os.environ["JAX_COMPILATION_CACHE_DIR"],
        programs=watch.programs, cache_hits=watch.hits, cache_misses=watch.misses)

    trace_dir = os.path.join(ROOT, ".bench_trace", cell.name)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    programs0 = watch.programs
    window = Window(entry, args.seconds)
    entry.begin_window()
    window.run()
    layer_ctx = entry.end_window(window)
    if args.trace:
        jax.profiler.stop_trace()
    say("window done", seconds=window.elapsed, items=len(window.results),
        articles=window.articles, programs_in_window=watch.programs - programs0)

    used = jax.devices()[: cell.workload["chips"]]
    device = dict(devices, memory_peak_bytes=memory_peak(used))
    say("device memory", peak_bytes=device["memory_peak_bytes"])
    # the reference runs on the host once the program's state is freed
    entry.release(window)

    metrics: dict = {}
    breakdown = None
    if args.trace:
        from benchmark.trace_reduce import find_xplane, reduce_trace

        red = reduce_trace(find_xplane(trace_dir))
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
        say("trace", busy_s=red.busy_s, window_s=red.window_s, programs=red.program_s)
        ctx = dict(layer_ctx, trace=red, window_s=window.elapsed, articles=window.articles,
                   device_kind=devices["kind"], say=say)
        for m in cell.per_layer:
            v = metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(entry.end_to_end(window), setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    checks = compare(entry.check(window), cell.limits)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    if args.rehearse:
        say("rehearsal: CPU numbers are not device metrics; none reported",
            readers_returned=sorted(metrics))
        metrics = {}
    line = {"correct": correct, "attempted": window.articles, "failed": 0,
            "metrics": metrics, "device": device}
    if breakdown is not None and not args.rehearse:
        line["breakdown"] = breakdown
    line["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr, flush=True)
    print(json.dumps(line), file=out, flush=True)
    return EXIT_REHEARSAL if args.rehearse else 0


class CompileWatch:
    """Counts programs compiled or loaded (``programs``) and, of those,
    persistent-cache hits and misses (a miss is a real compile)."""

    def __init__(self):
        from jax import monitoring

        self.hits = self.misses = self.programs = 0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, name: str, _secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.programs += 1


if __name__ == "__main__":
    sys.exit(main())
