"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's object from the seed (data, weights, the engine),
compiles it, and drives its first steps, whose readings the reference
checks once the window has closed. The window is whole calls into the
engine's round entry, each blocked until its outputs are ready; it closes
at the end of the first call that ends after ``--seconds``. With
``--trace 0`` the result carries the end-to-end metrics; with ``--trace 1``
the window is cut to at most ``TRACE_WINDOW_S`` and the result carries the
per-layer metrics read from its profiler trace.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, optionally
``breakdown``, and last ``checks``: each compared number with its limit).
The run refuses to start without a TPU, or with fewer chips than the cell
asks for, and then prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

#: the monitoring events JAX records for each backend compile and for each
#: program read back from the persistent cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
#: a traced run traces at most this long a part of the window: the
#: per-layer metrics need a steady stretch, and a trace of four chips over
#: 30 s holds millions of events
TRACE_WINDOW_S = 10.0
#: where a traced run keeps its trace (inside the checkout, listed in
#: .gitignore); emptied before each traced run
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class CompileCounter:
    """Backend compiles, their seconds, and persistent-cache hits."""

    def __init__(self):
        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def start(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self


def run_window(driver, seconds: float, annotate: bool) -> dict:
    """Whole calls until one ends after ``seconds``; the time is taken to
    the end of that call."""
    import jax
    updates = calls = 0
    t0 = time.perf_counter()
    while True:
        if annotate:
            with jax.profiler.TraceAnnotation("bench.call"):
                updates += driver.call()
        else:
            updates += driver.call()
        calls += 1
        t1 = time.perf_counter()
        if t1 - t0 >= seconds:
            return {"updates": updates, "calls": calls, "seconds": t1 - t0}


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def per_layer(bench: dict, name: str, driver, trace_dir: str,
              device_kind: str, chips: int) -> tuple:
    """The cell's per-layer metrics, ``busy_s``/``window_s`` and the
    breakdown, from the trace of the window."""
    from bench import metrics, peaks, trace
    tr = trace.load(trace_dir)
    lo, hi = trace.window(tr)
    ctx = {"trace": tr, "lo": lo, "hi": hi, "window_s": (hi - lo) / 1e9,
           "calls": len(tr.calls),
           "rounds": len(tr.calls) * driver.rounds_per_call,
           "driver": driver, "peaks": peaks.peaks(device_kind),
           "chips": chips}
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and name not in m["workloads"]:
            continue
        value = metrics.load(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    busy = [trace.busy_ns(d, lo, hi) / 1e9 for d in tr.devices]
    dev = {"busy_s": sum(busy) / max(1, len(busy)), "window_s": (hi - lo) / 1e9}
    breakdown = {"device_ops": trace.top_ops(tr, lo, hi),
                 "idle_gaps": trace.top_gaps(tr, lo, hi)}
    return out, dev, breakdown


def run_cell(args, bench: dict, cell: dict, used) -> int:
    """Everything after the look for the chips: set-up, window, reference,
    and the printed result."""
    import jax
    from bench import check, drivers
    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    counter = CompileCounter().start()

    spec = cell["cell"]
    driver = drivers.load(spec["driver"])(
        cell["config"], cell["traffic"], spec, args.seed, used)
    driver.setup()
    steps = int(spec["check_steps"])
    t_read = time.perf_counter()
    prog = driver.readings(steps)
    read_s = driver.read_seconds
    setup_compiles, setup_compile_s = counter.compiles, counter.seconds

    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    t_open = time.perf_counter()
    seconds = min(args.seconds, TRACE_WINDOW_S) if args.trace else args.seconds
    window = run_window(driver, seconds, annotate=bool(args.trace))
    if args.trace:
        jax.profiler.stop_trace()
    window_compiles = counter.compiles - setup_compiles
    setup_s = t_open - T_START - read_s
    mem = peak_bytes(used)
    print(f"setup: setup_s={setup_s:.4f} compiles={setup_compiles} "
          f"compile_s={setup_compile_s:.4f} cache_hits={counter.cache_hits} "
          f"cache_dir={cache_dir} readings_s={read_s:.4f} "
          f"first_steps_s={time.perf_counter() - t_read:.4f}", flush=True)
    print(f"window: calls={window['calls']} updates={window['updates']} "
          f"seconds={window['seconds']:.4f} compiles_in_window="
          f"{window_compiles}", flush=True)

    dev0 = used[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    result = {"correct": False, "attempted": window["updates"], "failed": 0}
    breakdown = None
    if args.trace:
        metrics, extra, breakdown = per_layer(
            bench, args.workload, driver, TRACE_DIR, dev0.device_kind,
            len(used))
        device.update(extra)
    else:
        metrics = {
            "updates_per_s": {"value": window["updates"] / window["seconds"],
                              "unit": "updates/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}

    driver.free()
    t_ref = time.perf_counter()
    ref = driver.reference(steps)
    nums = check.numbers(prog, ref)
    nums["compiles_in_window"] = window_compiles
    ok, table = check.verdict(nums, dict(spec["limits"], compiles_in_window=0))
    print(f"reference: seconds={time.perf_counter() - t_ref:.4f}", flush=True)
    result.update(correct=bool(ok), metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = table
    for name, row in table.items():
        print(f"check {name} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import common
    bench = common.manifest()
    cell = common.resolve_cell(args.workload, bench)
    chips = cell["chips"]

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r}); "
              "refusing to run", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    return run_cell(args, bench, cell, devices[:chips])


if __name__ == "__main__":
    sys.exit(main())
