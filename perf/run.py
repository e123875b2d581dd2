#!/usr/bin/env python3
"""Run one cell of heat_tpu's benchmark once and print its numbers.

    python3 perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no children.  Set-up (import, data made on the device from the
seed, compile or cache load, warm-up of this cell's shapes, autotune's
explore) is timed as ``setup_s``; then a closed loop of one caller makes the
cell's calls for ``--seconds``; then the program's state is dropped and the
plain reference judges a sample of what the calls returned.  The last line of
standard output is the result as one JSON object.

``--trace 0`` prints the cell's end-to-end metrics.  ``--trace 1`` holds a
profiler trace over a shorter window of its own (the workload's
``trace_seconds``, or at least ``trace_min_calls`` calls) and prints the
cell's per-layer metrics and a breakdown.

With no TPU, fewer or more devices than the cell's chips, or a device kind
missing from ``perf/peaks.json``, nothing is run and the exit code is 2.
``--rehearse-cpu`` is the only CPU mode: toy sizes, ``platform`` "cpu", no
device metric in the line.  It proves control flow, never speed.
"""

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import manifest as mf  # noqa: E402
from perf import traffic as tf  # noqa: E402
from perf import trace_reduce  # noqa: E402
from perf.work_models import floor_seconds  # noqa: E402

NO_DEVICE = 2
WARM_INDEX = 1 << 30  # warm-up calls are numbered apart from the window's
COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


def say(*parts):
    print(*parts, file=sys.stderr, flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    return ap.parse_args(argv)


def percentile(values, pct):
    """Nearest-rank percentile of all values."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * pct // 100))
    return ordered[int(rank) - 1]


def ready(jax, out):
    """Block until every device array in ``out`` is ready."""
    leaves = jax.tree.leaves(out, is_leaf=lambda v: hasattr(v, "larray"))
    jax.block_until_ready([getattr(v, "larray", v) for v in leaves])


class Counters:
    """What the program and JAX count, read before and after the window."""

    def __init__(self, jax):
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, name, _secs, **_kw):
        if name in COMPILE_EVENTS:
            self.compiles += 1

    def read(self):
        from heat_tpu.core import autotune, fusion

        fused = fusion.cache_stats()
        return {
            "jax_compiles": self.compiles,
            "fusion_misses": fused["misses"],
            "fusion_fallbacks": fused["fallbacks"],
            "autotune_explores": autotune.stats()["explores"],
        }


def run_window(jax, driver, state, calls, seconds, min_calls, sample, seed, annotate,
               collect):
    """The closed loop.  Returns what the metrics and the check need.

    ``collect``: run Python's cycle collector before every call, inside the
    window and outside the call's own time (a workload's ``collect_garbage``:
    "each_call"), for programs whose results sit in reference cycles.
    """
    import gc

    import numpy as np

    if collect:
        gc.collect()
        gc.freeze()  # what set-up left is not garbage: collections stay cheap

    rng = np.random.default_rng([int(seed), 0x5A3])
    size = int(sample.get("calls", 1))
    kept, last = [], None
    times, items, failed, collected = [], [], 0, []
    span = jax.profiler.TraceAnnotation if annotate else (lambda _name: contextlib.nullcontext())
    out = None
    begin = time.perf_counter()
    while True:
        item = next(calls)
        out = last = None  # the previous call's results are dropped first
        if collect:
            t_gc = time.perf_counter()
            gc.collect()
            collected.append(time.perf_counter() - t_gc)
        t0 = time.perf_counter()
        try:
            with span(trace_reduce.CALL):
                out = driver.call(state, item)
                ready(jax, out)
        except Exception as exc:  # a failed call is counted, the loop goes on
            failed += 1
            say(f"call {item['index']} failed: {type(exc).__name__}: {exc}")
            if failed > 3 and not times:
                raise
        else:
            t1 = time.perf_counter()
            times.append(t1 - t0)
            items.append(item)
            last = driver.keep(state, item, out)
            if sample.get("policy", "reservoir") == "reservoir":
                n = len(times) - 1
                if n < size:
                    kept.append(last)
                else:
                    j = int(rng.integers(0, n + 1))
                    if j < size:
                        kept[j] = last
        now = time.perf_counter()
        if now - begin >= seconds and len(times) + failed >= min_calls:
            break
    if last is not None and not any(k is last for k in kept):
        kept.append(last)
    return {
        "elapsed": now - begin, "times": times, "items": items,
        "failed": failed, "kept": kept, "collected": collected,
    }


def rehearsal_env(chips):
    """The only CPU mode: as many virtual devices as the cell has chips,
    32-bit types as on the chip.  Before JAX is imported."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={chips}"
    )
    os.environ["HEAT_TPU_X64"] = "0"


def build(jax, ht, cell, seed, marks=None):
    """Data on the device from the seed, then the driver's set-up.  Returns
    ``(driver, state, ctx)``; ``marks`` collects the seconds of each part."""
    config, wl = cell["config"], cell["workload"]
    started = time.perf_counter()
    driver = mf.load_module("drivers", wl["driver"])
    generator = mf.load_module("generators", config["data"]["generator"])
    data = generator.make(config, seed, ht.get_comm().sharding(config["split"], 2))
    ready(jax, data)
    made = time.perf_counter()
    ctx = types.SimpleNamespace(
        ht=ht, config=config, workload=wl, data=data, seed=seed,
        chips=int(cell["cell"]["chips"]),
    )
    state = driver.setup(ctx)
    del ctx.data
    if marks is not None:
        marks["data_s"] = made - started
        marks["driver_setup_s"] = time.perf_counter() - made
    return driver, state, ctx


def gather(trace, window, counters, cell, config, peaks, memory_peak):
    """What the per-layer readers read (see perf/layer_metrics)."""
    chips = cell["cell"]["chips"]
    floor, bounds = None, {}
    if peaks is not None:
        model = mf.load_module("work_models", cell["workload"]["work_model"])
        floor = 0.0
        for item in window["items"]:
            secs, which = floor_seconds(model.work(config, item, chips), peaks)
            floor += secs
            bounds[which] = bounds.get(which, 0.0) + secs
    return {
        "trace": trace, "calls": len(window["items"]), "floor_s": floor,
        "roofline_bound": max(bounds, key=bounds.get) if bounds else None,
        "counters": counters, "memory_peak_bytes": memory_peak, "chips": chips,
    }


def main(argv=None):
    args = parse_args(sys.argv[1:] if argv is None else argv)
    try:
        manifest = mf.load_manifest()
        cell = mf.load_cell(manifest, args.workload, args.rehearse_cpu)
    except mf.ManifestError as exc:
        say(f"perf/run.py: {exc}")
        return NO_DEVICE
    chips = int(cell["cell"]["chips"])
    config, wl = cell["config"], cell["workload"]

    if args.rehearse_cpu:
        rehearsal_env(chips)
    if args.trace:
        # the program's spans enter jax.profiler.TraceAnnotation in this mode
        os.environ["HEAT_TPU_TELEMETRY"] = "trace"

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if not args.rehearse_cpu and device["platform"] != "tpu":
        say(f"perf/run.py needs a TPU; JAX found platform {device['platform']!r} "
            f"({device['kind']!r}, {device['count']} device(s)).  Nothing was run.")
        return NO_DEVICE
    if device["count"] != chips:
        say(f"perf/run.py: cell {args.workload!r} is sized for {chips} chip(s); "
            f"JAX found {device['count']} {device['kind']!r}.  Nothing was run.")
        return NO_DEVICE
    peaks = None
    if not args.rehearse_cpu:
        try:
            peaks = mf.load_peaks(device["kind"])
        except mf.ManifestError as exc:
            say(f"perf/run.py: {exc}")
            return NO_DEVICE
    try:
        import heat_tpu as ht
        from heat_tpu.core import autotune
        from heat_tpu.utils import compile_cache
    except ImportError as exc:
        say(f"perf/run.py drives the heat_tpu package beside perf/: {exc}")
        return NO_DEVICE

    marks = {"import_s": time.perf_counter() - _T0}
    compile_cache.enable()
    counters = Counters(jax)
    driver, state, ctx = build(jax, ht, cell, args.seed, marks)

    # warm-up: every shape of the mix, often enough that autotune's explore
    # (both arms, explore_k samples each) is over before the window
    for shape in tf.shapes(wl["traffic"]):
        for rep in range(autotune.explore_k() + 1):
            item = dict(shape, index=WARM_INDEX + rep, u=0.5)
            ready(jax, driver.call(state, item))
    setup_s = time.perf_counter() - _T0
    marks["warm_up_s"] = setup_s - sum(marks.values())

    calls = tf.calls(wl["traffic"], args.seed)
    sample = wl["check"].get("sample", {})
    collect = wl.get("collect_garbage") == "each_call"
    before = counters.read()
    trace = None
    if args.trace:
        log_dir = os.path.join(PERF_DIR, "out", "trace", args.workload)
        shutil.rmtree(log_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        seconds = min(args.seconds, float(wl.get("trace_seconds", 4)))
        jax.profiler.start_trace(log_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                window = run_window(
                    jax, driver, state, calls, seconds,
                    int(wl.get("trace_min_calls", 2)), sample, args.seed, True,
                    collect,
                )
        finally:
            jax.profiler.stop_trace()
    else:
        window = run_window(jax, driver, state, calls, args.seconds, 1, sample,
                            args.seed, False, collect)
    after = counters.read()
    delta = {k: after[k] - before[k] for k in after}
    stats = [d.memory_stats() or {} for d in devs]
    memory_peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    device["memory_peak_bytes"] = int(memory_peak)

    times, elapsed = window["times"], window["elapsed"]
    if not times:
        say("perf/run.py: no call completed")
        return 1

    # the program's state goes before the reference runs
    kept = window.pop("kept")
    driver.release(state)
    t_check = time.perf_counter()
    numbers, info = driver.check(state, kept, ctx)
    check_s = time.perf_counter() - t_check
    del kept

    limits = wl["check"]["limits"]
    compared, correct = {}, True
    for name, value in numbers.items():
        if name not in limits:
            say(f"perf/run.py: {args.workload}: number {name!r} has no limit in the workload file")
            return 1
        ok = value == value and value <= limits[name]
        correct = correct and ok
        compared[name] = [value, limits[name]]
    # whatever the cell: a call that failed inside the window (the loop goes
    # on, so that the count is whole), or a fused program of the library that
    # fell back to eager there, is a run that did not do its work
    compared["failed_calls"] = [window["failed"], 0]
    compared["fusion_fallbacks"] = [delta["fusion_fallbacks"], 0]
    correct = correct and window["failed"] == 0 and delta["fusion_fallbacks"] == 0

    metrics = {}
    if args.trace:
        if not args.rehearse_cpu:
            trace = trace_reduce.reduce(trace_reduce.load_xplane(trace_reduce.find_xplane(log_dir)))
            busy = [d["busy_s"] for d in trace["devices"]]
            device["busy_s"] = sum(busy) / len(busy)
            device["window_s"] = trace["window_s"]
        run = gather(trace, window, delta, cell, config, peaks, memory_peak)
        for metric in cell["per_layer"]:
            if args.rehearse_cpu and metric["source"] == "device_trace":
                continue
            value = mf.load_module("layer_metrics", metric["name"]).read(run)
            if value is not None:
                metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    else:
        values = {
            "call_s": elapsed / len(times),
            "call_p95_s": percentile(times, 95),
            "setup_s": setup_s,
        }
        for metric in cell["end_to_end"]:
            metrics[metric["name"]] = {"value": values[metric["name"]], "unit": metric["unit"]}

    result = {
        "correct": correct,
        "attempted": len(times) + window["failed"],
        "failed": window["failed"],
        "metrics": metrics,
        "device": device,
    }
    if trace is not None:
        result["breakdown"] = {
            "device_ops": trace_reduce.top(trace["devices"][trace["fullest"]]["ops"]),
            "idle_gaps": trace_reduce.top(trace["idle_gaps"]),
        }
        result["roofline_bound"] = run["roofline_bound"]
    result["rehearsal"] = bool(args.rehearse_cpu)
    result["window"] = {
        "calls": len(times), "elapsed_s": elapsed, "setup_s": setup_s, "setup_parts": marks,
        "check_s": check_s, "median_call_s": percentile(times, 50), "max_call_s": max(times),
        "slow_calls": sum(t > 1.1 * percentile(times, 50) for t in times),
        "between_calls_s": elapsed - sum(times), "collect_s": sum(window["collected"]),
        "collect_max_s": max(window["collected"], default=0.0),
        "counters": delta, "info": info,
    }
    result["check"] = compared
    print(json.dumps(result), flush=True)
    for name, (value, limit) in compared.items():
        say(f"check {name}: {value!r} limit {limit!r} {'ok' if value <= limit else 'NOT OK'}")
    say(f"correct: {correct}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
