"""Chip benchmark of the hierarchical federation: one cell, one run.

    python3 benchmarks/chip/run.py --workload heartbeat-paper --seed 7 --seconds 30 --trace 0

Reads the cell from ``BENCHMARK.json`` at the checkout's root: its
configuration (``configs/<config>.json``), its traffic
(``traffic/<traffic>.json``), the driver that the traffic names
(``drivers/<driver>.py``), its limits (``limits/<workload>.json``) and, with
``--trace 1``, one reader per per-layer metric (``metrics/<name>.py``).
Needs a TPU with as many chips as the cell asks for; anywhere else it exits
2 and prints no result.

Set-up builds the engine from the seed and drives it through the warm-up
(``run(1)``, ``run(3)``, then ``run(2)`` until one compiles nothing).  The
window is one ``engine.run(R)`` call, R sized from the warm rounds to fill
``--seconds``; any compile inside it fails the run (exit 3, no result).
Then the engine is freed and the plain reference follows the warm-up's
first two calls; the last line of standard output is the result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

WARM_CALLS_MAX = 6
TRACE_SECONDS = 4.0  # length of the traced window: traces grow with it


def process_start() -> float:
    """Seconds on ``time.time()`` at which this process started."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


class BenchError(RuntimeError):
    """A run that cannot give a result (no chip, a compile in the window)."""

    def __init__(self, msg: str, code: int):
        super().__init__(msg)
        self.code = code


def load_cell(workload: str) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json", 2)
    w = cells[workload]
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [workload]) and m["moves"] in reported]
    limits_file = HERE / "limits" / f"{workload}.json"
    return {
        "workload": w,
        "config": json.loads((HERE / "configs" / f"{w['config']}.json").read_text()),
        "traffic": json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text()),
        "limits": json.loads(limits_file.read_text()) if limits_file.exists() else None,
        "end_to_end": e2e,
        "per_layer": layer,
    }


def require_chips(n: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX sees platform {devices[0].platform!r}", 2)
    if len(devices) < n:
        raise BenchError(f"the cell needs {n} chips, JAX sees {len(devices)}", 2)
    return devices[:n]


def enable_compile_cache() -> str:
    """Persistent compilation cache at a fixed path inside the checkout,
    every program cached however fast it compiled."""
    import jax

    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(cache)


class CompileCounter:
    """Programs compiled or loaded from the persistent cache, process-wide."""

    def __init__(self):
        from jax._src import monitoring

        self.compiles = 0
        self.loads = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.loads += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    @property
    def total(self) -> int:
        return self.compiles + self.loads


def call(engine, rounds: int, start) -> dict:
    """One ``engine.run(rounds)``, which starts from the model ``start``.
    Returns the start and end models as numpy trees, the per-round mean
    local losses and the engine's result."""
    import jax
    import numpy as np

    res = engine.run(rounds, eval_every=1)
    jax.block_until_ready(res.final_params)
    return {"start": start, "end": jax.tree.map(np.asarray, res.final_params),
            "losses": [m.mean_local_loss for m in res.history], "result": res}


def warm_up(driver, counter: CompileCounter) -> tuple:
    """The compared calls (``compare.CALLS``) of ``driver.engine``, then
    ``run(2)`` until a call compiles nothing.  Each compared call starts
    from the initial model, or from the previous call's end where the
    driver says that its engine's ``run`` resumes (``driver.resumes``).
    Returns (compared calls, seconds per warm round)."""
    import statistics

    import compare
    import jax
    import numpy as np

    engine = driver.engine
    start = jax.tree.map(np.asarray, engine.params)  # run() sets it to its end model
    calls = []
    for r in compare.CALLS:
        calls.append(call(engine, r, start))
        if driver.resumes:
            start = calls[-1]["end"]
    for _ in range(WARM_CALLS_MAX):
        before = counter.total
        last = call(engine, 2, start)
        if counter.total == before:
            break
    else:
        raise BenchError(f"still compiling after {WARM_CALLS_MAX} warm-up calls", 3)
    per_round = statistics.median(m.wall_seconds for m in last["result"].history)
    for c in calls:
        del c["result"]
    return calls, per_round


def peak_memory(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def load_peaks(kind: str) -> dict:
    """The chip's peaks from ``peaks.json``; a kind not in it is an error."""
    peaks = json.loads((HERE / "peaks.json").read_text())
    if kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json", 2)
    return peaks[kind]


def _load(kind: str, name: str):
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", HERE / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    """The reader of per-layer metric ``name``: ``metrics/<name>.py``'s ``read``."""
    return _load("metrics", name).read


def load_driver(name: str):
    """The driver class a traffic file names: ``drivers/<name>.py``'s ``Driver``."""
    return _load("drivers", name).Driver


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return e.code
    checks = line["checks"]
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


def run(workload: str, seed: int, seconds: float, trace: bool) -> dict:
    """One run of one cell on the chips it asks for; returns the result line."""
    cell = load_cell(workload)
    if cell["limits"] is None:
        raise BenchError(f"no limits file for {workload!r}", 2)
    devices = require_chips(int(cell["workload"]["chips"]))
    return measure(cell, devices, seed, seconds, trace)


def measure(cell: dict, devices, seed: int, seconds: float, trace: bool) -> dict:
    """Set-up, window and comparison of ``cell`` (as ``load_cell`` gives it)
    on ``devices``, which the caller has checked."""
    enable_compile_cache()
    counter = CompileCounter()

    import jax
    import numpy as np

    sys.path.insert(0, str(ROOT / "src"))
    import compare
    import reference

    tel = None
    if trace:
        from tracing import AnnotatingTelemetry

        tel = AnnotatingTelemetry()
    driver = load_driver(cell["traffic"]["driver"])(cell["config"], cell["traffic"], seed,
                                                    telemetry=tel)
    engine = driver.engine
    calls, per_round = warm_up(driver, counter)
    window = min(seconds, TRACE_SECONDS) if trace else seconds
    rounds = max(2, int(math.ceil(window / per_round)))

    trace_dir = tempfile.TemporaryDirectory() if trace else None
    if trace:
        first_span = len(tel.tracer.spans)
        jax.profiler.start_trace(trace_dir.name)
    compiled_before = counter.total
    t_setup = time.time() - process_start()
    t0 = time.perf_counter()
    res = engine.run(rounds, eval_every=1)
    jax.block_until_ready(res.final_params)
    t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    in_window = counter.total - compiled_before
    if in_window:
        raise BenchError(f"{in_window} programs compiled or loaded inside the window", 3)
    history = res.history
    mem = peak_memory(devices)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "device_kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    failed = sum(1 for m in history if not (math.isfinite(m.mean_local_loss)
                                            and math.isfinite(m.test_acc)))
    metrics, breakdown = {}, None
    if trace:
        from tracing import TracedRun

        traced = TracedRun.load(trace_dir.name, tel.tracer.spans[first_span:], devices,
                                cell["config"], load_peaks(devices[0].device_kind))
        trace_dir.cleanup()
        device["busy_s"] = traced.busy_s
        device["window_s"] = traced.window_s
        breakdown = traced.breakdown()
        for m in cell["per_layer"]:
            v = load_reader(m["name"])(traced)
            if v is None:
                print(f"benchmark: {m['name']}: nothing to read in this trace", file=sys.stderr)
            else:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        walls = [m.wall_seconds for m in history]
        e2e = {"cloud_round_ms": 1000.0 * (t1 - t0) / rounds,
               "cloud_round_p95_ms": 1000.0 * float(np.percentile(walls, 95)),
               "setup_s": t_setup}
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    fed = driver.federation()
    del driver, engine, res
    gc.collect()
    ref_calls = reference.run_calls(fed, seed, compare.CALLS)
    nums = compare.numbers(calls, ref_calls)
    checks = compare.judge(nums, cell["limits"]["limits"])
    line = {"correct": bool(checks) and all(c["ok"] for c in checks.values()) and failed == 0,
            "attempted": rounds, "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": v["value"], "limit": v["limit"]} for k, v in checks.items()}
    return line


if __name__ == "__main__":
    sys.exit(main())
