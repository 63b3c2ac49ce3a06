"""What the round loop's telemetry costs, and whether its profiler link holds.

    python tools/telemetry_overhead.py --seed 3100000001 --seconds 10 --repeats 2

Builds the paper's federation (``build_scenario("heartbeat", scale=1.0)``,
EARA-SCA, ``HFLSchedule(1, 4)``, the sync engine's device pipeline) three
times over one scenario: telemetry off, ``Telemetry()``, and
``Telemetry(profile=True)`` run under a ``jax.profiler`` trace.  Each is
warmed up, then timed over ``--seconds`` of rounds (one ``run(R)`` ended by
``block_until_ready``), the three modes interleaved ``--repeats`` times.

The profiled window's ``.xplane.pb`` is then read with
``jax.profiler.ProfileData``: the ``/host`` events that carry a ``sid`` are
joined to the telemetry's spans by ``sid``, and for ``eval``, ``fetch`` and
``cohort_epoch`` it reports how many annotations overlap an "XLA Ops" event
of the device plane (on one clock, nearly all of them do), the device-idle
milliseconds per round under ``eval`` and the median lag from a
``cohort_epoch`` annotation's start to the start of the next cohort-epoch
program.  The last line of standard output is one JSON object; ``--out``
also writes it to a file.
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import statistics
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

MODES = ("off", "on", "profile")


def build(seed: int):
    import numpy as np

    from repro.core.hfl import HFLSchedule
    from repro.engine import BatchedSyncEngine
    from repro.federated import build_scenario
    from repro.telemetry import Telemetry

    sc = build_scenario("heartbeat", scale=1.0, seed=seed, n_test_per_class=300)
    lam = np.asarray(sc.assign("eara-sca").lam)
    tels = {"off": None, "on": Telemetry(), "profile": Telemetry(profile=True)}
    return {mode: BatchedSyncEngine(sc.clients, lam, sc.program, sc.test,
                                    schedule=HFLSchedule(1, 4), seed=seed, upp=1.0,
                                    telemetry=tel)
            for mode, tel in tels.items()}


def timed(engine, rounds: int) -> float:
    """Milliseconds per cloud round of one ``run(rounds)``."""
    import jax

    t0 = time.perf_counter()
    res = engine.run(rounds, eval_every=1)
    jax.block_until_ready(res.final_params)
    return 1e3 * (time.perf_counter() - t0) / rounds


def profile_check(trace_dir: str, spans, rounds: int) -> dict:
    """The profiled window's annotations against its device ops."""
    from jax.profiler import ProfileData

    import numpy as np

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    anns, ops, modules = {}, [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                t0 = int(ev.start_ns)
                iv = (t0, t0 + int(ev.duration_ns))
                if plane.name.startswith("/host"):
                    sid = dict(ev.stats).get("sid")
                    if sid is not None:
                        anns[sid] = (ev.name,) + iv
                elif plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append(iv)
                elif plane.name == "/device:TPU:0" and line.name == "XLA Modules":
                    modules.append((ev.name,) + iv)
    wall = {s.sid: s.name for s in spans if s.track == "wall"}
    out = {"annotations": len(anns), "spans": len(wall),
           "sid_join": all(anns.get(k, (None,))[0] == v for k, v in wall.items())
           and len(anns) == len(wall), "device_ops": len(ops)}
    merged = []  # union of the op intervals, sorted
    for a, b in sorted(ops):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    starts = np.array([a for a, _ in merged], np.int64)

    def busy(a, b):
        """Nanoseconds of [a, b) in which an op runs."""
        i = max(0, int(np.searchsorted(starts, a)) - 1)
        total = 0
        while i < len(merged) and merged[i][0] < b:
            total += max(0, min(b, merged[i][1]) - max(a, merged[i][0]))
            i += 1
        return total

    for name in ("eval", "fetch", "cohort_epoch"):
        ivs = [(a, b) for n, a, b in anns.values() if n == name]
        out[name] = {"count": len(ivs), "overlap_ops": sum(busy(a, b) > 0 for a, b in ivs)}
    evals = [(a, b) for n, a, b in anns.values() if n == "eval"]
    out["eval_idle_ms"] = sum(b - a - busy(a, b) for a, b in evals) / rounds / 1e6
    out["eval_ms"] = sum(b - a for a, b in evals) / rounds / 1e6
    epochs = sorted(a for n, a, _ in modules if "cohort_epoch" in n)
    lags = []
    for n, a, _ in anns.values():
        if n == "cohort_epoch":
            i = int(np.searchsorted(epochs, a))
            if i < len(epochs):
                lags.append((epochs[i] - a) / 1e6)
    out["cohort_epoch_lag_ms_median"] = statistics.median(lags) if lags else None
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3100000001)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = jax.devices()[0]
    engines = build(args.seed)
    per_round = {}
    for mode, eng in engines.items():
        for r in (1, 3, 2, 2):  # compiles, and each Telemetry's cost analyses
            per_round[mode] = timed(eng, r)
    rounds = max(2, int(math.ceil(args.seconds * 1e3 / per_round["off"])))
    ms = {mode: [] for mode in MODES}
    check = None
    for _ in range(args.repeats):
        for mode in MODES:
            eng = engines[mode]
            if mode != "profile":
                ms[mode].append(timed(eng, rounds))
                continue
            with tempfile.TemporaryDirectory() as d:
                first = len(eng.tel.tracer.spans)
                jax.profiler.start_trace(d)
                ms[mode].append(timed(eng, rounds))
                jax.profiler.stop_trace()
                if check is None:
                    check = profile_check(d, eng.tel.tracer.spans[first:], rounds)
    line = {"device": {"platform": device.platform, "kind": device.device_kind},
            "seed": args.seed, "rounds": rounds, "cloud_round_ms": ms,
            "median_ms": {m: statistics.median(v) for m, v in ms.items()},
            "profile_check": check}
    text = json.dumps(line)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
