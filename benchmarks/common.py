"""Shared benchmark utilities: timing, CSV emission, JSON result files.

Every ``emit`` prints one ``name,us_per_call,derived`` CSV row and records
it; benchmark modules bracket their rows with ``mark()`` / ``dump_json()``
to land a machine-readable ``BENCH_<module>.json`` in the repo root, so
the perf trajectory is tracked (and diffable) across PRs.

Timing goes through :class:`repro.telemetry.trace.Tracer` spans — the same
span machinery the engines record under ``Scenario.simulate(telemetry=)``
— so a benchmark number and a trace span for the same region are the same
measurement, not two stopwatches.  ``BENCH_*.json`` files carry a ``meta``
block (jax version, backend, device count, quick-vs-full mode) and every
row can record ``mean_us``/``std_us`` across repeats alongside the
best-of-N headline number.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
from pathlib import Path
from typing import Callable, Dict, List

from repro.telemetry.trace import Tracer

QUICK = os.environ.get("BENCH_FULL", "") == ""


def peak_rss_bytes() -> int:
    """Process high-water RSS in bytes (``ru_maxrss``; KB on Linux).

    Monotonic: it never goes down, so per-scale-point memory curves need a
    fresh subprocess per point (see ``benchmarks/streaming_point.py``)."""
    import resource

    ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return int(ru) * (1024 if sys.platform.startswith("linux") else 1)


def device_buffer_bytes() -> int:
    """Total bytes of live jax device buffers (0 if jax is unavailable)."""
    try:
        import jax

        return int(sum(a.nbytes for a in jax.live_arrays()))
    except Exception:
        return 0

# JSON results default to the repo root (committed alongside the code);
# BENCH_OUT redirects them (e.g. to a scratch dir in CI artifacts).
OUT_DIR = Path(os.environ.get("BENCH_OUT", Path(__file__).resolve().parent.parent))

_rows: List[Dict[str, object]] = []


def run_meta() -> Dict[str, object]:
    """Environment stamp for one benchmark run: enough to judge whether two
    ``BENCH_*.json`` files are comparable before diffing their numbers."""
    meta: Dict[str, object] = {"quick": QUICK, "python": sys.version.split()[0]}
    try:
        import jax

        meta["jax"] = jax.__version__
        meta["backend"] = jax.default_backend()
        meta["device_count"] = jax.device_count()
    except Exception:
        pass
    return meta


def emit(
    name: str,
    us_per_call: float,
    derived: str = "",
    *,
    mean_us: float = None,
    std_us: float = None,
    repeats: int = None,
    **extra: object,
) -> None:
    row: Dict[str, object] = {
        "name": name, "us_per_call": round(us_per_call, 1), "derived": derived,
    }
    if mean_us is not None:
        row["mean_us"] = round(mean_us, 1)
    if std_us is not None:
        row["std_us"] = round(std_us, 1)
    if repeats is not None:
        row["repeats"] = repeats
    row.update(extra)  # bench-specific fields (e.g. wasted_frac)
    # memory stamp: RSS high-water + live device buffers at emit time, so
    # every BENCH_*.json row carries the footprint alongside the timing
    row.setdefault("peak_rss_bytes", peak_rss_bytes())
    if "device_bytes" not in row:
        # only read when the row lacks it: reading starts a JAX backend, which
        # a parent of per-point JAX children (bench_streaming) must not hold
        row["device_bytes"] = device_buffer_bytes()
    _rows.append(row)
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def rows() -> List[str]:
    return [f"{r['name']},{r['us_per_call']:.1f},{r['derived']}" for r in _rows]


def mark() -> int:
    """Index into the row log; pass to ``dump_json`` to scope one module."""
    return len(_rows)


def dump_json(filename: str, start: int = 0) -> Path:
    """Write rows emitted since ``start`` to ``OUT_DIR/filename``."""
    path = OUT_DIR / filename
    payload = {"quick": QUICK, "meta": run_meta(), "results": _rows[start:]}
    path.write_text(json.dumps(payload, indent=2) + "\n")
    return path


def span_stats(durations_s: List[float]) -> Dict[str, float]:
    """best/mean/std (µs) over a list of span durations (seconds)."""
    us = [d * 1e6 for d in durations_s]
    return {
        "best_us": min(us),
        "mean_us": statistics.fmean(us),
        "std_us": statistics.pstdev(us) if len(us) > 1 else 0.0,
        "repeats": len(us),
    }


def timeit_stats(fn: Callable, *args, repeats: int = 3, **kw) -> Dict[str, float]:
    """Time ``fn(*args, **kw)`` via tracer spans: one span per repeat, device
    work forced complete inside each span.  Returns best/mean/std in µs."""
    import jax

    tracer = Tracer()

    def once():
        # a failed wait must raise: swallowing it would time the enqueue only
        jax.block_until_ready(fn(*args, **kw))

    once()  # warmup / compile
    for _ in range(repeats):
        with tracer.span("timeit"):
            once()
    return span_stats(tracer.durations("timeit"))


def timeit(fn: Callable, *args, repeats: int = 3, **kw) -> float:
    return timeit_stats(fn, *args, repeats=repeats, **kw)["mean_us"]
