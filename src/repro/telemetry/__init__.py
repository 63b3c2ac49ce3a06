"""Federation telemetry: span tracing, round metrics, analytic-cost hooks.

One :class:`Telemetry` object follows a simulation run end-to-end:

* ``tel.span("cohort_epoch", round=r, ...)`` — wall-clock spans (nested,
  thread-safe) on every hot path of both engines.
* ``tel.sim_span("upload", t0, t1, client=i, edge=j)`` — the async engine's
  schedule on a *simulated-time* track (``EventQueue.now`` seconds).
* ``tel.upload(x, dtype)`` — an explicit host→device copy whose bytes land
  in the ``h2d_bytes`` counter and on the innermost open span.
* ``with tel.watch_gc():`` — one ``gc`` span per Python garbage collection
  while the block runs (the sync engines wrap ``run()`` in it).
* ``Telemetry(profile=True)`` — every wall span is also a
  ``jax.profiler.TraceAnnotation`` carrying its ``sid``, so a running
  profiler trace holds the spans on the device trace's clock.
* ``tel.metrics`` — counters/gauges/histograms (cohort occupancy, padding
  waste, staleness distribution, eval accuracy, ...).
* ``tel.jit_cost(key, fn, *args)`` — analytic FLOPs / bytes-moved for a
  jitted program, from :mod:`repro.distributed.hlo_stats` over the lowered
  (pre-compile) HLO; cached per (key, arg-shapes) so it runs once per
  program, mirroring first-compile.
* ``tel.on_round(...)`` — one record per cloud round (accuracy, wall/sim
  seconds, comm-bit deltas, span aggregates), exported as JSONL plus an
  end-of-run summary table.

Disabled telemetry is the :data:`NULL_TELEMETRY` singleton — every call
resolves to a shared no-op object, so instrumented code pays one attribute
lookup and nothing else.  Engine trajectories are bit-identical with
telemetry on or off (pinned by ``tests/test_telemetry.py``).

User-facing knob: ``Scenario.simulate(telemetry=...)`` accepts ``True``
(in-memory), a directory path (artifacts written on flush), or a
:class:`Telemetry` instance.
"""
from __future__ import annotations

import contextlib
import gc
import json
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.telemetry.metrics import (  # noqa: F401  (re-exports)
    MetricsRegistry,
    NULL_METRICS,
    jit_cache_sizes,
    register_jit,
    registered_jits,
)
from repro.telemetry.report import CommDelta, summary_table, write_rounds_jsonl
from repro.telemetry.trace import NULL_SPAN, NULL_TRACER, Tracer


def _arg_key(a):
    """Hashable cache key for one ``jit_cost`` argument: arrays collapse to
    (shape, dtype) — the same abstraction jit itself caches on."""
    if hasattr(a, "shape") and hasattr(a, "dtype"):
        return ("arr", tuple(a.shape), str(a.dtype))
    if isinstance(a, (tuple, list)):
        return ("seq", tuple(_arg_key(x) for x in a))
    if isinstance(a, dict):
        return ("map", tuple(sorted((str(k), _arg_key(v)) for k, v in a.items())))
    try:
        hash(a)
        return a
    except TypeError:
        return ("type", type(a).__name__)


class Telemetry:
    """Live telemetry sink: tracer + metrics + per-round records."""

    enabled = True

    def __init__(self, out_dir=None, profile: bool = False) -> None:
        self.tracer = Tracer(profile=profile)
        self.metrics = MetricsRegistry()
        self.rounds: List[dict] = []
        self.out_dir: Optional[Path] = Path(out_dir) if out_dir else None
        self._cost_cache: Dict[tuple, dict] = {}
        self._span_mark = 0
        self.gc_seconds = 0.0  # total of the gc spans recorded so far
        self._gc_span = None

    # -- tracing -------------------------------------------------------
    def span(self, name: str, **attrs):
        return self.tracer.span(name, **attrs)

    def sim_span(self, name: str, t0: float, t1: float, **attrs) -> None:
        self.tracer.sim_span(name, t0, t1, **attrs)

    # -- host->device traffic -------------------------------------------
    def upload(self, x, dtype=None):
        """``x`` copied to the default device as ``dtype``, explicitly (so
        ``jax.transfer_guard_host_to_device("disallow")`` lets it pass).
        The device array's bytes add to the ``h2d_bytes`` counter and to
        the ``h2d_bytes`` attribute of the innermost open span."""
        arr = jax.device_put(np.asarray(x, dtype))
        self.metrics.inc("h2d_bytes", arr.nbytes)
        self.tracer.add_to_open("h2d_bytes", arr.nbytes)
        return arr

    # -- garbage-collector pauses --------------------------------------
    @contextlib.contextmanager
    def watch_gc(self):
        """Record a ``gc`` span (attr ``generation``) per collection while
        the block runs; ``gc_seconds`` accumulates their durations.  The
        callback is removed on exit, also on error."""
        gc.callbacks.append(self._on_gc)
        try:
            yield
        finally:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        # "start" and "stop" come in pairs, on the collecting thread
        if phase == "start":
            self._gc_span = self.tracer.span("gc", generation=info["generation"])
            self._gc_span.__enter__()
        else:
            self._gc_span.__exit__(None, None, None)
            self.gc_seconds += self._gc_span.duration

    # -- analytic cost -------------------------------------------------
    def jit_cost(self, key: str, fn, *args, **kwargs) -> Optional[dict]:
        """FLOPs/bytes_moved of ``fn(*args, **kwargs)`` from its lowered HLO.

        ``fn`` may be a jitted function (its own ``lower``) or any traceable
        callable (wrapped in a throwaway ``jax.jit`` for lowering only — no
        compilation or execution happens here).  Returns ``None`` when the
        program cannot be lowered/analyzed; results are cached on
        (key, arg shapes/dtypes) so repeated calls are dict lookups.
        """
        ck = (key, tuple(_arg_key(a) for a in args),
              tuple(sorted((k, _arg_key(v)) for k, v in kwargs.items())))
        hit = self._cost_cache.get(ck)
        if hit is None:
            hit = self._analyze(key, fn, args, kwargs)
            self._cost_cache[ck] = hit
        return hit or None

    def _analyze(self, key: str, fn, args, kwargs) -> dict:
        try:
            import jax

            from repro.distributed import hlo_stats

            lowerable = fn if hasattr(fn, "lower") else jax.jit(fn)
            hlo = lowerable.lower(*args, **kwargs).as_text(dialect="hlo")
            st = hlo_stats.analyze(hlo)
            cost = {"flops": float(st.flops),
                    "bytes_moved": float(st.bytes_moved)}
        except Exception:
            return {}
        self.metrics.set_gauge(f"analytic_flops/{key}", cost["flops"])
        self.metrics.set_gauge(f"analytic_bytes/{key}", cost["bytes_moved"])
        return cost

    # -- round reporting ----------------------------------------------
    def _span_aggregate(self) -> dict:
        """Count/total-seconds per span name since the previous round."""
        with self.tracer._lock:
            fresh = self.tracer.spans[self._span_mark:]
            self._span_mark = len(self.tracer.spans)
        agg: Dict[str, dict] = {}
        for s in fresh:
            if s.track != "wall":
                continue
            a = agg.setdefault(s.name, {"count": 0, "total_s": 0.0})
            a["count"] += 1
            a["total_s"] += s.duration
        return agg

    def on_round(self, **fields) -> dict:
        rec = dict(fields)
        rec["spans"] = self._span_aggregate()
        rec["jit_cache_sizes"] = jit_cache_sizes()
        self.rounds.append(rec)
        return rec

    # -- finalisation --------------------------------------------------
    def summary(self) -> str:
        return summary_table(self.rounds)

    def flush(self, out_dir=None) -> Dict[str, Path]:
        """Write trace.json / trace.jsonl / rounds.jsonl / metrics.json /
        summary.txt under ``out_dir`` (or the constructor's).  Returns the
        written paths; empty dict when no output directory is configured."""
        out = Path(out_dir) if out_dir else self.out_dir
        if out is None:
            return {}
        out.mkdir(parents=True, exist_ok=True)
        paths = {
            "trace": self.tracer.write_chrome_trace(out / "trace.json"),
            "spans": self.tracer.write_jsonl(out / "trace.jsonl"),
            "rounds": write_rounds_jsonl(out / "rounds.jsonl", self.rounds),
        }
        m = out / "metrics.json"
        m.write_text(json.dumps(self.metrics.snapshot(), indent=2),
                     encoding="utf-8")
        paths["metrics"] = m
        s = out / "summary.txt"
        s.write_text(self.summary() + "\n", encoding="utf-8")
        paths["summary"] = s
        return paths


class _NullTelemetry:
    """Zero-overhead disabled telemetry (singleton)."""

    enabled = False
    tracer = NULL_TRACER
    metrics = NULL_METRICS
    rounds: List[dict] = []
    out_dir = None
    gc_seconds = 0.0

    def span(self, name: str, **attrs):
        return NULL_SPAN

    def sim_span(self, name: str, t0: float, t1: float, **attrs) -> None:
        pass

    def upload(self, x, dtype=None):
        return jax.device_put(np.asarray(x, dtype))

    def watch_gc(self):
        return NULL_SPAN

    def jit_cost(self, key: str, fn, *args, **kwargs) -> None:
        return None

    def on_round(self, **fields) -> dict:
        return {}

    def summary(self) -> str:
        return "(telemetry disabled)"

    def flush(self, out_dir=None) -> Dict[str, Path]:
        return {}


NULL_TELEMETRY = _NullTelemetry()


def coerce_telemetry(t) -> Optional[Telemetry]:
    """Normalise the ``simulate(telemetry=...)`` knob.

    ``None``/``False`` → ``None`` (disabled); ``True`` → in-memory
    :class:`Telemetry`; a str/Path → :class:`Telemetry` flushing artifacts
    there; a :class:`Telemetry` (or the null singleton) passes through.
    """
    if t is None or t is False:
        return None
    if isinstance(t, Telemetry):
        return t
    if t is NULL_TELEMETRY:
        return None
    if t is True:
        return Telemetry()
    if isinstance(t, (str, Path)):
        return Telemetry(out_dir=t)
    raise TypeError(f"telemetry must be None/bool/path/Telemetry, got {type(t)!r}")


__all__ = [
    "Telemetry",
    "NULL_TELEMETRY",
    "coerce_telemetry",
    "Tracer",
    "MetricsRegistry",
    "CommDelta",
    "register_jit",
    "jit_cache_sizes",
    "registered_jits",
    "summary_table",
    "write_rounds_jsonl",
]
