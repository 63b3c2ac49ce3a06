"""From a profiler trace of the window to per-layer numbers.

``AnnotatingTelemetry`` is the program's ``Telemetry`` with every span also
opened as a ``jax.profiler.TraceAnnotation``, which puts the program's host
spans on the device trace's clock without any change to the program.

``TracedRun.load`` reads the window's ``.xplane.pb`` with
``jax.profiler.ProfileData`` and keeps, on one clock in nanoseconds:

* ``spans``: the program's host spans (name, start, end, attributes);
* ``ops`` / ``modules``: per chip, the device's op and program events.

The traced window runs from the first ``cloud_round`` span's start to the
last one's end.  The readers in ``metrics/`` take their numbers from here.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

from repro.telemetry import Telemetry

SPAN_NAMES = ("cloud_round", "assignment", "cohort_epoch", "edge_aggregate",
              "cloud_reduce", "eval")


class _AnnotatedSpan:
    def __init__(self, name: str, span):
        import jax

        self._ann = jax.profiler.TraceAnnotation(name)
        self._span = span

    def __enter__(self):
        self._ann.__enter__()
        return self._span.__enter__()

    def __exit__(self, *exc):
        try:
            return self._span.__exit__(*exc)
        finally:
            self._ann.__exit__(*exc)


class AnnotatingTelemetry(Telemetry):
    """Telemetry whose spans also appear in the profiler's trace."""

    def span(self, name: str, **attrs):
        return _AnnotatedSpan(name, super().span(name, **attrs))


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered(intervals: List[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in union(intervals))


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


class TracedRun:
    """One traced window, on the trace's clock (ns)."""

    def __init__(self, spans, ops, modules, chips: int, config: dict, peaks: dict, rounds: int):
        self.spans = spans  # [(name, t0, t1, attrs)]
        self.ops = ops  # {chip: [(name, t0, t1)]}
        self.modules = modules  # {chip: [(name, t0, t1)]}
        self.chips = chips
        self.config = config
        self.peaks = peaks
        self.rounds = rounds
        rounds_spans = [s for s in spans if s[0] == "cloud_round"]
        self.t0 = min(s[1] for s in rounds_spans)
        self.t1 = max(s[2] for s in rounds_spans)

    # -- the reading -------------------------------------------------------
    @classmethod
    def load(cls, trace_dir: str, spans, devices, config: dict, peaks: dict) -> "TracedRun":
        from jax.profiler import ProfileData

        files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise RuntimeError(f"no .xplane.pb under {trace_dir}")
        return cls.from_profile(ProfileData.from_file(files[-1]), spans, len(devices), config,
                                peaks)

    @classmethod
    def from_profile(cls, pd, host_spans, chips: int, config: dict, peaks: dict) -> "TracedRun":
        annotations: Dict[str, List[Tuple[int, int]]] = {n: [] for n in SPAN_NAMES}
        ops: Dict[int, list] = {}
        modules: Dict[int, list] = {}
        for plane in pd.planes:
            if plane.name.startswith("/host"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name in annotations:
                            t0 = int(ev.start_ns)
                            annotations[ev.name].append((t0, t0 + int(ev.duration_ns)))
            elif plane.name.startswith("/device:TPU:"):
                tail = plane.name[len("/device:TPU:"):]
                if not tail.isdigit() or int(tail) >= chips:
                    continue
                chip = int(tail)
                for line in plane.lines:
                    target = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                    if target is not None:
                        rows = target.setdefault(chip, [])
                        for ev in line.events:
                            t0 = int(ev.start_ns)
                            rows.append((ev.name, t0, t0 + int(ev.duration_ns)))
        for chip in range(chips):
            for line, found in (("XLA Ops", ops), ("XLA Modules", modules)):
                if chip not in found:
                    raise RuntimeError(f"the trace has no {line!r} line for TPU {chip}")
        spans = _align(host_spans, annotations)
        rounds = len(annotations["cloud_round"])
        return cls(spans, ops, modules, chips, config, peaks, rounds)

    # -- derived -----------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def device_intervals(self, chip: int) -> List[Tuple[int, int]]:
        return clip([(a, b) for _, a, b in self.ops[chip]], self.t0, self.t1)

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran on the device, per chip, averaged."""
        return sum(covered(self.device_intervals(c)) for c in range(self.chips)) / self.chips / 1e9

    def module_seconds(self, marks, chip: Optional[int] = None) -> Tuple[float, int]:
        """Total device seconds and count of program executions whose
        name contains one of ``marks`` (inside the window), per chip averaged."""
        chips = range(self.chips) if chip is None else [chip]
        total, count = 0, 0
        for c in chips:
            for name, a, b in self.modules.get(c, []):
                if a >= self.t0 and b <= self.t1 and any(m in name for m in marks):
                    total += b - a
                    count += 1
        return total / len(chips) / 1e9, count // len(chips)

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s[0] == name and s[1] >= self.t0 and s[2] <= self.t1]

    def host_timeline(self) -> List[Tuple[int, int, str]]:
        """The window cut into pieces, each labelled by the innermost host
        span open over it ("none" where no span is open)."""
        cuts = sorted({self.t0, self.t1} | {t for _, a, b, _ in self.spans for t in (a, b)
                                            if self.t0 < t < self.t1})
        spans = sorted(self.spans, key=lambda s: s[1])
        out, i, open_ = [], 0, []
        for a, b in zip(cuts, cuts[1:]):
            while i < len(spans) and spans[i][1] <= a:
                open_.append(spans[i])
                i += 1
            open_ = [s for s in open_ if s[2] > a]
            inner = min(open_, key=lambda s: s[2] - s[1], default=None)
            out.append((a, b, inner[0] if inner else "none"))
        return out

    def breakdown(self) -> dict:
        """Top device ops by time (per chip, averaged), and chip 0's idle
        time by the host span that was open during it."""
        per_op: Dict[str, float] = {}
        for c in range(self.chips):
            for n, a, b in self.ops[c]:
                a, b = max(a, self.t0), min(b, self.t1)
                if b > a:
                    per_op[n] = per_op.get(n, 0.0) + (b - a) / 1e9 / self.chips
        busy = union(self.device_intervals(0))
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        idle: Dict[str, float] = {}
        pieces = self.host_timeline()
        j = 0
        for a, b in gaps:  # both lists sorted: one merge pass
            while j < len(pieces) and pieces[j][1] <= a:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < b:
                lo, hi = max(a, pieces[k][0]), min(b, pieces[k][1])
                if hi > lo:
                    idle[pieces[k][2]] = idle.get(pieces[k][2], 0.0) + (hi - lo) / 1e9
                k += 1
        top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(per_op), "idle_gaps": top(idle)}


def _align(host_spans, annotations) -> list:
    """The telemetry's spans of the window (with their attributes), moved
    onto the trace's clock: the k-th span of a name is the k-th annotation
    of that name."""
    by_name: Dict[str, list] = {}
    for s in host_spans:
        if s.track == "wall" and s.name in annotations:
            by_name.setdefault(s.name, []).append(s)
    out = []
    for name, spans in by_name.items():
        anns = sorted(annotations[name])
        if len(anns) != len(spans):
            raise RuntimeError(f"{len(spans)} {name} spans but {len(anns)} annotations in the trace")
        for s, (a, b) in zip(sorted(spans, key=lambda s: s.t0), anns):
            out.append((name, a, b, dict(s.attrs)))
    return out
