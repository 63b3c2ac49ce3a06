"""Milliseconds per cloud round, per chip averaged, in which an ``eval``
span is open and no "XLA Ops" event runs on the device: the share of the
round's evaluation that leaves the chip idle."""

from tracing import clip, covered, union


def read(run):
    evals = union([(a, b) for _, a, b, _ in run.spans_named("eval")])
    if not evals or not run.rounds:
        return None
    idle = 0
    for chip in range(run.chips):
        ops = run.device_intervals(chip)
        idle += sum(b - a - covered(clip(ops, a, b)) for a, b in evals)
    return idle / run.chips / run.rounds / 1e6
