"""Milliseconds per cloud round of Python garbage-collector pauses: the
``gc_s`` attribute of the window's ``cloud_round`` spans (the total of the
round's ``gc`` spans)."""


def read(run):
    counted = [s[3]["gc_s"] for s in run.spans_named("cloud_round") if "gc_s" in s[3]]
    if not counted or not run.rounds:
        return None
    return 1e3 * sum(counted) / run.rounds
