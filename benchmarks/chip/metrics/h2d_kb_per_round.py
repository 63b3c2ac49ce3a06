"""Host-to-device kilobytes (1,000 bytes) per cloud round that the round
loop uploads: the ``h2d_bytes`` attributes of the window's spans (batch
indices, starts and aggregation weights), each upload counted once, on the
innermost span open at it.  The ``eval`` span adds none: the test set is
uploaded once, when the engine is built, before the window."""

SPANS = ("cloud_round", "assignment", "cohort_epoch", "edge_aggregate", "cloud_reduce", "eval")


def read(run):
    counted = [s[3]["h2d_bytes"] for name in SPANS for s in run.spans_named(name)
               if "h2d_bytes" in s[3]]
    if not counted or not run.rounds:
        return None
    return sum(counted) / run.rounds / 1e3
