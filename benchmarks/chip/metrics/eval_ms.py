"""Host milliseconds per cloud round in the program's ``eval`` spans: the
test-set evaluation that ends every round (the dispatch of one compiled
program over the test set held on the device, and the one blocking read of
its metrics)."""


def read(run):
    spans = run.spans_named("eval")
    if not spans or not run.rounds:
        return None
    return sum(b - a for _, a, b, _ in spans) / run.rounds / 1e6
