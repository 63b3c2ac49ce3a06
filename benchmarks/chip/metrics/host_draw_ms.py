"""Host milliseconds per cloud round in the program's ``assignment`` span:
the participation or cohort draw and every client's batch-index draws."""


def read(run):
    spans = run.spans_named("assignment")
    if not spans or not run.rounds:
        return None
    return sum(b - a for _, a, b, _ in spans) / run.rounds / 1e6
