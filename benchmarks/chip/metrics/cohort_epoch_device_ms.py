"""Device milliseconds per cloud round (per chip) in the programs of the
cohort epoch: the jitted ``_cohort_epoch_flat`` / ``_cohort_epoch``."""

MARKS = ("cohort_epoch",)


def read(run):
    seconds, count = run.module_seconds(MARKS)
    if not count or not run.rounds:
        return None
    return 1e3 * seconds / run.rounds
