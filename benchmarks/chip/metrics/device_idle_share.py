"""Share of the traced window, in percent, in which no operation ran on the
device (per chip, averaged): 1 - union of op intervals / window."""


def read(run):
    if run.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.busy_s / run.window_s)
