"""Megabytes (10^6 bytes) per cloud round that the paged shard store
uploads: the ``page_in_bytes`` attributes of the window's ``cohort_epoch``
spans (each miss batch padded to a power of two rows of ``n_max`` samples,
plus its slot ids), summed, per round."""


def read(run):
    counted = [s[3]["page_in_bytes"] for s in run.spans_named("cohort_epoch")
               if "page_in_bytes" in s[3]]
    if not counted or not run.rounds:
        return None
    return sum(counted) / run.rounds / 1e6
