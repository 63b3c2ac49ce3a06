"""Milliseconds per cloud round that the paged shard store spends paging
clients in: the ``page_in_s`` attributes of the window's ``cohort_epoch``
spans (the ``page_in`` spans that each epoch's ``PagedShardStore.ensure``
opened: host-side shard synthesis and the slab's upload and scatter),
summed, per round."""


def read(run):
    counted = [s[3]["page_in_s"] for s in run.spans_named("cohort_epoch") if "page_in_s" in s[3]]
    if not counted or not run.rounds:
        return None
    return 1e3 * sum(counted) / run.rounds
