"""Milliseconds per cloud round that the paged shard store spends
synthesising missed shards on the host: the ``page_synth_s`` attributes of
the window's ``cohort_epoch`` spans (the wall time of the shard writer
inside each epoch's ``page_in`` span), summed, per round.  The rest of
``page_in_ms`` is the miss batch's padding, upload and scatter."""


def read(run):
    counted = [s[3]["page_synth_s"] for s in run.spans_named("cohort_epoch")
               if "page_synth_s" in s[3]]
    if not counted or not run.rounds:
        return None
    return 1e3 * sum(counted) / run.rounds
