"""Least time the edge FedAvg needs over the device time of the program that
runs it (``segment_agg_keep``, the segment kernel inside), in percent.

Least time: N*D adds at the bf16 peak, or reading the (N, D) rows once and
reading and writing the (E, D) edge models at the HBM peak, whichever is
longer (``flops.segment_mean_least``).  N and E come from the program's
``edge_aggregate`` span, D from the configuration."""

from flops import segment_mean_least

MARKS = ("segment_agg_keep",)


def read(run):
    seconds, count = run.module_seconds(MARKS)
    spans = run.spans_named("edge_aggregate")
    if not count or not spans:
        return None
    least = sum(segment_mean_least(s[3]["clients"], s[3]["edges"], run.config["n_params"],
                                   run.peaks)["seconds"] for s in spans)
    # one program execution per edge_aggregate span, on each chip
    return 100.0 * least * count / len(spans) / seconds
