"""Model FLOP utilisation of local training, in percent: three forward
passes of the CNN per trained sample (``flops.cnn_train_flops``) times the
samples the window trained (each ``cohort_epoch`` span's clients x epochs x
steps x batch: real clients only, the rows that pad a cohort do not count,
nor does Adam), over the window times chips times the chip's bf16 peak."""

from flops import cnn_train_flops


def read(run):
    spans = run.spans_named("cohort_epoch")
    if not spans:
        return None
    samples = sum(a["clients"] * a["epochs"] * a["steps"] * a["batch"] for _, _, _, a in spans)
    flops = samples * cnn_train_flops(run.config["widths"])
    return 100.0 * flops / (run.window_s * run.chips * run.peaks["bf16_flops"])
