"""Faults planted in the program's timed path, in every engine module that
binds the patched function, so a cell on any engine meets them."""
import importlib

EPOCH_ENGINES = ("repro.engine.sync_sim", "repro.engine.stream_sim")


def frozen_edges(monkeypatch):
    """Every edge round returns the edge models it was given."""
    monkeypatch.setattr("repro.engine.sync_sim._segment_agg_keep",
                        lambda upd, seg, w, has, prev, n, backend: prev)
    monkeypatch.setattr("repro.engine.stream_sim._edge_agg_finish",
                        lambda num, den, has, prev: prev)


def half_batch(monkeypatch):
    """Every local step takes its mean loss over half of its batch."""
    for name in EPOCH_ENGINES:
        engine = importlib.import_module(name)
        orig = engine._cohort_epoch_flat

        def half(flat, xb, yb, *args, orig=orig):
            h = xb.shape[2] // 2
            return orig(flat, xb[:, :, :h], yb[:, :, :h], *args)

        monkeypatch.setattr(engine, "_cohort_epoch_flat", half)
