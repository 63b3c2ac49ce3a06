"""The compiled test-set evaluation (``federated.simulation.Evaluator``):
the same number as the eager per-batch loop it replaced, one upload of the
test set per evaluator, no host-to-device copy per call, and one trace per
test shape."""
import jax
import numpy as np
import pytest

from repro.data.synthetic_health import Dataset
from repro.federated.programs import CNNProgram, LMProgram, MLPProgram, tiny_lm_config
from repro.federated.simulation import Evaluator, _eval_batches, evaluate
from repro.models.cnn1d import CNNConfig
from repro.telemetry import Telemetry

BATCH = 16
PROGRAMS = {
    "cnn": CNNProgram(CNNConfig(in_channels=1, n_classes=3, seq_len=32, c1=4, c2=4, hidden=8)),
    "mlp": MLPProgram(feat=(32, 1), classes=3, hidden=8),
    "lm": LMProgram(
        cfg=tiny_lm_config(vocab_size=32, seq_len=8, d_model=8, n_layers=2, n_heads=2, d_ff=16),
        seq_len=8,
        n_topics=3,
    ),
}


def _test_set(program, n: int, seed: int = 0) -> Dataset:
    rng = np.random.default_rng(seed)
    if isinstance(program, LMProgram):
        x = rng.integers(0, program.cfg.vocab_size, (n, program.seq_len)).astype(np.int32)
    else:
        x = rng.standard_normal((n, 32, 1)).astype(np.float32)
    return Dataset(x, rng.integers(0, 3, n).astype(np.int32), 3)


def _params(program, seed: int = 1):
    return program.init(jax.random.PRNGKey(seed))


def _eager(params, program, test: Dataset, batch: int):
    """The per-batch loop the compiled evaluation replaced: per-batch
    metrics and their size-weighted mean."""
    accs, ns, per_batch = [], [], []
    for i in range(0, len(test), batch):
        x = jax.device_put(np.asarray(test.x[i : i + batch]))
        y = jax.device_put(np.asarray(test.y[i : i + batch]))
        metric = float(program.metric(params, x, y))
        per_batch.append(metric)
        accs.append(metric * len(y))
        ns.append(len(y))
    return float(np.sum(accs) / np.sum(ns)), per_batch


@pytest.mark.parametrize("n", [2 * BATCH, 2 * BATCH + 5, BATCH - 3],
                         ids=["divisible", "remainder", "under-one-batch"])
@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_matches_the_eager_per_batch_loop(name, n):
    program = PROGRAMS[name]
    test = _test_set(program, n)
    params = _params(program)
    want, want_batches = _eager(params, program, test, BATCH)
    ev = Evaluator(program, test, batch=BATCH)
    got_batches = np.asarray(_eval_batches(params, ev.x, ev.y, program, BATCH))
    assert got_batches.shape == (len(want_batches),)
    assert ev._sizes.tolist() == [len(test.y[i : i + BATCH]) for i in range(0, n, BATCH)]
    # each batch's metric is a count over its rows (or tokens): one flipped
    # prediction moves it by at most 1/batch, the mean by at most 1/N
    np.testing.assert_allclose(got_batches, want_batches, atol=1.0 / BATCH + 1e-7)
    got = ev(params)
    assert abs(got - want) <= 1.0 / n
    assert got == evaluate(params, program, test, batch=BATCH)


def test_default_batch_on_the_paper_test_size():
    """1,500 rows in batches of 512: two full batches and 476 left over."""
    program = PROGRAMS["mlp"]
    test = _test_set(program, 1_500)
    params = _params(program)
    ev = Evaluator(program, test)
    assert ev._sizes.tolist() == [512, 512, 476]
    want, _ = _eager(params, program, test, 512)
    assert abs(ev(params) - want) <= 1.0 / 1_500


def test_uploads_the_test_set_once():
    program = PROGRAMS["cnn"]
    test = _test_set(program, 2 * BATCH + 5)
    tel = Telemetry()
    ev = Evaluator(program, test, batch=BATCH, telemetry=tel)
    for seed in (1, 2, 3):
        with tel.span("eval"):
            ev(_params(program, seed))
    counters = tel.metrics.snapshot()["counters"]
    assert counters["eval_test_uploads"] == 1
    assert counters["h2d_bytes"] == test.x.nbytes + test.y.nbytes
    spans = tel.tracer.spans
    assert [s.attrs for s in spans if s.name == "fetch"] == [{"what": "eval"}] * 3
    assert all("h2d_bytes" not in s.attrs for s in spans)


def test_second_call_copies_nothing_from_the_host():
    program = PROGRAMS["lm"]
    test = _test_set(program, 2 * BATCH + 5)
    params = _params(program)
    ev = Evaluator(program, test, batch=BATCH)
    first = ev(params)
    with jax.transfer_guard_host_to_device("disallow"):
        assert ev(params) == first


class _CountingCNN(CNNProgram):
    """A CNN program that counts how often its metric is traced."""

    traces = []

    def metric(self, params, x, y):
        self.traces.append(x.shape)
        return super().metric(params, x, y)


def test_traces_once_per_test_shape():
    program = _CountingCNN(CNNConfig(in_channels=1, n_classes=3, seq_len=32, c1=4, c2=4, hidden=8))
    params = _params(program)
    test = _test_set(program, 2 * BATCH + 5)
    ev = Evaluator(program, test, batch=BATCH)
    ev(params)
    # the full batches trace once (the lax.map body), the remainder once
    assert program.traces == [(BATCH, 32, 1), (5, 32, 1)]
    ev(_params(program, 2))
    Evaluator(program, _test_set(program, 2 * BATCH + 5, seed=1), batch=BATCH)(params)
    assert len(program.traces) == 2
    Evaluator(program, _test_set(program, 3 * BATCH), batch=BATCH)(params)
    assert program.traces[2:] == [(BATCH, 32, 1)]


def test_rejects_an_empty_test_set():
    with pytest.raises(ValueError, match="empty"):
        Evaluator(PROGRAMS["mlp"], Dataset(np.zeros((0, 32, 1), np.float32),
                                           np.zeros(0, np.int32), 3))
