"""The eval's accumulation as one native pass a chunk
(runtime/csrc/shifu_evalacc.cc through `StreamingMetrics.update`), held to
the numpy statements it replaces: the same bins and counts bit for bit, the
same sums to 1e-12, over the dtypes a job's chunks arrive in, the edge
scores, and chunk lengths on both sides of the pairwise sum's blocks."""

import logging
import shutil

import ml_dtypes
import numpy as np
import pytest

from shifu_tpu import obs
from shifu_tpu.obs.sketch import ScoreSketch
from shifu_tpu.ops import metrics as metrics_mod
from shifu_tpu.ops.metrics import StreamingMetrics
from shifu_tpu.train import loop as loop_mod

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None, reason="no g++ in environment")

_LENGTHS = [1, 7, 128, 129, 8192, 65536]
_WEIGHTS = ["none", "ones", "with_zeros", "uniform"]


def _chunk(n, score_dtype, label_dtype, weights, seed=40):
    rng = np.random.default_rng(seed + n)
    labels = rng.random(n) < 0.35
    logits = rng.normal(0.4 * labels - 0.2, 1.5).astype(np.float32)
    s = (1.0 / (1.0 + np.exp(-logits))).astype(np.float32)
    s[:4] = [0.0, 1.0, -0.25, 1.5][:n]  # at both ends, below 0, above 1
    s = s.astype(score_dtype)
    t = labels.astype(label_dtype)
    w = {"none": None,
         "ones": np.ones(n, np.float32),
         "with_zeros": rng.uniform(0.5, 2.0, n).astype(np.float32),
         "uniform": rng.uniform(0.5, 2.0, n).astype(np.float32)}[weights]
    if weights == "with_zeros":
        w[1::3] = 0.0
    return s, t, w


def _numpy_only(monkeypatch):
    monkeypatch.setattr(metrics_mod, "_native", lambda: None)


def _reduce(chunks, numpy_only=False):
    """(StreamingMetrics, ScoreSketch, masks) over `chunks`."""
    with pytest.MonkeyPatch.context() as mp:
        if numpy_only:
            _numpy_only(mp)
        sm, sk = StreamingMetrics(), ScoreSketch()
        masks = [sm.update(s, t, w, sk) for s, t, w in chunks]
    return sm, sk, masks


def _assert_same(native, numpy_):
    (sm, sk, masks), (sm0, sk0, masks0) = native, numpy_
    for a, b in zip(sm.state_arrays(), sm0.state_arrays()):
        np.testing.assert_array_equal(a, b)
    assert (sm.rows, sm.nonzero_rows) == (sm0.rows, sm0.nonzero_rows)
    for m, m0 in zip(masks, masks0):
        assert m.dtype == np.bool_
        np.testing.assert_array_equal(m, m0)
    np.testing.assert_allclose(sm.weighted_error(), sm0.weighted_error(),
                               rtol=1e-12, atol=0)
    np.testing.assert_array_equal(sk.hist, sk0.hist)
    assert sk.n == sk0.n
    np.testing.assert_allclose([sk.sum, sk.sumsq], [sk0.sum, sk0.sumsq],
                               rtol=1e-12, atol=0)


@pytest.mark.parametrize("n", _LENGTHS)
@pytest.mark.parametrize("weights", _WEIGHTS)
@pytest.mark.parametrize("label_dtype", [np.float32, np.uint8],
                         ids=["f32_labels", "u8_labels"])
@pytest.mark.parametrize("score_dtype", [np.float32, ml_dtypes.bfloat16],
                         ids=["f32_scores", "bf16_scores"])
def test_native_pass_equals_the_numpy_statements(score_dtype, label_dtype,
                                                 weights, n):
    chunks = [_chunk(n, score_dtype, label_dtype, weights),
              _chunk(n, score_dtype, label_dtype, weights, seed=41)]
    native = _reduce(chunks)
    assert native[0].native_rows == native[0].nonzero_rows > 0
    numpy_ = _reduce(chunks, numpy_only=True)
    assert numpy_[0].native_rows == 0
    _assert_same(native, numpy_)


def test_every_weight_zero_and_empty_chunks():
    """Rows that count nowhere: the error's denominator, the bins and the
    sketch stay as they were, on both paths."""
    s, t, _ = _chunk(300, np.float32, np.float32, "none")
    chunks = [(s, t, np.zeros(300, np.float32)), (s[:0], t[:0], None),
              (s[:0], t[:0], np.zeros(0, np.float32))]
    native, numpy_ = _reduce(chunks), _reduce(chunks, numpy_only=True)
    _assert_same(native, numpy_)
    sm, sk, _ = native
    assert (sm.rows, sm.nonzero_rows, sk.n) == (300, 0, 0)
    assert not any(a.any() for a in sm.state_arrays()) and not sk.hist.any()


@pytest.mark.parametrize("case", ["float64_scores", "bins_not_a_power_of_two",
                                  "float64_weights"])
def test_chunks_the_pass_does_not_take_go_the_numpy_way(case, monkeypatch):
    s, t, w = _chunk(500, np.float32, np.float32, "with_zeros")
    bins = 1000 if case == "bins_not_a_power_of_two" else 1 << 20
    if case == "float64_scores":
        s = s.astype(np.float64)
    if case == "float64_weights":
        w = w.astype(np.float64)
    sm, sm0 = StreamingMetrics(bins), StreamingMetrics(bins)
    sm.update(s, t, w)
    assert sm.native_rows == 0
    _numpy_only(monkeypatch)
    sm0.update(s, t, w)
    for a, b in zip(sm.state_arrays(), sm0.state_arrays()):
        np.testing.assert_array_equal(a, b)
    assert sm.weighted_error() == sm0.weighted_error()


def _resident_chunks(n_rows=20_000, bs=4096, seed=40):
    """(scores, labels, weights) as `_fetch_resident_eval` yields them:
    rows of a (blocks, bs) score array cut to the block's labels, and views
    of the dataset's (N, 1) label and weight columns."""
    rng = np.random.default_rng(seed)
    target = (rng.random((n_rows, 1)) < 0.3).astype(np.float32)
    weight = np.ones((n_rows, 1), np.float32)
    weight[::97] = 0.0
    nb = -(-n_rows // bs)
    scores = rng.random((nb, bs)).astype(np.float32)
    tgt, wgt = target[:, 0], weight[:, 0]
    for i, lo in enumerate(range(0, n_rows, bs)):
        t, w = tgt[lo:lo + bs], wgt[lo:lo + bs]
        yield scores[i][:t.shape[0]], t, w


def test_accumulate_streaming_equals_the_numpy_path(monkeypatch):
    rows = obs.counter("eval_rows_total", "")
    native = obs.counter("eval_rows_native_total", "")
    before = rows.total(), native.total()
    sk = ScoreSketch()
    got = loop_mod._accumulate_streaming(_resident_chunks(), sketch=sk)
    counted = rows.total() - before[0]
    assert counted > 0 and native.total() - before[1] == counted

    _numpy_only(monkeypatch)
    sk0 = ScoreSketch()
    want = loop_mod._accumulate_streaming(_resident_chunks(), sketch=sk0)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-12, atol=0)
    assert got[1] == want[1]
    np.testing.assert_array_equal(sk.hist, sk0.hist)
    assert sk.n == sk0.n == counted
    np.testing.assert_allclose([sk.sum, sk.sumsq], [sk0.sum, sk0.sumsq],
                               rtol=1e-12, atol=0)
    # the sink sees the same rows the sketch took
    sunk = []
    loop_mod._accumulate_streaming(_resident_chunks(), sunk.append)
    assert sum(x.size for x in sunk) == counted


def test_a_failed_build_takes_the_numpy_path_and_says_so_once(monkeypatch,
                                                               caplog):
    from shifu_tpu.runtime import nativelib

    def no_compiler(*args, **kwargs):
        raise RuntimeError("native build failed (g++ not found)")

    monkeypatch.setattr(metrics_mod, "_native_lib", None)
    monkeypatch.setattr(nativelib, "build_library", no_compiler)
    native = obs.counter("eval_rows_native_total", "")
    before = native.total()
    sk = ScoreSketch()
    with caplog.at_level(logging.WARNING, logger=metrics_mod.__name__):
        got = loop_mod._accumulate_streaming(_resident_chunks(), sketch=sk)
        loop_mod._accumulate_streaming(_resident_chunks())
    assert native.total() == before
    assert [r.getMessage() for r in caplog.records
            if "native pass" in r.getMessage()] == [
        "eval accumulation: the native pass could not be built, numpy "
        "reduces every chunk (native build failed (g++ not found))"]

    monkeypatch.setattr(metrics_mod, "_native", lambda: None)
    sk0 = ScoreSketch()
    assert loop_mod._accumulate_streaming(_resident_chunks(),
                                          sketch=sk0) == got
    np.testing.assert_array_equal(sk.hist, sk0.hist)
    assert (sk.n, sk.sum, sk.sumsq) == (sk0.n, sk0.sum, sk0.sumsq)
