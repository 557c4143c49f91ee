"""Losses / metrics / activations parity tests.

weighted_mse must reproduce TF's `tf.losses.mean_squared_error(...,
weights=w)` SUM_BY_NONZERO_WEIGHTS semantics, the exact loss the reference
optimizes (reference: resources/ssgd_monitor.py:129)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from shifu_tpu.ops import (
    auc,
    bce,
    get_activation,
    get_loss,
    weighted_bce,
    weighted_error,
    weighted_mse,
)
from shifu_tpu.ops.initializers import xavier_bias


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def test_weighted_mse_matches_tf_semantics():
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((16, 1)).astype(np.float32)
    target = (rng.random((16, 1)) < 0.5).astype(np.float32)
    weight = rng.uniform(0, 2, (16, 1)).astype(np.float32)
    weight[3] = 0.0  # zero-weight row excluded from the denominator
    got = float(weighted_mse(jnp.array(logits), jnp.array(target), jnp.array(weight)))
    p = _sigmoid(logits)
    expected = np.sum(weight * (p - target) ** 2) / np.sum(weight != 0)
    assert got == pytest.approx(expected, rel=1e-5)


def test_weighted_mse_all_ones_weight_is_plain_mse():
    logits = jnp.array([[0.0], [2.0]])
    target = jnp.array([[0.0], [1.0]])
    weight = jnp.ones((2, 1))
    got = float(weighted_mse(logits, target, weight))
    p = _sigmoid(np.array([[0.0], [2.0]]))
    assert got == pytest.approx(float(np.mean((p - np.array([[0.], [1.]])) ** 2)), rel=1e-5)


def test_bce_matches_reference_formula():
    logits = jnp.array([[0.5], [-1.0], [3.0]])
    target = jnp.array([[1.0], [0.0], [1.0]])
    got = float(bce(logits, target, jnp.ones((3, 1))))
    l = np.array([0.5, -1.0, 3.0])
    y = np.array([1.0, 0.0, 1.0])
    expected = np.mean(np.maximum(l, 0) - l * y + np.log1p(np.exp(-np.abs(l))))
    assert got == pytest.approx(expected, rel=1e-4)  # float32 compute


def test_weighted_bce_zero_weight_rows_ignored():
    logits = jnp.array([[1.0], [99.0]])
    target = jnp.array([[1.0], [0.0]])
    weight = jnp.array([[1.0], [0.0]])
    got = float(weighted_bce(logits, target, weight))
    l = 1.0
    expected = np.log1p(np.exp(-l))
    assert got == pytest.approx(expected, rel=1e-5)


def test_get_loss_unknown():
    with pytest.raises(KeyError):
        get_loss("nope")


def test_auc_perfect_and_random():
    labels = np.array([0, 0, 1, 1])
    assert auc(np.array([0.1, 0.2, 0.8, 0.9]), labels) == 1.0
    assert auc(np.array([0.9, 0.8, 0.2, 0.1]), labels) == 0.0
    assert auc(np.array([0.5, 0.5, 0.5, 0.5]), labels) == 0.5


def test_auc_matches_sklearn_when_available():
    sk = pytest.importorskip("sklearn.metrics")
    rng = np.random.default_rng(1)
    scores = rng.random(500)
    labels = (rng.random(500) < 0.3).astype(float)
    scores[labels == 1] += 0.2  # separable-ish
    assert auc(scores, labels) == pytest.approx(
        sk.roc_auc_score(labels, scores), abs=1e-10)
    w = rng.uniform(0.1, 3.0, 500)
    assert auc(scores, labels, w) == pytest.approx(
        sk.roc_auc_score(labels, scores, sample_weight=w), abs=1e-10)


def test_auc_with_ties():
    scores = np.array([0.5, 0.5, 0.5, 0.1])
    labels = np.array([1, 0, 1, 0])
    # each positive ties one negative (0.5 credit each) and beats the 0.1 negative
    expected = (0.5 * 1 + 1) / 2  # per positive: (0.5 + 1)/2 negatives
    assert auc(scores, labels) == pytest.approx(expected)


def test_weighted_error_nonzero_denominator():
    s = np.array([0.5, 0.8])
    y = np.array([0.0, 1.0])
    w = np.array([1.0, 0.0])
    assert weighted_error(s, y, w) == pytest.approx(0.25)


def test_streaming_metrics_match_exact():
    """StreamingMetrics (O(bins), used by multi-host eval and the eval CLI)
    must match the exact weighted AUC and error on chunked sigmoid-score
    streams — VERDICT round-1 bar: within 1e-3 (actual: ~1e-6 at 2^20 bins)."""
    from shifu_tpu.ops.metrics import StreamingMetrics

    rng = np.random.default_rng(5)
    n = 20_000
    labels = (rng.random(n) < 0.35).astype(float)
    scores = np.clip(rng.normal(0.4 + 0.2 * labels, 0.15), 0.0, 1.0)
    weights = rng.uniform(0.0, 2.0, n)  # includes zero weights
    sm = StreamingMetrics()
    for lo in range(0, n, 3000):  # uneven chunks
        hi = min(n, lo + 3000)
        sm.update(scores[lo:hi], labels[lo:hi], weights[lo:hi])
    assert sm.rows == n
    assert sm.auc() == pytest.approx(auc(scores, labels, weights), abs=1e-3)
    assert sm.auc() == pytest.approx(auc(scores, labels, weights), abs=5e-6)
    assert sm.weighted_error() == pytest.approx(
        weighted_error(scores, labels, weights), rel=1e-12)
    # unweighted + degenerate (single-class) cases
    sm2 = StreamingMetrics()
    sm2.update(scores[labels == 1], labels[labels == 1])
    assert np.isnan(sm2.auc())


class _FrozenStreamingUpdate:
    """`StreamingMetrics.update` as it stood before PR 26, kept here as the
    reference the one-pass update is held to: float64 copies, compaction
    by boolean mask, one `np.bincount(minlength=bins)` a class and a chunk."""

    def __init__(self, bins):
        self.bins = bins
        self._pos = np.zeros(bins, np.float64)
        self._neg = np.zeros(bins, np.float64)
        self._err_sum = 0.0
        self._nonzero = 0
        self._rows = 0

    def update(self, scores, labels, weights=None):
        scores = np.asarray(scores, np.float64).ravel()
        labels = np.asarray(labels, np.float64).ravel()
        w = (np.ones_like(scores) if weights is None
             else np.asarray(weights, np.float64).ravel())
        self._rows += scores.shape[0]
        self._err_sum += float(np.sum(w * (scores - labels) ** 2))
        self._nonzero += int(np.sum(w != 0))
        keep = w > 0
        scores, labels, w = scores[keep], labels[keep], w[keep]
        idx = np.clip((scores * self.bins).astype(np.int64), 0, self.bins - 1)
        pos = labels >= 0.5
        self._pos += np.bincount(idx[pos], weights=w[pos],
                                 minlength=self.bins)
        self._neg += np.bincount(idx[~pos], weights=w[~pos],
                                 minlength=self.bins)


def _stream_chunks(case, rng):
    """The (scores, labels, weights) chunks of one equivalence case.  The
    weights are uniform in [0.1, 2): as float32 or narrower they are
    multiples of 2**-27 and float64 holds their sums exactly, as float64
    (`_STREAM_ROUNDS`) a bin that several rows share rounds by the order."""
    import ml_dtypes

    def chunk(n, dtype=np.float32, weights=True):
        labels = (rng.random(n) < 0.35)
        scores = np.clip(rng.normal(0.4 + 0.2 * labels, 0.15), 0.0, 1.0)
        w = rng.uniform(0.1, 2.0, n) if weights else None
        return [scores.astype(dtype), labels.astype(dtype),
                None if w is None else w.astype(dtype)]

    if case == "weights_none":
        return [chunk(700, weights=False), chunk(300, weights=False)]
    if case == "weights_zero_negative_nan":
        out = [chunk(900), chunk(400)]
        for _, _, w in out:
            w[::5] = 0.0
            w[1::7] = -1.5
            w[2::11] = -0.0
        out[1][2][3] = np.nan  # in the error and its denominator, in no bin
        return out
    if case == "scores_at_and_past_the_ends":
        c = chunk(64)
        c[0][:10] = [0.0, 1.0, -0.0, np.nextafter(np.float32(0), -1),
                     np.nextafter(np.float32(1), 2), -1e-3, 1.001, 7.0,
                     -3e38, 3e38]
        return [c]
    if case == "scores_nan_inf":
        c = chunk(64)
        c[0][:4] = [np.nan, np.inf, -np.inf, np.nan]
        c[2][3] = 0.0  # one NaN score with no weight
        return [c]
    if case == "empty_chunk":
        return [chunk(50), chunk(0), chunk(0, weights=False), chunk(20)]
    if case == "one_row_chunks":
        return [chunk(1) for _ in range(40)] + [chunk(1, weights=False)]
    if case == "column_inputs":
        return [[a if a is None else a.reshape(-1, 1) for a in chunk(500)]
                for _ in range(3)]
    if case == "mixed_shapes":
        s, t, w = chunk(500)
        return [[s.reshape(-1, 1), t, w.reshape(-1, 1)]]
    if case == "float64":
        return [chunk(3000, np.float64), chunk(1000, np.float64)]
    if case == "float16":
        return [chunk(3000, np.float16)]
    if case == "bfloat16":
        return [chunk(3000, ml_dtypes.bfloat16), chunk(500, ml_dtypes.bfloat16)]
    if case == "jax_arrays":
        return [[jnp.asarray(a) for a in chunk(800)]]
    if case == "python_lists":
        return [[a.tolist() for a in chunk(30, np.float64)]]
    if case == "many_chunks":
        return [chunk(int(n)) for n in rng.integers(1, 4000, 25)]
    if case == "one_concatenated_chunk":
        parts = [chunk(int(n)) for n in rng.integers(1, 4000, 25)]
        return [[np.concatenate([p[i] for p in parts]) for i in range(3)]]
    if case == "few_distinct_scores":
        # most rows share their bin with others of the chunk, whatever `bins`
        out = [chunk(5000), chunk(5000)]
        for c in out:
            c[0] = (np.round(c[0] * 37) / 37).astype(np.float32)
        return out
    if case == "float64_shared_bins":
        out = [chunk(5000, np.float64), chunk(5000, np.float64)]
        for c in out:
            c[0] = np.round(c[0] * 150) / 150
        return out
    raise AssertionError(case)


_STREAM_CASES = ["weights_none", "weights_zero_negative_nan",
                 "scores_at_and_past_the_ends", "scores_nan_inf",
                 "empty_chunk", "one_row_chunks", "column_inputs",
                 "mixed_shapes", "float64", "float16", "bfloat16",
                 "jax_arrays", "python_lists", "many_chunks",
                 "one_concatenated_chunk", "few_distinct_scores",
                 "float64_shared_bins"]
# float64 weights with full mantissas: the sums of a shared bin round, and a
# bin takes its rows in row order where the frozen update summed the chunk
# first; rows in a bin (under a hundred here) times 2**-53 bounds the distance
_STREAM_ROUNDS = {"float64", "python_lists", "float64_shared_bins"}


@pytest.mark.filterwarnings("ignore:invalid value encountered in cast")
@pytest.mark.filterwarnings("ignore:overflow encountered")
@pytest.mark.parametrize("bins", [1 << 20, 1 << 12, 1000])
@pytest.mark.parametrize("case", _STREAM_CASES)
def test_streaming_update_matches_the_frozen_update(case, bins):
    """PR 26: every chunk is reduced once, and the statistics stay what they
    were — the (pos, neg) bins bit for bit wherever float64 holds the sums
    exactly (to 1e-15 where it rounds them), the error to 1e-12, the row
    counts exactly — against the update as it stood, on the same chunks."""
    from shifu_tpu.ops.metrics import StreamingMetrics

    chunks = _stream_chunks(case, np.random.default_rng(26))
    old, new = _FrozenStreamingUpdate(bins), StreamingMetrics(bins)
    for s, t, w in chunks:
        old.update(s, t, w)
        counted = new.update(s, t, w)
        n = np.asarray(s).size
        want = (np.ones(n, bool) if w is None
                else np.asarray(w, np.float64).ravel() > 0)
        np.testing.assert_array_equal(counted, want)
    pos, neg = new.state_arrays()
    rounds = 1e-15 if case in _STREAM_ROUNDS else 0
    np.testing.assert_allclose(pos, old._pos, rtol=rounds, atol=0)
    np.testing.assert_allclose(neg, old._neg, rtol=rounds, atol=0)
    assert new.rows == old._rows
    assert new.nonzero_rows == old._nonzero
    np.testing.assert_allclose(new.weighted_error(),
                               old._err_sum / max(old._nonzero, 1),
                               rtol=1e-12, atol=0)
    # `auc()` reads the right half of the one histogram as the positives
    below = np.concatenate([[0.0], np.cumsum(old._neg)[:-1]])
    with np.errstate(invalid="ignore"):
        want_auc = (np.sum(old._pos * (below + 0.5 * old._neg))
                    / (old._pos.sum() * old._neg.sum()))
    np.testing.assert_allclose(new.auc(), float(want_auc), rtol=10 * rounds,
                               atol=0)


def test_streaming_bins_take_their_rows_in_row_order():
    """Where float64 rounds, a bin is total + w1 + w2, rows in order: one
    ulp from the total + (w1 + w2) that one bincount a chunk gave.  This
    data tells the two apart, so the exact cases above do not pass for lack
    of a difference; float32 weights of a job's range never show one."""
    from shifu_tpu.ops.metrics import StreamingMetrics

    bins = 1 << 12
    s = np.float32([0.5, 0.5, 0.5])
    t = np.float32([1, 1, 1])
    first, then = np.float32([1.0]), np.float32([2.0 ** -53, 2.0 ** -53])
    old, new = _FrozenStreamingUpdate(bins), StreamingMetrics(bins)
    straight = np.zeros(bins)
    for s_, t_, w_ in ((s[:1], t[:1], first), (s[1:], t[1:], then)):
        old.update(s_, t_, w_)
        new.update(s_, t_, w_)
        np.add.at(straight, np.full(len(w_), bins // 2), w_)
    assert old._pos[bins // 2] == 1.0 + 2.0 ** -52
    assert straight[bins // 2] == 1.0
    np.testing.assert_array_equal(new.state_arrays()[0], straight)

    rng = np.random.default_rng(28)
    s = (np.round(rng.random(20000) * 50) / 50).astype(np.float32)
    t = (rng.random(20000) < 0.5).astype(np.float32)
    w = rng.uniform(1e-3, 1e3, 20000).astype(np.float32)
    old, new = _FrozenStreamingUpdate(bins), StreamingMetrics(bins)
    for lo in range(0, 20000, 5000):
        old.update(s[lo:lo + 5000], t[lo:lo + 5000], w[lo:lo + 5000])
        new.update(s[lo:lo + 5000], t[lo:lo + 5000], w[lo:lo + 5000])
    for x, y in zip(new.state_arrays(), (old._pos, old._neg)):
        np.testing.assert_array_equal(x, y)


def test_streaming_metrics_surface_survives_the_one_pass_update():
    """What the callers lean on: `state_arrays()` stays live across
    `update` (obs/drift.py snapshots and subtracts it), `merge` of two
    halves is one pass over both, `state_dict`/`from_state` round-trips."""
    from shifu_tpu.ops.metrics import StreamingMetrics

    rng = np.random.default_rng(27)
    n = 6000
    labels = (rng.random(n) < 0.4).astype(np.float32)
    scores = np.clip(rng.normal(0.4 + 0.2 * labels, 0.2), 0, 1).astype(
        np.float32)
    weights = rng.choice([0.0, 0.25, 0.5, 1.0, 2.0], n).astype(np.float32)
    bins = 1 << 12

    one = StreamingMetrics(bins)
    pos, neg = one.state_arrays()
    assert not pos.any() and not neg.any()
    one.update(scores[:1000], labels[:1000], weights[:1000])
    snap = pos.copy(), neg.copy()
    assert pos.sum() + neg.sum() == weights[:1000].sum()  # live, no copy
    one.update(scores[1000:], labels[1000:], weights[1000:])
    assert pos.sum() + neg.sum() == weights.sum()
    assert (pos - snap[0]).sum() + (neg - snap[1]).sum() == \
        weights[1000:].sum()
    for live, again in zip((pos, neg), one.state_arrays()):
        assert np.shares_memory(live, again)

    a, b = StreamingMetrics(bins), StreamingMetrics(bins)
    a.update(scores[:2500], labels[:2500], weights[:2500])
    b.update(scores[2500:], labels[2500:], weights[2500:])
    a_pos = a.state_arrays()[0]
    assert a.merge(b) is a
    assert np.shares_memory(a_pos, a.state_arrays()[0])  # merged in place
    for x, y in zip(a.state_arrays(), one.state_arrays()):
        np.testing.assert_array_equal(x, y)  # dyadic weights: exact sums
    assert (a.rows, a.nonzero_rows) == (one.rows, one.nonzero_rows) == \
        (n, int((weights != 0).sum()))
    assert a.auc() == one.auc()
    assert a.weighted_error() == pytest.approx(one.weighted_error(),
                                               rel=1e-12)

    import json
    back = StreamingMetrics.from_state(json.loads(json.dumps(
        one.state_dict())))
    for x, y in zip(back.state_arrays(), one.state_arrays()):
        np.testing.assert_array_equal(x, y)
    assert (back.bins, back.rows, back.nonzero_rows) == (bins, n,
                                                         one.nonzero_rows)
    assert back.auc() == one.auc()
    assert back.weighted_error() == one.weighted_error()
    back.update(scores[:10], labels[:10])  # and it goes on accumulating
    assert back.rows == n + 10
    with pytest.raises(ValueError):
        one.merge(StreamingMetrics(bins * 2))


def test_activation_fallback_and_leaky_alpha():
    f = get_activation("unknown_thing")
    # reference fallback: leaky_relu with TF alpha 0.2 (ssgd_monitor.py:77-90)
    assert float(f(jnp.array(-1.0))) == pytest.approx(-0.2)
    assert float(get_activation("relu")(jnp.array(-1.0))) == 0.0


def test_xavier_bias_range():
    key = jax.random.PRNGKey(0)
    b = xavier_bias(key, (100,))
    limit = np.sqrt(3.0 / 100)
    assert float(jnp.abs(b).max()) <= limit
    assert float(jnp.abs(b).max()) > limit * 0.5  # actually spread out
