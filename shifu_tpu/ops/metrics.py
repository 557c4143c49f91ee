"""Evaluation metrics.

The reference reports per-epoch weighted train/valid error through its socket
-> ZooKeeper -> ApplicationMaster pipeline (resources/ssgd_monitor.py:281-293,
appmaster/TensorflowSession.java:595-626); AUC parity vs the TF-PS baseline is
the headline accuracy metric (BASELINE.json).  AUC here is the exact weighted
Mann-Whitney statistic with half-credit for ties.
"""

from __future__ import annotations

import ctypes
import logging
import threading

import numpy as np


def auc(scores: np.ndarray, labels: np.ndarray, weights: np.ndarray | None = None) -> float:
    """Weighted ROC-AUC: P(score_pos > score_neg) + 0.5 * P(tie), O(n log n).

    For each positive row, credit the negative weight ranked strictly below it
    plus half the negative weight tied with it; normalize by wp * wn.
    """
    scores = np.asarray(scores, np.float64).ravel()
    labels = np.asarray(labels, np.float64).ravel()
    w = np.ones_like(scores) if weights is None else np.asarray(weights, np.float64).ravel()
    keep = w > 0
    scores, labels, w = scores[keep], labels[keep], w[keep]
    pos = labels >= 0.5
    wp, wn = w[pos].sum(), w[~pos].sum()
    if wp == 0 or wn == 0:
        return float("nan")

    order = np.argsort(scores, kind="mergesort")
    s, is_pos, ww = scores[order], pos[order], w[order]
    neg_w = np.where(~is_pos, ww, 0.0)
    cum_neg = np.cumsum(neg_w)

    # vectorized tie groups: for a row in group [g0, g1],
    # strictly-below = cum_neg[g0-1], tied = cum_neg[g1] - cum_neg[g0-1]
    n = len(s)
    new_group = np.concatenate([[False], s[1:] != s[:-1]])
    starts = np.flatnonzero(np.concatenate([[True], s[1:] != s[:-1]]))
    ends = np.concatenate([starts[1:], [n]]) - 1
    group_id = np.cumsum(new_group.astype(np.int64))
    below_g = np.where(starts > 0, cum_neg[np.maximum(starts - 1, 0)], 0.0)
    tie_g = cum_neg[ends] - below_g
    credit = (below_g + 0.5 * tie_g)[group_id]
    return float(np.sum(ww[is_pos] * credit[is_pos]) / (wp * wn))


def weighted_error(scores: np.ndarray, labels: np.ndarray, weights: np.ndarray | None = None) -> float:
    """The reference's per-epoch 'error': weighted MSE of sigmoid scores with
    TF's SUM_BY_NONZERO_WEIGHTS normalization (ssgd_monitor.py:129,281-284)."""
    scores = np.asarray(scores, np.float64).ravel()
    labels = np.asarray(labels, np.float64).ravel()
    w = np.ones_like(scores) if weights is None else np.asarray(weights, np.float64).ravel()
    nonzero = max(int(np.sum(w != 0)), 1)
    return float(np.sum(w * (scores - labels) ** 2) / nonzero)


def _as_chunk(a) -> np.ndarray:
    """(n,) array of a chunk's column: float32 where float32 holds its dtype
    exactly (float16, bfloat16, bool, small ints), else as it arrives."""
    a = np.asarray(a).ravel()
    if np.can_cast(a.dtype, np.float32, "safe"):
        return a.astype(np.float32, copy=False)
    return a


_native_lock = threading.Lock()
_native_lib = None  # the loaded library; False once it could not be built


def _native():
    """The eval's accumulation as one native pass a chunk
    (runtime/csrc/shifu_evalacc.cc), built with g++ on first use and cached
    for the machine; None where it cannot be built, and then the numpy
    statements of `StreamingMetrics.update` serve, with the same numbers."""
    global _native_lib
    if _native_lib is None:
        with _native_lock:
            if _native_lib is None:
                try:
                    from ..runtime.nativelib import build_library

                    # no fused multiply-add: numpy rounds the product and
                    # the sum apart, and so does the native pass
                    lib = ctypes.CDLL(build_library(
                        "shifu_evalacc.cc", extra_flags=["-ffp-contract=off"]))
                    p, i64 = ctypes.c_void_p, ctypes.c_int64
                    lib.shifu_evalacc_update.restype = ctypes.c_int
                    lib.shifu_evalacc_update.argtypes = [
                        p, p, ctypes.c_int, p, i64, i64, p, p, i64, p, p, p]
                    _native_lib = lib
                except (OSError, RuntimeError) as e:  # no compiler: numpy serves
                    logging.getLogger(__name__).warning(
                        "eval accumulation: the native pass could not be "
                        "built, numpy reduces every chunk (%s)", e)
                    _native_lib = False
    return _native_lib or None


class StreamingMetrics:
    """Out-of-core metric accumulation for eval sets that do not fit RAM.

    Consumes (scores, labels, weights) chunks; weighted error is exact, AUC
    is the same weighted Mann-Whitney statistic computed over fixed score
    bins on [0, 1] (sigmoid outputs) — with `bins` = 2^20 the quantization
    error is < 1e-6 for any realistic score distribution.  The reference
    never aggregated eval metrics at all (its eval module scored row by row
    and left metrics to the Shifu host); this bounds the framework's own
    `eval` CLI at O(bins) memory regardless of row count.

    A chunk is reduced once, in the dtype it arrives in: one float64 pass for
    the error, one index a row into a `2 * bins` histogram (negatives, then
    positives), and only the bins the chunk touches are written.
    """

    def __init__(self, bins: int = 1 << 20):
        if not 0 < bins <= 1 << 30:
            raise ValueError(f"bins must be in [1, 2**30], got {bins}")
        self.bins = bins
        # filled, not calloc'd (np.zeros): numpy asks the kernel for huge
        # pages on an allocation this large, and the scattered bins a chunk
        # touches then miss the TLB less (-10 % a chunk on a Xeon host)
        self._hist = np.empty(2 * bins, np.float64)
        self._hist.fill(0.0)
        self._err_sum = 0.0
        self._nonzero = 0
        self._rows = 0
        self._native_rows = 0

    @property
    def _neg(self) -> np.ndarray:
        return self._hist[:self.bins]

    @property
    def _pos(self) -> np.ndarray:
        return self._hist[self.bins:]

    def update(self, scores, labels, weights=None,
               sketch=None) -> np.ndarray:
        """Fold one chunk in, and the scores of its rows with weight > 0
        into `sketch` (an obs.sketch.ScoreSketch) where one is given;
        returns the (n,) mask of the rows that went into the histogram
        (weight > 0), for callers that want the same rows.

        One native pass reduces the chunk wherever it applies (`_native`
        built, `bins` a power of two, float32 scores, float32 or uint8
        labels, float32 weights or none); the numpy statements below reduce
        every other chunk, to the same bins and counts and the same sums."""
        s = _as_chunk(scores)
        if s.dtype == np.float32 and not self.bins & (self.bins - 1):
            keep = self._update_native(s, labels, weights, sketch)
            if keep is not None:
                return keep
        t = _as_chunk(labels)
        # scores * bins is exact in float32 when bins is a power of two: a
        # float32 score then lands in the bin its float64 product names
        if s.dtype != np.float32 or self.bins & (self.bins - 1):
            s = s.astype(np.float64, copy=False)
        n = s.shape[0]
        self._rows += n
        err = np.subtract(s, t, dtype=np.float64)
        np.multiply(err, err, out=err)
        if weights is None:
            keep, w = np.ones(n, np.bool_), 1.0
            self._nonzero += n
        else:
            w = _as_chunk(weights)
            np.multiply(err, w, out=err)
            self._nonzero += int(np.count_nonzero(w != 0))
            keep = w > 0
            # zero weight, not compaction: x + 0.0 is x, and the rows keep
            # their places.  Float64 on every numpy: any other operand takes
            # np.add.at off its in-place path
            kept, w = w, np.zeros(n, np.float64)
            np.copyto(w, kept, where=keep)
        self._err_sum += float(np.sum(err))
        idx = (s * s.dtype.type(self.bins)).astype(np.int64)
        np.clip(idx, 0, self.bins - 1, out=idx)
        idx += (t >= 0.5) * np.int64(self.bins)
        # np.add.at, not bincount: since numpy 1.25 it adds 65,536 rows in
        # place in 0.19 ms on the TPU host, where bincount(minlength=2 * bins)
        # takes 0.44 ms to fill a fresh 16 MB and the totals 0.65 ms to add
        # it, for a few thousand touched bins (PERF.md, PR 26).  A bin takes
        # its rows in row order; float64 holds the sums of a job's float32
        # weights exactly, so the order shows only with float64 weights.
        np.add.at(self._hist, idx, w)
        if sketch is not None:
            sketch.update(s[keep])
        return keep

    def _update_native(self, s, labels, weights, sketch):
        """`update` as one native pass over a chunk of float32 scores `s`;
        None, with nothing folded in, where the pass does not apply."""
        lib = _native()
        if lib is None:
            return None
        t = np.asarray(labels).ravel()
        if t.dtype != np.uint8:
            t = _as_chunk(t)
        w = None if weights is None else _as_chunk(weights)
        n = s.shape[0]
        if (t.dtype not in (np.float32, np.uint8) or t.shape[0] != n
                or w is not None and (w.dtype != np.float32
                                      or w.shape[0] != n)
                or sketch is not None and not (
                    sketch.bins > 0 and sketch.hist.dtype == np.int64
                    and sketch.hist.shape == (sketch.bins,)
                    and sketch.hist.flags.c_contiguous)):
            return None
        s, t = np.ascontiguousarray(s), np.ascontiguousarray(t)
        if w is not None:
            w = np.ascontiguousarray(w)
        keep = np.empty(n, np.bool_)
        sums = np.empty(3, np.float64)
        counts = np.empty(2, np.int64)
        if lib.shifu_evalacc_update(
                s.ctypes.data, t.ctypes.data, int(t.dtype == np.uint8),
                None if w is None else w.ctypes.data, n, self.bins,
                self._hist.ctypes.data,
                None if sketch is None else sketch.hist.ctypes.data,
                0 if sketch is None else sketch.bins,
                keep.ctypes.data, sums.ctypes.data, counts.ctypes.data) != 0:
            return None
        nonzero, kept = int(counts[0]), int(counts[1])
        self._rows += n
        self._nonzero += nonzero
        self._native_rows += nonzero
        self._err_sum += float(sums[0])
        if sketch is not None and kept:
            # the pass filled the sketch's bins: what ScoreSketch.update
            # adds beside them
            sketch.n += kept
            sketch.sum += float(sums[1])
            sketch.sumsq += float(sums[2])
        return keep

    @property
    def rows(self) -> int:
        return self._rows

    @property
    def nonzero_rows(self) -> int:
        """Rows whose weight is not 0: the error's denominator."""
        return self._nonzero

    @property
    def native_rows(self) -> int:
        """Of `nonzero_rows`, those the native pass reduced."""
        return self._native_rows

    def weighted_error(self) -> float:
        return self._err_sum / max(self._nonzero, 1)

    def auc(self) -> float:
        wp, wn = self._pos.sum(), self._neg.sum()
        if wp == 0 or wn == 0:
            return float("nan")
        neg_below = np.concatenate([[0.0], np.cumsum(self._neg)[:-1]])
        credit = neg_below + 0.5 * self._neg
        return float(np.sum(self._pos * credit) / (wp * wn))

    def merge(self, other: "StreamingMetrics") -> "StreamingMetrics":
        """Fold another accumulator into this one.  Every piece of
        state is additive, so merge(a, b) == a single pass over the
        concatenated chunks — the property windowed drift AUC and the
        fleet rollup lean on (obs/drift.py)."""
        if other.bins != self.bins:
            raise ValueError(
                f"cannot merge StreamingMetrics with bins={other.bins} "
                f"into bins={self.bins}")
        self._hist += other._hist
        self._err_sum += other._err_sum
        self._nonzero += other._nonzero
        self._rows += other._rows
        return self

    def state_arrays(self) -> tuple[np.ndarray, np.ndarray]:
        """The live (pos, neg) bin-weight arrays (no copy) — windowed
        consumers snapshot these and subtract cumulative states."""
        return self._pos, self._neg

    def state_dict(self) -> dict:
        """JSON-serializable state (sparse: only nonzero bins), exact
        round-trip through `from_state`."""
        nz_p = np.flatnonzero(self._pos)
        nz_n = np.flatnonzero(self._neg)
        return {
            "bins": int(self.bins),
            "pos_idx": nz_p.tolist(),
            "pos_w": self._pos[nz_p].tolist(),
            "neg_idx": nz_n.tolist(),
            "neg_w": self._neg[nz_n].tolist(),
            "err_sum": float(self._err_sum),
            "nonzero": int(self._nonzero),
            "rows": int(self._rows),
        }

    @classmethod
    def from_state(cls, state: dict) -> "StreamingMetrics":
        m = cls(bins=int(state["bins"]))
        m._pos[np.asarray(state["pos_idx"], np.int64)] = state["pos_w"]
        m._neg[np.asarray(state["neg_idx"], np.int64)] = state["neg_w"]
        m._err_sum = float(state["err_sum"])
        m._nonzero = int(state["nonzero"])
        m._rows = int(state["rows"])
        return m
