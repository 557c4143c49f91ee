"""The one row generator: normalized tabular rows with a learnable logistic
ground truth, made on the host from the seed in fixed-size chunks, a few
threads at a time.

The arithmetic is `shifu_tpu/data/synthetic.make_rows`' (numeric features
~ N(0, 1), as after Shifu's ZSCALE; categorical ids stored as floats; target
~ Bernoulli(sigmoid(1.5 x.w + per-id effects + noise)); weights uniform in
[0.5, 2) where the schema has a weight column), copied here so that no later
PR can change the yardstick, and cut into chunks that each draw from a
generator of their own: every run of every check pays this set-up, and one
numpy stream over 2.4 G normals takes half a minute.  (Made on the device
instead, the rows came back to the host at 0.24 GB/s: 26 s for the largest
cell; my chip run, PR 24.)  Two departures, both in the configuration files:
ids are skewed (`floor(V * u**id_skew)`, a few hot buckets and a long tail,
as hashed click-log fields are; 1.0 is uniform), and features leave in the
wire dtype the loaders store (`feature_dtype`).

The same (spec, seed, stream) gives the same rows, whatever the row count
asked for and however many threads: chunk `i` depends on (seed, stream, i)
alone.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np

CHUNK_ROWS = 1 << 20
TRAIN_STREAM, VALID_STREAM = 0, 1
_TRUTH = 7        # the stream of the ground truth, shared by train and valid
MAX_THREADS = 12

_DTYPES = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}


def _rng(seed: int, *path: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), *path])))


def _truth(spec: dict, seed: int) -> dict:
    """The ground truth: a unit weight vector over the numeric features and
    a table of per-id effects, one row a categorical field."""
    rng = _rng(seed, _TRUTH)
    n_num = int(spec["num_numeric"])
    n_cat = int(spec.get("num_categorical", 0))
    w = rng.standard_normal(n_num).astype(np.float32)
    w /= max(float(np.linalg.norm(w)), 1e-9)
    effect = (0.5 * rng.standard_normal(
        (n_cat, int(spec.get("vocab_size", 0))), dtype=np.float32)
        if n_cat else None)
    return {"w": w, "effect": effect}


def _chunk(spec: dict, truth: dict, seed: int, stream: int, index: int,
           keep: int, out: dict, lo: int) -> None:
    """Rows lo..lo+keep of `out`: the first `keep` rows of chunk `index`,
    which is always drawn whole, so that a row does not depend on how many
    rows were asked for."""
    rng = _rng(seed, stream, index)
    rows = CHUNK_ROWS
    n_num = int(spec["num_numeric"])
    n_cat = int(spec.get("num_categorical", 0))
    feats = out["features"][lo:lo + keep]
    if keep < rows:
        feats = np.empty((rows, feats.shape[1]), feats.dtype)
    logits = np.zeros(rows, np.float32)
    if n_num:
        x = rng.standard_normal((rows, n_num), dtype=np.float32)
        logits += 1.5 * (x @ truth["w"])
        feats[:, :n_num] = x       # cast to the wire dtype as it is stored
    if n_cat:
        vocab = int(spec["vocab_size"])
        u = rng.random((rows, n_cat), dtype=np.float32)
        ids = np.minimum((vocab * u ** float(spec.get("id_skew", 1.0)))
                         .astype(np.int32), vocab - 1)
        logits += truth["effect"][np.arange(n_cat)[None, :], ids].sum(axis=1)
        feats[:, n_num:] = ids
    logits += float(spec.get("label_noise", 0.5)) * rng.standard_normal(
        rows, dtype=np.float32)
    prob = 1.0 / (1.0 + np.exp(-logits))
    target = rng.random(rows, dtype=np.float32) < prob
    out["target"][lo:lo + keep, 0] = target[:keep]
    if spec.get("with_weight"):
        out["weight"][lo:lo + keep, 0] = rng.uniform(0.5, 2.0, rows)[:keep]
    if keep < rows:
        out["features"][lo:lo + keep] = feats[:keep]


def make_rows(spec: dict, num_rows: int, seed: int, stream: int) -> dict:
    """{"features" (N, F) in the wire dtype, "target" (N, 1) f32, "weight"
    (N, 1) f32} as host arrays.  `spec` is a configuration file's flat keys:
    num_numeric, num_categorical, vocab_size, id_skew, label_noise,
    with_weight, feature_dtype."""
    n_feat = int(spec["num_numeric"]) + int(spec.get("num_categorical", 0))
    out = {
        "features": np.empty((num_rows, n_feat),
                             _DTYPES[spec.get("feature_dtype", "float32")]),
        "target": np.empty((num_rows, 1), np.float32),
        "weight": np.ones((num_rows, 1), np.float32),
    }
    truth = _truth(spec, seed)
    jobs = [(i, lo, min(CHUNK_ROWS, num_rows - lo))
            for i, lo in enumerate(range(0, num_rows, CHUNK_ROWS))]
    threads = max(1, min(MAX_THREADS, len(jobs), (os.cpu_count() or 2) - 1))
    with ThreadPoolExecutor(threads) as pool:
        # read every result: a chunk that raised raises here
        for done in [pool.submit(_chunk, spec, truth, seed, stream, i, n,
                                 out, lo) for i, lo, n in jobs]:
            done.result()
    return out
