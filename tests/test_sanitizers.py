"""Sanitizer self-tests for the native components (ASan + UBSan + TSan).

The reference had no race/memory detection of any kind (SURVEY.md §5.2:
"None").  Here the authored C++ components carry a -DSHIFU_SELFTEST_MAIN
entry that drives their kernels (multithreaded chunked parse; tiled matmul /
layernorm / softmax incl. remainder paths) under
-fsanitize=address,undefined — an out-of-bounds read, use-after-free, leak,
or UB in the hot paths fails these tests — and the parser's threaded path
additionally runs under -fsanitize=thread for data-race detection.
"""

import gzip
import re
import shutil
import subprocess

import numpy as np
import pytest

from shifu_tpu.runtime.nativelib import build_selftest

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None, reason="no g++ in environment")

# Only the sanitizer *runtime* being absent is a legitimate skip (toolchain
# without libasan/libubsan installed).  Any other compile error — syntax,
# signature drift, bad flag — must fail the test, so match the specific
# linker complaints, not the command line (which always says -fsanitize).
_MISSING_RUNTIME = re.compile(
    r"cannot find -l(asan|ubsan|tsan)|lib(a|ub|t)san[^\n]*(not found|No such)",
    re.IGNORECASE)


def _build_or_skip(source: str, **kw) -> str:
    try:
        return build_selftest(source, **kw)
    except RuntimeError as e:
        if _MISSING_RUNTIME.search(str(e)):
            pytest.skip(f"sanitizer runtime unavailable: {str(e)[:120]}")
        raise


def test_parser_selftest_asan_ubsan(tmp_path):
    exe = _build_or_skip("shifu_parser.cc",
                         extra_flags=["-lz", "-pthread", "-ldl"])
    # include the optional file path: exercises gzip inflate + count under ASan
    rows = np.random.default_rng(0).standard_normal((500, 8))
    text = "\n".join("|".join(f"{v:.5g}" for v in r) for r in rows) + "\n"
    gz = tmp_path / "part.gz"
    with gzip.open(gz, "wt") as f:
        f.write(text)
    proc = subprocess.run([exe, str(gz)], capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "parser selftest ok" in proc.stdout


def test_parser_selftest_tsan():
    """Race detection on the multithreaded chunked parse (ThreadSanitizer).

    SURVEY.md §5.2: the reference had no race detection of any kind.  The
    parser's threaded path (chunk offset prefix-sum + disjoint-range writes
    into one shared output buffer) gets a dedicated TSan run.
    """
    exe = _build_or_skip("shifu_parser.cc", sanitize="thread",
                         extra_flags=["-lz", "-pthread", "-ldl"])
    proc = subprocess.run([exe], capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "WARNING: ThreadSanitizer" not in proc.stderr
    assert "parser selftest ok" in proc.stdout


@pytest.fixture(scope="module")
def packed_model(tmp_path_factory):
    """A small exported artifact with its packed model.bin."""
    import jax

    from shifu_tpu.config import (
        DataConfig, JobConfig, ModelSpec, OptimizerConfig, TrainConfig)
    from shifu_tpu.data import synthetic
    from shifu_tpu.export import save_artifact
    from shifu_tpu.runtime import pack_native
    from shifu_tpu.train import init_state

    # moe_mlp covers the widest op set (dense, softmax activation,
    # expert_dense, moe_combine), so mutations reach every record reader
    schema = synthetic.make_schema(num_features=8)
    job = JobConfig(
        schema=schema, data=DataConfig(batch_size=32),
        model=ModelSpec(model_type="moe_mlp", hidden_nodes=(16, 8),
                        activations=("relu", "tanh"), num_experts=3),
        train=TrainConfig(epochs=1, loss="weighted_mse",
                          optimizer=OptimizerConfig(name="adadelta")),
    ).validate()
    state = init_state(job, 8)
    out = str(tmp_path_factory.mktemp("fuzz") / "model")
    save_artifact(jax.device_get(state.params), job, out)
    return pack_native(out)


def test_model_bin_fuzz_asan(packed_model, tmp_path):
    """Corrupted/truncated model.bin files must be rejected or scored —
    never crash.  Runs every mutant through the ASan/UBSan selftest binary,
    so an out-of-bounds read in the untrusted-file loader fails here even
    when it wouldn't segfault in production."""
    exe = _build_or_skip("shifu_scorer.cc", extra_flags=["-pthread"])
    blob = bytearray(open(packed_model, "rb").read())
    proc = subprocess.run([exe, packed_model], capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0 and "model load ok" in proc.stdout, (
        proc.stdout + proc.stderr)

    rng = np.random.default_rng(0)
    mutant = tmp_path / "mutant.bin"
    for trial in range(60):
        m = bytearray(blob)
        kind = trial % 3
        if kind == 0:  # truncation
            m = m[: rng.integers(0, len(m))]
        elif kind == 1:  # single byte flip
            i = int(rng.integers(0, len(m)))
            m[i] ^= int(rng.integers(1, 256))
        else:  # corrupt a 4-byte header/length field
            i = int(rng.integers(0, max(1, len(m) // 4))) * 4
            m[i:i + 4] = rng.integers(0, 256, 4, dtype=np.uint8).tobytes()
        mutant.write_bytes(bytes(m))
        proc = subprocess.run([exe, str(mutant)], capture_output=True,
                              text=True, timeout=120)
        assert proc.returncode == 0, (
            f"trial {trial} (kind {kind}): rc={proc.returncode}\n"
            + proc.stdout + proc.stderr)


def test_scorer_selftest_tsan():
    """Race detection on the scorer's threaded batch split + shared arena
    pool (the selftest runs compute_batch with SHIFU_SCORER_THREADS=3)."""
    exe = _build_or_skip("shifu_scorer.cc", sanitize="thread",
                         extra_flags=["-pthread"])
    proc = subprocess.run([exe], capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "WARNING: ThreadSanitizer" not in proc.stderr
    assert "scorer selftest ok" in proc.stdout


def test_scorer_selftest_asan_ubsan():
    exe = _build_or_skip("shifu_scorer.cc", extra_flags=["-pthread"])
    proc = subprocess.run([exe], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "scorer selftest ok" in proc.stdout


def test_evalacc_selftest_asan_ubsan():
    """The eval's one-pass accumulation on its edge chunks (no rows, one
    row, every weight zero, no weights, uint8 labels, NaN and scores past
    both ends) under ASan/UBSan."""
    exe = _build_or_skip("shifu_evalacc.cc", extra_flags=["-ffp-contract=off"])
    proc = subprocess.run([exe], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "evalacc selftest ok" in proc.stdout
