"""Shared gating + imports for the Pallas kernels.

Three kernels engage by themselves on a TPU backend: the fused
FT-Transformer block (`ModelSpec.fused_block="auto"`), the small-token
attention kernel (S <= 64, D <= 16) and the fused int8 dequant+matmul
(int8 wire / resident format); the fused embedding rows-update engages
with the sparse-update plan when D % 128 == 0.  Flash attention and the
embedding lookup stay behind SHIFU_TPU_PALLAS.  Off a TPU backend every
kernel runs in interpret mode and only under that same opt-in (or an
explicit `use_pallas=True`, the tests' exactness path).  `chip_smoke.py`
compiles each kernel natively on the chip and holds it to an f64 oracle.
"""

from __future__ import annotations

import os

import jax
from jax.experimental.pallas import tpu as pltpu

__all__ = ["on_tpu", "pallas_opt_in", "pltpu"]


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU: kernels then compile
    natively (`interpret=False`); anywhere else they interpret."""
    return jax.default_backend() == "tpu"


def pallas_opt_in() -> bool:
    """True when the user opted into the Pallas kernels.

    "0", "false", "" and unset all mean off — so SHIFU_TPU_PALLAS=0
    explicitly disables (a bare bool(getenv) would read "0" as on).
    """
    return os.environ.get("SHIFU_TPU_PALLAS", "").lower() not in (
        "", "0", "false", "no")
