"""Shared kernel utilities."""

from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu


def default_interpret() -> bool:
    """Pallas kernels compile only on TPU; everywhere else run the kernel
    body in interpret mode (the CPU validation path)."""
    return jax.default_backend() != "tpu"


def tpu_compiler_params(dimension_semantics: tuple[str, ...]) -> pltpu.CompilerParams:
    """Mosaic compiler params naming each grid axis parallel or arbitrary
    (sequential); interpret mode ignores them."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)
