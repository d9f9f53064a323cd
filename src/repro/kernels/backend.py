"""Backend-derived settings every kernel entry point shares.

One place decides three things the device dictates: whether Pallas runs
compiled or in interpret mode, the float dtype arrays live in on the
device, and the compiler parameters each ``pallas_call`` passes so the
compiler's scoped-VMEM limit is the preflight's budget.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.autotune import VMEM_BUDGET_BYTES


def default_interpret() -> bool:
    """Pallas kernels compile on a TPU and run in interpret mode elsewhere."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` means "what the backend runs"; an explicit bool wins."""
    return default_interpret() if interpret is None else bool(interpret)


def float_dtype():
    """The float dtype device arrays hold: float64 under ``jax_enable_x64``
    (the CPU reference runs), float32 otherwise (the TPU has no float64)."""
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def compiler_params() -> pltpu.CompilerParams:
    """Scoped-VMEM limit for every kernel: the budget the preflight plans
    (:data:`repro.core.autotune.VMEM_BUDGET_BYTES`) and nothing else."""
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_BUDGET_BYTES)
