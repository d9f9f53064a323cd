"""Shared test config.

x64 is enabled globally: on the CPU the Pallas kernels run in interpret
mode and are checked against float64 references (the chip serves float32,
see ``chip_smoke.py``).  Note: NO device-count flags are
set here — smoke tests and benches must see the single real CPU device; the
512-device dry-run sets its XLA_FLAGS inside launch/dryrun.py (subprocess
tests do the same).
"""
import pytest

import jax

jax.config.update("jax_enable_x64", True)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Drop compiled executables after each test module: a full-suite run
    compiles hundreds of programs and the LLVM JIT otherwise exhausts
    process memory near the end of the suite."""
    yield
    jax.clear_caches()
