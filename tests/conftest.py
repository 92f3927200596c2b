"""Shared test-session hygiene.

The tier-1 suite compiles a few thousand distinct XLA programs in one
process.  Dropping the jit caches at module boundaries keeps the
population of live executables (and the compiler state behind them)
bounded; each module recompiles what it actually uses, which costs a
little wall time and changes no results.
"""
import jax
import pytest


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    yield
    jax.clear_caches()
