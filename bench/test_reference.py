"""The plain reference's chunked scan against the recurrence one step at
a time, and its lower-precision matmul against the float32 one.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_reference.py
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import HERE

from drive import load_module  # noqa: E402

M = load_module(HERE / "models" / "mamba2.py", "bench_ref_mamba2")


@pytest.mark.parametrize("S,Q", [(64, 16), (48, 16), (32, 32)])
def test_chunked_scan_equals_the_recurrence(S, Q):
    H, P, N = 3, 4, 5
    k = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(k[0], (S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (S, H)))
    a = -jnp.exp(jax.random.normal(k[2], (H,)))
    B = jax.random.normal(k[3], (S, N))
    C = jax.random.normal(k[4], (S, N))
    with jax.default_matmul_precision("highest"):
        got = M.ssd_chunked(x, dt, a, B, C, Q)
        want = M.ssd_sequential(x, dt, a, B, C)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_lowered_matmul_rounds_and_float32_does_not():
    x = jax.random.normal(jax.random.key(1), (16, 32))
    y = jax.random.normal(jax.random.key(2), (32, 8))
    mm32, _ = M._mm(None)
    mm8, _ = M._mm(jnp.float8_e4m3fn)
    exact = x @ y
    assert jnp.array_equal(mm32(x, y), exact)
    err = float(jnp.max(jnp.abs(mm8(x, y) - exact)) / jnp.max(jnp.abs(exact)))
    assert 1e-3 < err < 0.2          # e4m3 keeps 3 mantissa bits
