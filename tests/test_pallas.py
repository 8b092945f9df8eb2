"""Pallas kernel tests.  Where Pallas does not compile natively (e.g.
the CPU test backend) the kernels run in interpret mode — same program,
emulated execution — so the math is verified everywhere and only the
Mosaic lowering is left to the on-chip smoke
(chip_smoke.py phase B)."""

import numpy as np
import pytest  # noqa: F401

from bifrost_tpu.ops import pallas_kernels as pk

# native where available, interpret elsewhere — never skip the math
INTERPRET = not pk.available()


def test_stokes_detect_matches_jnp():
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    T, F = 16, 256
    xr, xi, yr, yi = (rng.randn(T, F).astype(np.float32)
                      for _ in range(4))
    out = np.asarray(pk.stokes_detect(jnp.asarray(xr), jnp.asarray(xi),
                                      jnp.asarray(yr), jnp.asarray(yi),
                                      interpret=INTERPRET))
    x = xr + 1j * xi
    y = yr + 1j * yi
    xy = x * np.conj(y)
    expect = np.stack([np.abs(x) ** 2 + np.abs(y) ** 2,
                       np.abs(x) ** 2 - np.abs(y) ** 2,
                       2 * xy.real, -2 * xy.imag], axis=1)
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-4)


def test_xcorr_herm_exact_interpret():
    """Fused Hermitian int8 correlation kernel vs the integer oracle
    at a lane-aligned shape (interpret mode; the on-chip compile is
    checked by chip_smoke.py phase B)."""
    import jax.numpy as jnp
    rng = np.random.RandomState(1)
    T, F, n = 16, 3, 256
    re = rng.randint(-64, 64, (T, F, n)).astype(np.int8)
    im = rng.randint(-64, 64, (T, F, n)).astype(np.int8)
    got = np.asarray(pk.xcorr_herm(jnp.asarray(re), jnp.asarray(im),
                                   interpret=True))
    x = re.astype(np.float64) + 1j * im
    want = np.einsum('tfi,tfj->fij', x, np.conj(x))
    np.testing.assert_array_equal(got, want.astype(np.complex64))
