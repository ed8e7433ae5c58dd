"""Pallas SSD kernel vs the pure-jnp oracle (models/ssm.ssd_chunked),
interpret mode, shape/dtype sweep per the kernel-validation protocol."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ssd as K
from repro.models import ssm


def _mk(bh, t, p, n, dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((bh, t, p)), dtype)
    a = jnp.asarray(-np.abs(rng.standard_normal((bh, t))) * 0.1, dtype)
    b = jnp.asarray(rng.standard_normal((bh, t, n)) * 0.3, dtype)
    c = jnp.asarray(rng.standard_normal((bh, t, n)) * 0.3, dtype)
    return x, a, b, c


def _oracle(x, a, b, c, chunk):
    # oracle wants [B, T, H, P] with groups; use B=BH, H=1, G=1
    bh, t, p = x.shape
    y, _ = ssm.ssd_chunked(
        x.reshape(bh, t, 1, p).swapaxes(0, 0),
        a.reshape(bh, t, 1),
        b.reshape(bh, t, 1, -1),
        c.reshape(bh, t, 1, -1),
        chunk=chunk)
    return y.reshape(bh, t, p)


@pytest.mark.parametrize("bh,t,p,n,chunk", [
    (2, 64, 16, 32, 16),
    (3, 128, 32, 16, 32),
    (1, 256, 64, 128, 128),      # mamba2-370m head geometry
    (4, 32, 8, 8, 8),
])
def test_ssd_kernel_matches_oracle(bh, t, p, n, chunk):
    x, a, b, c = _mk(bh, t, p, n)
    y = K.ssd(x, a, b, c, chunk=chunk, interpret=True)
    ref = _oracle(x, a, b, c, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_dtypes(dtype):
    x, a, b, c = _mk(2, 64, 16, 16, dtype=np.float32, seed=1)
    x, a, b, c = (z.astype(dtype) for z in (x, a, b, c))
    y = K.ssd(x, a, b, c, chunk=32, interpret=True)
    assert y.dtype == dtype
    ref = _oracle(x.astype(jnp.float32), a.astype(jnp.float32),
                  b.astype(jnp.float32), c.astype(jnp.float32), 32)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref), rtol=tol, atol=tol)


def test_state_carries_across_chunks():
    """Long-range dependence: token 0 must influence the last chunk's
    output (the state scratch carry — the kernel's Z-discipline)."""
    x, a, b, c = _mk(1, 128, 8, 8, seed=2)
    y1 = K.ssd(x, a, b, c, chunk=32, interpret=True)
    x2 = x.at[0, 0].add(10.0)
    y2 = K.ssd(x2, a, b, c, chunk=32, interpret=True)
    last = np.abs(np.asarray(y1[0, -32:]) - np.asarray(y2[0, -32:]))
    assert last.max() > 1e-6, "state did not carry across chunks"


def test_vmem_budget():
    assert K.vmem_bytes(128, 64, 128) < 16 * 2 ** 20


def test_ssd_kernel_carries_its_name():
    """The kernel's ``name`` names it in the lowered program (interpret
    mode: Mosaic has no ``cumsum``, so it does not lower for the chip)."""
    import jax
    x, a, b, c = _mk(1, 64, 16, 16)
    text = jax.jit(lambda *z: K.ssd(*z, chunk=32, interpret=True)).lower(
        x, a, b, c).as_text(debug_info=True)
    assert '"ssd/pallas_call"' in text
