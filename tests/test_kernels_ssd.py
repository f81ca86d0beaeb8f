"""SSD Pallas kernel: interpret-mode validation against the jnp oracle
(shape/dtype sweeps + hypothesis property test), and ssd_core wiring."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models.mamba2 import ssd_chunked, ssd_core
from repro.models.ssd import ssd_intra_pallas, ssd_intra_reference


def _inputs(key, bsz, nc, l, g, r, n, p, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (bsz, nc, l, g, r, p), dtype)
    ld = -jax.nn.softplus(jax.random.normal(ks[1], (bsz, nc, l, g, r),
                                            dtype))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (bsz, nc, l, g, r),
                                           dtype))
    b_ = jax.random.normal(ks[3], (bsz, nc, l, g, n), dtype)
    c_ = jax.random.normal(ks[4], (bsz, nc, l, g, n), dtype)
    s0 = jax.random.normal(ks[5], (bsz, nc, g, r, n, p), dtype) * 0.3
    return x, ld, dt, b_, c_, s0


@pytest.mark.parametrize("shape", [
    (1, 2, 16, 1, 4, 8, 8),
    (2, 1, 32, 2, 2, 16, 8),
    (1, 3, 8, 1, 8, 4, 16),
])
def test_ssd_kernel_matches_oracle(shape):
    args = _inputs(jax.random.PRNGKey(0), *shape)
    ref = ssd_intra_reference(*args)
    out = ssd_intra_pallas(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(l=st.sampled_from([8, 16, 32]),
       r=st.sampled_from([1, 2, 4]),
       n=st.sampled_from([4, 8]),
       p=st.sampled_from([4, 8, 16]),
       seed=st.integers(0, 2 ** 16))
def test_ssd_kernel_property_sweep(l, r, n, p, seed):
    args = _inputs(jax.random.PRNGKey(seed), 1, 2, l, 1, r, n, p)
    ref = ssd_intra_reference(*args)
    out = ssd_intra_pallas(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


def test_ssd_core_still_matches_sequential():
    """ssd_core (which now routes intra-chunk through the tagged oracle)
    must equal the step-by-step recurrence."""
    bsz, s, g, r, n, p = 2, 48, 1, 3, 8, 4
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (bsz, s, g, r, p))
    ld = -jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, g, r)))
    sc = jax.nn.softplus(jax.random.normal(ks[2], (bsz, s, g, r)))
    b_ = jax.random.normal(ks[3], (bsz, s, g, n))
    c_ = jax.random.normal(ks[4], (bsz, s, g, n))
    y, final = ssd_core(x, ld, sc, b_, c_, chunk=16)

    # sequential reference
    st_ = jnp.zeros((bsz, g, r, n, p))
    ys = []
    for t in range(s):
        dec = jnp.exp(ld[:, t])[..., None, None]
        upd = jnp.einsum("bgn,bgr,bgrp->bgrnp", b_[:, t], sc[:, t], x[:, t])
        st_ = st_ * dec + upd
        ys.append(jnp.einsum("bgn,bgrnp->bgrp", c_[:, t], st_))
    ref = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=2e-3,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(final), np.asarray(st_),
                               rtol=2e-3, atol=2e-4)
