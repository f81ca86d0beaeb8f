"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp ref oracle,
with hypothesis sweeps over shapes and dtypes."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.jacobi import jacobi_fused, jacobi_fused_ref


def _rand(shape, dtype=np.float32, seed=0, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape).astype(dtype) * scale)


def _pad_all(a, w):
    return jnp.pad(a, w, mode="wrap")  # periodic ghosts for testing


class TestUpdateVelocity:
    @pytest.mark.parametrize("shape", [(8, 8, 8), (16, 8, 8), (8, 16, 24)])
    def test_pallas_matches_ref(self, shape):
        vx, vy, vz = (_rand(shape, seed=s, scale=0.3) for s in (1, 2, 3))
        args = dict(dt=0.01, h=0.1, nu=0.05, fx=0.1, fy=0.0, fz=-0.2)
        pads = {k: _pad_all(a, 1) for k, a in zip("xyz", (vx, vy, vz))}
        got = ops.update_velocity(
            pads["x"], pads["y"], pads["z"], template="3DBLOCK",
            interpret=True, tile=(4, 4, 8), **args)
        want = ref.update_velocity(pads["x"], pads["y"], pads["z"], **args)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-5, atol=2e-5)

    def test_momentum_conserved_periodic_no_visc(self):
        # with periodic ghosts, flux-form advection conserves momentum sums
        shape = (8, 8, 8)
        vx, vy, vz = (_rand(shape, seed=s, scale=0.3) for s in (4, 5, 6))
        pads = [_pad_all(a, 1) for a in (vx, vy, vz)]
        nvx, nvy, nvz = ref.update_velocity(*pads, dt=0.01, h=0.5, nu=0.0)
        for new, old in zip((nvx, nvy, nvz), (vx, vy, vz)):
            np.testing.assert_allclose(float(new.sum()), float(old.sum()),
                                       rtol=1e-4, atol=1e-4)


class TestDivergenceProjection:
    def test_divergence_of_constant_is_zero(self):
        c = jnp.full((10, 10, 10), 3.7)
        d = ops.divergence(c, c, c, template="JNP", h=0.1)
        np.testing.assert_allclose(np.asarray(d), 0.0, atol=1e-5)

    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    def test_pallas_matches_ref(self, dtype):
        shape = (8, 8, 8)
        vx, vy, vz = (_rand(shape, dtype, seed=s) for s in (7, 8, 9))
        # divergence wants (1,0) lo-side ghosts
        pads = [jnp.pad(a, ((1, 0), (1, 0), (1, 0)), mode="wrap")
                for a in (vx, vy, vz)]
        got = ops.apply_kernel("DIVERGENCE", dict(zip(("vx", "vy", "vz"), pads)),
                               template="3DBLOCK", interpret=True,
                               tile=(4, 4, 8), h=0.25)["div"]
        want = ref.divergence(*pads, h=0.25)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_projection_reduces_divergence(self):
        # one exact-Poisson projection on a periodic grid must kill divergence
        n, h = 16, 1.0 / 16
        vx, vy, vz = (_rand((n, n, n), np.float64, seed=s, scale=0.1)
                      for s in (10, 11, 12))
        div = ref.divergence(*[jnp.pad(a, ((1, 0),) * 3, mode="wrap")
                               for a in (vx, vy, vz)], h=h)
        # solve lap p = div/dt exactly via FFT (periodic)
        dt = 1.0
        k = np.fft.fftfreq(n) * n
        kx, ky, kz = np.meshgrid(k, k, k, indexing="ij")
        denom = (2 * (np.cos(2 * np.pi * kx / n) - 1)
                 + 2 * (np.cos(2 * np.pi * ky / n) - 1)
                 + 2 * (np.cos(2 * np.pi * kz / n) - 1)) / h ** 2
        denom[0, 0, 0] = 1.0
        ph = np.fft.fftn(np.asarray(div) / dt) / denom
        ph[0, 0, 0] = 0.0
        p = jnp.asarray(np.real(np.fft.ifftn(ph)))
        p_pad = jnp.pad(p, ((0, 1),) * 3, mode="wrap")
        nvx, nvy, nvz = ref.project_velocity(vx, vy, vz, p_pad, dt=dt, h=h)
        div2 = ref.divergence(*[jnp.pad(a, ((1, 0),) * 3, mode="wrap")
                                for a in (nvx, nvy, nvz)], h=h)
        # f32 roundoff floor (x64 is off in this session)
        assert float(jnp.abs(div2).max()) < 1e-6 * float(jnp.abs(div).max())


class TestJacobi:
    def test_single_sweep_pallas_vs_ref(self):
        p = _rand((10, 10, 10), seed=13)
        rhs = _rand((8, 8, 8), seed=14)
        got = ops.jacobi_pressure(jnp.asarray(p), rhs, template="3DBLOCK",
                                  interpret=True, tile=(4, 4, 8), h=0.1,
                                  omega=0.8)
        want = ref.jacobi_pressure(p, rhs, h=0.1, omega=0.8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("sweeps", [1, 2, 3])
    def test_fused_equals_iterated(self, sweeps):
        """k fused communication-avoiding sweeps == k plain sweeps."""
        n, k = 8, sweeps
        p = _rand((n + 2 * k,) * 3, seed=15)
        rhs = _rand((n + 2 * k,) * 3, seed=16)
        fused = jacobi_fused_ref(p, rhs, h=0.2, omega=0.9, sweeps=k)
        # iterate single sweeps, shrinking manually
        cur, r = p, rhs
        for _ in range(k):
            cur = ref.jacobi_pressure(cur, r[1:-1, 1:-1, 1:-1], h=0.2, omega=0.9)
            r = r[1:-1, 1:-1, 1:-1]
        np.testing.assert_allclose(np.asarray(fused), np.asarray(cur),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("sweeps", [1, 2])
    def test_fused_pallas_vs_ref(self, sweeps):
        n, k = 8, sweeps
        p = _rand((n + 2 * k,) * 3, seed=17)
        rhs = _rand((n + 2 * k,) * 3, seed=18)
        got = jacobi_fused(p, rhs, h=0.3, sweeps=k, tile=(4, 4, 4),
                           interpret=True)
        want = jacobi_fused_ref(p, rhs, h=0.3, sweeps=k)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_jacobi_converges_on_poisson(self):
        # solve lap p = rhs on periodic grid; residual must shrink
        n, h = 16, 1.0 / 16
        rng = np.random.RandomState(3)
        rhs = rng.randn(n, n, n).astype(np.float32)
        rhs -= rhs.mean()  # compatibility condition
        rhs = jnp.asarray(rhs)
        p = jnp.zeros((n, n, n))

        def residual(p):
            lap = ref.laplacian(_pad_all(p, 1), h)
            return float(jnp.abs(lap - rhs).max())

        r0 = residual(p)
        for _ in range(200):
            p = ref.jacobi_pressure(_pad_all(p, 1), rhs, h=h, omega=0.9)
            p = p - p.mean()
        assert residual(p) < 0.05 * r0

