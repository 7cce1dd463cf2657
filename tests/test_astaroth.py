"""Astaroth MHD integrator tests.

Strategy (SURVEY.md section 4): distributed-vs-single-device numerical
parity (the same XLA program on a 1-device mesh is the dense oracle),
finiteness/stability over iterations, conf-file loading, and
initial-condition pinning against the reference's formulas.
"""

import numpy as np
import pytest

import jax

from stencil_tpu.geometry import Dim3
from stencil_tpu.models.astaroth import (FIELDS, Astaroth, MhdParams,
                                         _hash_field, _radial_explosion)
from stencil_tpu.parallel.methods import Method


def make_pair(size=(16, 16, 16), iters=2, dtype=np.float64):
    """Run the same problem on a 1-device mesh and a 2x2x2 mesh."""
    single = Astaroth(*size, mesh_shape=(1, 1, 1), dtype=dtype,
                      devices=jax.devices()[:1])
    multi = Astaroth(*size, mesh_shape=(2, 2, 2), dtype=dtype)
    for m in (single, multi):
        m.init()
        for _ in range(iters):
            m.step()
    return single, multi


class TestDistributedParity:
    @pytest.mark.slow
    def test_multi_matches_single_device(self):
        single, multi = make_pair()
        for q in FIELDS:
            a = single.field(q)
            b = multi.field(q)
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-12, err_msg=q)

    @pytest.mark.slow
    def test_slab_method_matches(self):
        size = (16, 16, 16)
        a = Astaroth(*size, mesh_shape=(2, 2, 2), dtype=np.float64,
                     methods=Method.PpermutePacked)
        b = Astaroth(*size, mesh_shape=(2, 2, 2), dtype=np.float64,
                     methods=Method.PpermuteSlab)
        for m in (a, b):
            m.init()
            m.step()
        for q in FIELDS:
            np.testing.assert_array_equal(a.field(q), b.field(q), err_msg=q)


class TestStability:
    @pytest.mark.slow
    @pytest.mark.parametrize("thinz,pair", [
        ("1", "0"), ("0", "0"),
        # fused substep-0+1 kernel (STENCIL_MHD_PAIR=1 opt-in), under
        # both window plans (tiled-z at rr=6 slices the ESUB tile
        # differently than the rr=3 single-substep path)
        ("1", "1"), ("0", "1")])
    def test_wrap_megakernel_matches_xla(self, thinz, pair, monkeypatch):
        """The fused Pallas substep megakernel (ops/pallas_mhd.py,
        single-chip fast path) against the slicing formulation — under
        BOTH window plans (exact-radius thin-z default and the
        STENCIL_MHD_THINZ=0 tiled-z A/B control) and with the fused
        substep-0+1 pair kernel opted in."""
        monkeypatch.setenv("STENCIL_MHD_THINZ", thinz)
        monkeypatch.setenv("STENCIL_MHD_PAIR", pair)
        size = (16, 16, 16)
        a = Astaroth(*size, mesh_shape=(1, 1, 1), dtype=np.float64,
                     devices=jax.devices()[:1], kernel="xla")
        b = Astaroth(*size, mesh_shape=(1, 1, 1), dtype=np.float64,
                     devices=jax.devices()[:1], kernel="wrap")
        for m in (a, b):
            m.init()
            m.step()
            m.step()
        for q in FIELDS:
            np.testing.assert_allclose(b.field(q), a.field(q),
                                       rtol=1e-11, atol=1e-13, err_msg=q)

    def test_fields_stay_finite(self):
        m = Astaroth(16, 16, 16, mesh_shape=(2, 2, 2), dtype=np.float64)
        m.init()
        m.run(10)
        for q in FIELDS:
            v = m.field(q)
            assert np.all(np.isfinite(v)), q

    def test_fields_actually_evolve(self):
        m = Astaroth(16, 16, 16, mesh_shape=(2, 2, 2), dtype=np.float64)
        m.init()
        before = {q: m.field(q).copy() for q in ("lnrho", "uux", "ss")}
        # dt is 1e-8 (reference loads AC_dt=1e-8) so changes are small
        # but must be nonzero
        m.step()
        changed = sum(not np.array_equal(before[q], m.field(q))
                      for q in before)
        assert changed == len(before)


class TestDeadWElision:
    """alpha_0 == 0 makes the incoming w dead at substep 0 and the
    outgoing w dead at substep 2 (the next iteration restarts the
    recurrence); the kernels elide those HBM sweeps on request
    (w=None / write_w=False). Dropping the 0*w term changes how the
    compiler fuses the update (FMA contraction), so fields match to
    ~1 ulp rather than bit-for-bit; write_w elision IS bit-exact."""

    @staticmethod
    def _mk_state(seed=7, size=(16, 16, 16)):
        rng = np.random.default_rng(seed)
        f = {q: np.asarray(rng.normal(0.0, 0.1, size), np.float64)
             for q in FIELDS}
        wz = {q: np.zeros(size, np.float64) for q in FIELDS}
        return f, wz

    @pytest.mark.slow
    def test_wrap_kernel_elision_bit_identical(self):
        from stencil_tpu.ops.pallas_mhd import mhd_substep_wrap_pallas

        prm = MhdParams()
        f, wz = self._mk_state()
        fa, wa = mhd_substep_wrap_pallas(f, wz, 0, prm, prm.dt)
        fb, wb = mhd_substep_wrap_pallas(f, None, 0, prm, prm.dt)
        for q in FIELDS:
            np.testing.assert_allclose(np.asarray(fa[q]),
                                       np.asarray(fb[q]),
                                       rtol=1e-14, atol=1e-18,
                                       err_msg=q)
            np.testing.assert_array_equal(np.asarray(wa[q]),
                                          np.asarray(wb[q]), err_msg=q)
        fc, wc = mhd_substep_wrap_pallas(fb, wb, 2, prm, prm.dt)
        fd, wd = mhd_substep_wrap_pallas(fb, wb, 2, prm, prm.dt,
                                         write_w=False)
        assert wd is None
        assert wc is not None
        for q in FIELDS:
            np.testing.assert_array_equal(np.asarray(fc[q]),
                                          np.asarray(fd[q]), err_msg=q)

    def test_wrap_kernel_w_none_rejected_midstep(self):
        from stencil_tpu.ops.pallas_mhd import mhd_substep_wrap_pallas

        prm = MhdParams()
        f, _ = self._mk_state()
        with pytest.raises(AssertionError):
            mhd_substep_wrap_pallas(f, None, 1, prm, prm.dt)


class TestParams:
    def test_defaults_match_reference_conf(self):
        p = MhdParams()
        assert p.nu_visc == 5e-3
        assert p.mu0 == 1.4
        assert p.gamma == 0.5
        assert p.cs2_sound == 1.0

    def test_from_conf_roundtrip(self, tmp_path):
        conf = tmp_path / "a.conf"
        conf.write_text("""
// comment
AC_nu_visc = 1e-2
AC_mu0 = 2.0   // inline comment
/* block
comment */
AC_gamma = 0.6
AC_dsx = 0.1
""")
        p = MhdParams.from_conf(str(conf))
        assert p.nu_visc == 1e-2
        assert p.mu0 == 2.0
        assert p.gamma == 0.6
        assert p.dsx == 0.1
        assert p.dsy == 0.04908738521  # untouched default


class TestInitialConditions:
    def test_hash_field_range_and_determinism(self):
        a = _hash_field((8, 8, 8))
        b = _hash_field((8, 8, 8))
        np.testing.assert_array_equal(a, b)
        assert a.min() >= -1.0 and a.max() <= 1.0
        assert a.std() > 0.1  # actually random-ish

    def test_radial_explosion_shell(self):
        prm = MhdParams()
        ux, uy, uz = _radial_explosion(Dim3(64, 64, 64), prm)
        speed = np.sqrt(ux ** 2 + uy ** 2 + uz ** 2)
        # gaussian shell: peak speed ~ampl at radius 0.8 from origin
        assert speed.max() == pytest.approx(1.0, abs=0.05)
        # velocity points radially away from origin (0.01, 32dy, 50dz)
        oz, oy, ox = 50 * prm.dsz, 32 * prm.dsy, 0.01
        z, y, x = 40, 40, 20
        r = np.array([x * prm.dsx - ox, y * prm.dsy - oy, z * prm.dsz - oz])
        u = np.array([ux[z, y, x], uy[z, y, x], uz[z, y, x]])
        if np.linalg.norm(u) > 1e-12:
            cos = np.dot(r, u) / np.linalg.norm(r) / np.linalg.norm(u)
            assert cos == pytest.approx(1.0, abs=1e-9)


class TestBfloat16:
    """bfloat16 MHD: fields stored half-width, RHS computed in float32
    (ops/pallas_mhd.compute_dtype) — the TPU bf16-in-memory /
    f32-accumulate idiom. Parity is against the float32 XLA oracle at
    bf16 storage tolerance (~2^-8 per-step rounding), since the Pallas
    path computes on exactly the f32 promotions of the stored values.
    Reference analog: the float/double templating the reference builds
    with (e.g. astaroth typed on AcReal); bf16 is the TPU-native
    half-traffic point on that axis."""

    @staticmethod
    def _f32_oracle(size, iters=2):
        a = Astaroth(*size, mesh_shape=(1, 1, 1), dtype=np.float32,
                     devices=jax.devices()[:1], kernel="xla")
        a.init()
        for _ in range(iters):
            a.step()
        return {q: np.asarray(a.field(q), np.float32) for q in FIELDS}

    @staticmethod
    def _assert_close(got_model, ref, label, tol=3e-2):
        import jax.numpy as jnp
        for q in FIELDS:
            raw = got_model.field(q)
            assert raw.dtype == jnp.bfloat16, (label, q, raw.dtype)
            got = np.asarray(raw, np.float32)
            scale = max(np.abs(ref[q]).max(), 1e-30)
            err = np.abs(got - ref[q]).max() / scale
            assert err < tol, (label, q, err)

    @pytest.mark.slow
    @pytest.mark.parametrize("thinz,pair", [
        ("1", "0"), ("0", "0"), ("1", "1")])
    def test_wrap_bf16_matches_f32_oracle(self, thinz, pair, monkeypatch):
        import jax.numpy as jnp
        monkeypatch.setenv("STENCIL_MHD_THINZ", thinz)
        monkeypatch.setenv("STENCIL_MHD_PAIR", pair)
        size = (32, 32, 32)
        ref = self._f32_oracle(size)
        b = Astaroth(*size, mesh_shape=(1, 1, 1), dtype=jnp.bfloat16,
                     devices=jax.devices()[:1], kernel="wrap")
        assert b.kernel_path == "wrap"
        b.init()
        b.step()
        b.step()
        self._assert_close(b, ref, f"wrap thinz={thinz} pair={pair}")

    @pytest.mark.slow
    @pytest.mark.parametrize("pair", ["0", "1"])
    def test_halo_bf16_matches_f32_oracle(self, pair, monkeypatch):
        """Multi-device slab layout: 16-row (bf16-tile) slab exchange +
        the halo megakernel, on an x-unsharded (1,2,2) mesh."""
        import jax.numpy as jnp
        monkeypatch.setenv("STENCIL_MHD_PAIR", pair)
        size = (32, 32, 32)
        ref = self._f32_oracle(size)
        c = Astaroth(*size, mesh_shape=(1, 2, 2), dtype=jnp.bfloat16,
                     devices=jax.devices()[:4], kernel="halo")
        assert c.kernel_path == "halo"
        c.init()
        c.step()
        c.step()
        self._assert_close(c, ref, f"halo pair={pair}")

    def test_xla_bf16_matches_f32_oracle(self):
        """The XLA fallback path must apply the same storage/compute
        split (bf16 in HBM, f32 RHS evaluation) as the Pallas paths —
        a bf16-evaluated 6th-order RHS would drift far beyond storage
        tolerance."""
        import jax.numpy as jnp
        size = (32, 32, 32)
        ref = self._f32_oracle(size)
        b = Astaroth(*size, mesh_shape=(2, 2, 2), dtype=jnp.bfloat16,
                     kernel="xla")
        b.init()
        b.step()
        b.step()
        self._assert_close(b, ref, "xla bf16")

    def test_bf16_overlap_selects_rdma_path(self):
        """bf16 + overlap takes the in-kernel RDMA path like f32 (the
        16-row slab tiling now runs through ops/pallas_mhd_overlap)."""
        import jax.numpy as jnp
        m = Astaroth(32, 32, 32, mesh_shape=(1, 2, 2),
                     dtype=jnp.bfloat16, devices=jax.devices()[:4],
                     kernel="halo", overlap=True)
        assert m.kernel_path == "halo-overlap"

    @pytest.mark.slow
    @pytest.mark.parametrize("pair", ["0", "1"])
    def test_overlap_bf16_matches_f32_oracle(self, pair, monkeypatch):
        """The overlapped (in-kernel RDMA) path in bf16, alone and
        composed with the substep-0+1 pair."""
        import jax.numpy as jnp
        monkeypatch.setenv("STENCIL_MHD_PAIR", pair)
        size = (32, 32, 32)
        ref = self._f32_oracle(size)
        c = Astaroth(*size, mesh_shape=(1, 2, 2), dtype=jnp.bfloat16,
                     devices=jax.devices()[:4], kernel="halo",
                     overlap=True)
        assert c.kernel_path == "halo-overlap"
        c.init()
        c.step()
        c.step()
        self._assert_close(c, ref, f"halo-overlap pair={pair}")
