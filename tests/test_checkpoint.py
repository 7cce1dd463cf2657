"""Checkpoint/resume roundtrips (SURVEY.md section 5.4 modernization)."""

import numpy as np
import pytest

from stencil_tpu.utils.checkpoint import restore_domain, save_domain


def test_jacobi_checkpoint_resume(tmp_path):
    from stencil_tpu.models.jacobi import Jacobi3D

    n = 16
    a = Jacobi3D(n, n, n, mesh_shape=(2, 2, 2), dtype=np.float32)
    a.init()
    a.step()
    a.step()
    save_domain(a.dd, str(tmp_path / "ckpt"), step=2)
    a.step()
    want = a.temperature()

    b = Jacobi3D(n, n, n, mesh_shape=(2, 2, 2), dtype=np.float32)
    step, extra = restore_domain(b.dd, str(tmp_path / "ckpt"))
    assert step == 2
    assert extra == {}
    b.step()
    np.testing.assert_array_equal(b.temperature(), want)


def test_checkpoint_reshard_onto_different_mesh(tmp_path):
    """Restore onto a different mesh decomposition: the elastic-resume
    capability the reference lacks entirely (SURVEY.md section 5.3/5.4)."""
    from stencil_tpu.models.jacobi import Jacobi3D

    n = 16
    a = Jacobi3D(n, n, n, mesh_shape=(2, 2, 2), dtype=np.float32)
    a.init()
    a.step()
    save_domain(a.dd, str(tmp_path / "ckpt"), step=1)
    a.step()
    want = a.temperature()

    b = Jacobi3D(n, n, n, mesh_shape=(8, 1, 1), dtype=np.float32)
    step, _ = restore_domain(b.dd, str(tmp_path / "ckpt"))
    assert step == 1
    b.step()
    np.testing.assert_allclose(b.temperature(), want, atol=1e-6)


def test_checkpoint_rejects_mismatched_domain(tmp_path):
    from stencil_tpu.models.jacobi import Jacobi3D

    a = Jacobi3D(16, 16, 16, mesh_shape=(2, 2, 2), dtype=np.float32)
    a.init()
    save_domain(a.dd, str(tmp_path / "ckpt"), step=0)

    b = Jacobi3D(32, 16, 16, mesh_shape=(2, 2, 2), dtype=np.float32)
    with pytest.raises(Exception):
        restore_domain(b.dd, str(tmp_path / "ckpt"))


def test_checkpoint_rejects_mismatched_dtype(tmp_path):
    """Restoring a float32 checkpoint into a float64 domain must fail
    with a clear error, not silently reinterpret the data."""
    from stencil_tpu.models.jacobi import Jacobi3D

    a = Jacobi3D(16, 16, 16, mesh_shape=(2, 2, 2), dtype=np.float32)
    a.init()
    save_domain(a.dd, str(tmp_path / "ckpt"), step=0)

    b = Jacobi3D(16, 16, 16, mesh_shape=(2, 2, 2), dtype=np.float64)
    with pytest.raises(Exception, match="dtype"):
        restore_domain(b.dd, str(tmp_path / "ckpt"))


def test_astaroth_checkpoint_with_accumulators(tmp_path):
    from stencil_tpu.models.astaroth import Astaroth, MhdParams

    prm = MhdParams()
    a = Astaroth(16, 16, 16, params=prm, mesh_shape=(2, 2, 2),
                 dtype=np.float64)
    a.init()
    a.step()
    save_domain(a.dd, str(tmp_path / "ckpt"), step=1, extra=a._w)
    a.step()
    want = {q: a.field(q) for q in ("lnrho", "uux", "ss")}

    b = Astaroth(16, 16, 16, params=prm, mesh_shape=(2, 2, 2),
                 dtype=np.float64)
    step, extra = restore_domain(b.dd, str(tmp_path / "ckpt"))
    assert step == 1
    assert set(extra) == set(a._w)
    b._w = extra
    b.step()
    for q in want:
        np.testing.assert_allclose(b.field(q), want[q], rtol=1e-12,
                                   atol=1e-14)


def test_checkpoint_bf16_cross_mesh_roundtrip(tmp_path):
    """bfloat16 fields survive save/restore bit-exactly, including
    onto a different mesh (orbax stores the raw bf16 interior; the
    restore path re-shards it like any other dtype)."""
    import jax.numpy as jnp

    from stencil_tpu.models.jacobi import Jacobi3D

    a = Jacobi3D(16, 16, 16, mesh_shape=(2, 2, 2), dtype=jnp.bfloat16)
    a.init()
    a.step()
    save_domain(a.dd, str(tmp_path / "ck"), step=1)
    a.step()
    want = np.asarray(a.temperature(), np.float32)

    b = Jacobi3D(16, 16, 16, mesh_shape=(1, 2, 4), dtype=jnp.bfloat16)
    step, _ = restore_domain(b.dd, str(tmp_path / "ck"))
    assert step == 1
    b.step()
    got = np.asarray(b.temperature(), np.float32)
    np.testing.assert_array_equal(got, want)


def test_validate_checkpoint_component():
    """Tenant/campaign ids become checkpoint directory components — an
    id like ``../other-tenant`` must be rejected before it touches the
    filesystem (multi-tenant serving, stencil_tpu/serving)."""
    from stencil_tpu.utils.checkpoint import validate_checkpoint_component

    for ok in ("tenant0", "a-b_c.d", "run..01", "UPPER", "0"):
        assert validate_checkpoint_component(ok) == ok
    for bad in ("", ".", "..", "a/b", "/abs", "a\\b", "..\\up",
                "x\x00y", "a\nb", "tab\tid", None, 7):
        with pytest.raises(ValueError):
            validate_checkpoint_component(bad)


def test_validate_checkpoint_component_names_the_kind():
    from stencil_tpu.utils.checkpoint import validate_checkpoint_component

    with pytest.raises(ValueError, match="tenant id"):
        validate_checkpoint_component("../up", kind="tenant id")
