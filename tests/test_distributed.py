"""DistributedDomain orchestrator tests: the end-to-end ripple oracle
through the public API (mirrors reference
test/test_cuda_mpi_distributed_domain.cu and test_exchange.cu)."""

import numpy as np
import pytest

import jax

from stencil_tpu.distributed import DistributedDomain
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.parallel.methods import Method
from stencil_tpu.placement import PlacementStrategy

RIPPLE = [1.0, 0.25, 0.5, 0.75]


def ripple_grid(size: Dim3) -> np.ndarray:
    z, y, x = np.meshgrid(np.arange(size.z), np.arange(size.y),
                          np.arange(size.x), indexing="ij")
    r = np.array(RIPPLE)
    return ((x + r[x % 4]) + (y + r[y % 4]) + (z + r[z % 4])).astype(np.float64)


def check_dd_halos(dd: DistributedDomain, name: str, oracle: np.ndarray):
    """Every halo cell of every shard must equal oracle[wrap(global)]."""
    from stencil_tpu.local_domain import raw_size
    dim = dd.placement.dim()
    local = dd.local_size
    pr = raw_size(local, dd.radius)
    lo = dd.radius.pad_lo()
    host = np.asarray(dd.curr[name])
    gs = dd.size
    for bz in range(dim.z):
        for by in range(dim.y):
            for bx in range(dim.x):
                blk = host[bz * pr.z:(bz + 1) * pr.z,
                           by * pr.y:(by + 1) * pr.y,
                           bx * pr.x:(bx + 1) * pr.x]
                for lz in range(pr.z):
                    for ly in range(pr.y):
                        for lx in range(pr.x):
                            gx = (bx * local.x + lx - lo.x) % gs.x
                            gy = (by * local.y + ly - lo.y) % gs.y
                            gz = (bz * local.z + lz - lo.z) % gs.z
                            assert blk[lz, ly, lx] == pytest.approx(
                                oracle[gz, gy, gx]), (
                                f"block ({bx},{by},{bz}) local ({lx},{ly},{lz})")


@pytest.mark.parametrize("strategy", [PlacementStrategy.Trivial,
                                      PlacementStrategy.NodeAware,
                                      PlacementStrategy.IntraNodeRandom])
def test_exchange_oracle_8dev(strategy):
    dd = DistributedDomain(8, 8, 8)
    dd.set_radius(1)
    dd.add_data("q0", np.float64)
    dd.set_placement(strategy)
    dd.realize()
    oracle = ripple_grid(dd.size)
    dd.set_interior("q0", oracle)
    dd.exchange()
    check_dd_halos(dd, "q0", oracle)


def test_exchange_multi_quantity_methods():
    for method in (Method.PpermuteSlab, Method.PpermutePacked):
        dd = DistributedDomain(8, 8, 8)
        dd.set_radius(2)
        dd.set_methods(method)
        dd.add_data("a", np.float32)
        dd.add_data("b", np.float64)
        dd.realize()
        oracle = ripple_grid(dd.size)
        dd.set_interior("a", oracle.astype(np.float32))
        dd.set_interior("b", oracle * 3.0)
        dd.exchange()
        check_dd_halos(dd, "b", oracle * 3.0)


def test_roundtrip_interior():
    dd = DistributedDomain(8, 8, 8)
    dd.set_radius(1)
    dd.add_data("q", np.float64)
    dd.realize()
    oracle = ripple_grid(dd.size)
    dd.set_interior("q", oracle)
    np.testing.assert_array_equal(dd.interior_to_host("q"), oracle)


def test_swap_double_buffer():
    dd = DistributedDomain(8, 8, 8)
    dd.set_radius(1)
    dd.add_data("q", np.float64)
    dd.realize()
    oracle = ripple_grid(dd.size)
    dd.set_interior("q", oracle)
    dd.swap()
    assert float(dd.interior_to_host("q").max()) == 0.0
    dd.swap()
    np.testing.assert_array_equal(dd.interior_to_host("q"), oracle)


def test_interior_exterior_queries():
    dd = DistributedDomain(8, 8, 8)
    dd.set_radius(2)
    dd.add_data("q", np.float32)
    dd.realize()
    inters = dd.get_interior()
    exts = dd.get_exterior()
    assert len(inters) == 8 and len(exts) == 8
    local_vol = dd.local_size.flatten()
    for i in range(8):
        vol = inters[i].extent().flatten() + sum(
            r.extent().flatten() for r in exts[i])
        assert vol == local_vol


def test_plan_files(tmp_path):
    dd = DistributedDomain(8, 8, 8)
    dd.set_radius(1)
    dd.add_data("q", np.float32)
    dd.set_output_prefix(str(tmp_path) + "/")
    dd.realize()
    plan = (tmp_path / "plan.txt").read_text()
    assert "mesh" in plan and "bytes per shard" in plan
    mat = np.loadtxt(tmp_path / "comm_matrix.txt")
    assert mat.shape == (8, 8)
    # radius-1 f32, 4^3 local: each face message is 4*4*1*4 bytes = 64
    assert mat[0, 1] > 0
    # per-message detail (reference: src/stencil.cu:523-637): one line
    # per planned cross-shard message, consistent with the matrix
    msgs = [l for l in plan.splitlines() if l.startswith("message ")]
    assert any(l.startswith("message 0 -> 1 ") and l.endswith("B")
               for l in msgs), msgs[:3]
    m01 = sum(int(l.split(":")[1].split()[0]) for l in msgs
              if l.startswith("message 0 -> 1 "))
    assert m01 == mat[0, 1], (m01, mat[0, 1])
    assert np.all(mat.diagonal() == 0)


def test_exchange_bytes_counters():
    dd = DistributedDomain(8, 8, 8)
    dd.set_radius(1)
    dd.add_data("q", np.float32)
    dd.realize()
    b = dd.exchange_bytes_per_axis()
    # 2x2x2 mesh, local 4^3 padded to 6^3: x axis moves 2*6*6*4 bytes
    assert b["x"] == 2 * 6 * 6 * 4
    assert dd.exchange_bytes_total() == sum(b.values()) * 8


def test_paraview_dump(tmp_path):
    dd = DistributedDomain(4, 4, 4)
    dd.set_radius(1)
    dd.set_mesh_shape((2, 2, 2))
    dd.add_data("q", np.float64)
    dd.realize()
    oracle = ripple_grid(dd.size)
    dd.set_interior("q", oracle)
    dd.write_paraview(str(tmp_path) + "/out")
    files = sorted(tmp_path.glob("out*.txt"))
    assert len(files) == 8
    header = files[0].read_text().splitlines()[0]
    assert header == "Z,Y,X,q"


def test_placement_order_survives_mesh():
    # regression: make_mesh must not re-sort an explicit device order,
    # else QAP/random placements silently never take effect
    dd = DistributedDomain(8, 8, 8)
    dd.set_radius(1)
    dd.add_data("q", np.float32)
    dd.set_placement(PlacementStrategy.IntraNodeRandom)
    dd.realize()
    part = dd.placement.part
    for i in range(8):
        idx = part.dimensionize(i)
        want = dd.placement.get_device(idx)
        got = dd.mesh.devices[idx.x, idx.y, idx.z]
        assert want == got, (i, want, got)


def test_rejects_bad_configs():
    dd = DistributedDomain(7, 7, 7)
    dd.set_radius(1)
    dd.add_data("q", np.float32)
    dd.realize()  # 7^3 over 8 devices: uneven (+-1) subdomains
    assert dd.rem != (0, 0, 0)

    dd = DistributedDomain(4, 4, 4)
    dd.set_radius(1)
    dd.add_data("q", np.float32)
    dd.set_mesh_shape((2, 2, 1))  # 4 != 8 devices
    with pytest.raises(ValueError):
        dd.realize()

    dd = DistributedDomain(8, 8, 8)
    dd.set_radius(8)  # radius larger than 4^3 subdomain
    dd.add_data("q", np.float32)
    with pytest.raises(ValueError):
        dd.realize()


def test_fast_path_exchange_stats():
    """The models' per-iteration exchange accounting must (a) agree
    between the pair and sequential MHD halo paths (same wire bytes,
    the pair's whole point), (b) match interior_slab_bytes exactly,
    and (c) produce a positive standalone timing — the honest fast-path
    stats the orchestrator counters cannot provide (reference:
    src/stencil.cu:1005-1008,1174-1181)."""
    import jax

    from stencil_tpu.models.astaroth import FIELDS, Astaroth
    from stencil_tpu.models.jacobi import Jacobi3D
    from stencil_tpu.parallel.exchange import interior_slab_bytes
    from stencil_tpu.parallel.mesh import mesh_dim

    a = Astaroth(16, 8, 16, mesh_shape=(1, 1, 2), dtype=np.float64,
                 devices=jax.devices()[:2], kernel="halo", exchange_every=2)
    b = Astaroth(16, 8, 16, mesh_shape=(1, 1, 2), dtype=np.float64,
                 devices=jax.devices()[:2], kernel="halo")
    sa, sb = a.exchange_stats(), b.exchange_stats()
    assert (sa["rounds_per_iteration"], sb["rounds_per_iteration"]) == (2.0, 3.0)
    assert sa["bytes_per_iteration"] == sb["bytes_per_iteration"]
    counts = mesh_dim(b.dd.mesh)
    local = b.dd.local_size
    per = interior_slab_bytes((local.z, local.y, local.x), counts, 3, 8,
                              y_z_extended=True)
    assert sb["bytes_per_iteration"] == 3 * per * 2 * len(FIELDS)
    assert b.measure_exchange_seconds(reps=2) > 0

    j = Jacobi3D(16, 16, 16, mesh_shape=(1, 2, 2), dtype=np.float32,
                 devices=jax.devices()[:4], kernel="halo")
    js = j.exchange_stats()
    assert js["path"] == "halo"
    assert js["rounds_per_iteration"] == 0.5     # 2-step groups
    assert j.measure_exchange_seconds(reps=2) > 0
    w = Jacobi3D(16, 16, 16, mesh_shape=(1, 1, 1),
                 devices=jax.devices()[:1], kernel="wrap",
                 dtype=np.float32)
    assert w.exchange_stats()["bytes_per_iteration"] == 0
    assert w.measure_exchange_seconds() == 0.0
