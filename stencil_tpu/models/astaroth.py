"""Astaroth-parity MHD integrator: 8 fields, 6th order, RK3.

TPU-native re-implementation of the reference's astaroth mini-app
("rough approximation of astaroth using the stencil library",
reference: astaroth/astaroth.cu:1-3): 8 scalar fields — lnrho, uu(x,y,z),
aa(x,y,z), entropy (astaroth/astaroth.cu:19-27) — advanced by a
Williamson (1980) 3-step low-storage Runge-Kutta
(astaroth/integration.cuh:14-38) with 6th-order central + cross
derivatives (radius 3 <-> STENCIL_ORDER 6, astaroth/astaroth.h:8-9) and
periodic boundaries.

Physics (reference: astaroth/user_kernels.h:383-453):
* continuity:  d lnrho/dt = -u . grad lnrho - div u
* momentum:    du/dt = -(u.grad)u - cs2 (grad ss / cp + grad lnrho)
               + (1/rho) j x B + nu (lap u + (1/3) grad div u
               + 2 S . grad lnrho) + zeta grad div u
* induction:   dA/dt = u x B + eta lap A           (B = curl A)
* entropy:     d ss/dt = -u . grad ss + (1/(rho T)) [eta mu0 j.j
               + 2 rho nu S:S + zeta rho (div u)^2] + heat conduction
with j = (1/mu0)(grad div A - lap A),
cs2 = cs2_sound exp(gamma ss/cp + (gamma-1)(lnrho - lnrho0)).

Design notes vs the reference:
* One iteration = 3 substeps; each substep is exchange + rates + RK3
  update fused into a single shard_map'ped XLA program over the 3D mesh.
* The reference mini-app never swaps its in/out buffers between
  substeps, so substeps 1-2 re-read the original state
  (astaroth/astaroth.cu:643-649 swaps once per iteration) — a quirk of
  the mini-app, not of Astaroth. Here the 2N-storage scheme is applied
  correctly (w = alpha w + dt F(u); u += beta w per substep), which has
  identical per-iteration comm/compute cost (3 exchanges + 3 stencil
  sweeps).
* dtype is configurable: float32 is the TPU-native choice; float64
  (the reference's AcReal) works on CPU for validation.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..distributed import DistributedDomain
from ..geometry import Dim3, Dim3Like, Radius
from ..local_domain import zyx_shape
from ..ops.fd6 import RADIUS, FieldData
from ..ops.stencil_kernels import read_interior, write_interior
from ..parallel.exchange import dispatch_exchange
from ..parallel.mesh import mesh_dim
from ..parallel.methods import Method, pick_method
from ..telemetry.spans import BLOCK, KERNEL, RUN, get_tracer, layer_scope
from ..utils.config import load_config

FIELDS = ("lnrho", "uux", "uuy", "uuz", "ax", "ay", "az", "ss")

# Williamson (1980) low-storage RK3 (reference: integration.cuh:20-21)
RK3_ALPHA = (0.0, -5.0 / 9.0, -153.0 / 128.0)
RK3_BETA = (1.0 / 3.0, 15.0 / 16.0, 8.0 / 15.0)


@dataclasses.dataclass
class MhdParams:
    """Physical constants (reference: astaroth/astaroth.conf defaults)."""

    dsx: float = 0.04908738521
    dsy: float = 0.04908738521
    dsz: float = 0.04908738521
    dt: float = 1e-8            # astaroth.cu:578 loads AC_dt = 1e-8
    nu_visc: float = 5e-3
    cs_sound: float = 1.0
    zeta: float = 0.01
    eta: float = 5e-3
    mu0: float = 1.4
    cp_sound: float = 1.0
    gamma: float = 0.5
    lnT0: float = 1.2
    lnrho0: float = 1.3

    @property
    def cs2_sound(self) -> float:
        return self.cs_sound * self.cs_sound

    @classmethod
    def from_conf(cls, path: str) -> "MhdParams":
        """Load from an astaroth.conf-style file (reference:
        astaroth/astaroth_utils.cu acLoadConfig)."""
        ints, reals = load_config(path)
        m = {"AC_dsx": "dsx", "AC_dsy": "dsy", "AC_dsz": "dsz",
             "AC_dt": "dt", "AC_nu_visc": "nu_visc",
             "AC_cs_sound": "cs_sound", "AC_zeta": "zeta", "AC_eta": "eta",
             "AC_mu0": "mu0", "AC_cp_sound": "cp_sound",
             "AC_gamma": "gamma", "AC_lnT0": "lnT0", "AC_lnrho0": "lnrho0"}
        kw = {}
        for src, dst in m.items():
            if src in reals:
                kw[dst] = reals[src]
            elif src in ints:
                kw[dst] = float(ints[src])
        return cls(**kw)


def _fast_dtype_ok(dtype) -> bool:
    """True when the fused Pallas kernel paths support ``dtype``:
    float32 (native) and bfloat16 (stored half-width, computed in
    float32 — see ops/pallas_mhd.compute_dtype). float64 falls back
    to the XLA path (TPU f64 is emulated anyway)."""
    import jax.numpy as jnp
    return np.dtype(dtype) in (np.dtype(np.float32),
                               np.dtype(jnp.bfloat16))


def _dot(a, b):
    return a[0] * b[0] + a[1] * b[1] + a[2] * b[2]


def _cross(a, b):
    return (a[1] * b[2] - a[2] * b[1],
            a[2] * b[0] - a[0] * b[2],
            a[0] * b[1] - a[1] * b[0])


@layer_scope(KERNEL)
def mhd_rates(f: Dict[str, FieldData], prm: MhdParams, dtype):
    """Right-hand sides of all 8 equations at the current state
    (reference: astaroth/user_kernels.h:383-453)."""

    def c(v):
        return jnp.asarray(v, dtype)

    lnrho, ss = f["lnrho"], f["ss"]
    uu = (f["uux"], f["uuy"], f["uuz"])
    aa = (f["ax"], f["ay"], f["az"])

    u = tuple(q.value for q in uu)
    grad_lnrho = lnrho.gradient
    grad_ss = ss.gradient

    div_u = uu[0].grad(0) + uu[1].grad(1) + uu[2].grad(2)

    # continuity (user_kernels.h continuity)
    d_lnrho = -_dot(u, grad_lnrho) - div_u

    # traceless rate-of-strain tensor S (user_kernels.h stress_tensor)
    third = c(1.0 / 3.0)
    S = [[None] * 3 for _ in range(3)]
    S[0][0] = c(2.0 / 3.0) * uu[0].grad(0) - third * (uu[1].grad(1) + uu[2].grad(2))
    S[1][1] = c(2.0 / 3.0) * uu[1].grad(1) - third * (uu[0].grad(0) + uu[2].grad(2))
    S[2][2] = c(2.0 / 3.0) * uu[2].grad(2) - third * (uu[0].grad(0) + uu[1].grad(1))
    S[0][1] = S[1][0] = c(0.5) * (uu[0].grad(1) + uu[1].grad(0))
    S[0][2] = S[2][0] = c(0.5) * (uu[0].grad(2) + uu[2].grad(0))
    S[1][2] = S[2][1] = c(0.5) * (uu[1].grad(2) + uu[2].grad(1))

    # current j = (1/mu0)(grad div A - lap A); B = curl A
    grad_div_a = tuple(
        aa[0].hess(i, 0) + aa[1].hess(i, 1) + aa[2].hess(i, 2)
        for i in range(3))
    lap_a = tuple(q.laplace for q in aa)
    inv_mu0 = c(1.0 / prm.mu0)
    j = tuple(inv_mu0 * (grad_div_a[i] - lap_a[i]) for i in range(3))
    B = (aa[2].grad(1) - aa[1].grad(2),
         aa[0].grad(2) - aa[2].grad(0),
         aa[1].grad(0) - aa[0].grad(1))

    # induction (user_kernels.h induction)
    u_x_B = _cross(u, B)
    d_aa = tuple(u_x_B[i] + c(prm.eta) * lap_a[i] for i in range(3))

    # momentum (user_kernels.h momentum)
    cs2 = c(prm.cs2_sound) * jnp.exp(
        c(prm.gamma / prm.cp_sound) * ss.value
        + c(prm.gamma - 1.0) * (lnrho.value - c(prm.lnrho0)))
    inv_rho = jnp.exp(-lnrho.value)
    adv = tuple(_dot((uu[i].grad(0), uu[i].grad(1), uu[i].grad(2)), u)
                for i in range(3))
    grad_div_u = tuple(
        uu[0].hess(i, 0) + uu[1].hess(i, 1) + uu[2].hess(i, 2)
        for i in range(3))
    lap_u = tuple(q.laplace for q in uu)
    j_x_B = _cross(j, B)
    S_dot_glnrho = tuple(_dot(S[i], grad_lnrho) for i in range(3))
    d_uu = tuple(
        -adv[i]
        - cs2 * (c(1.0 / prm.cp_sound) * grad_ss[i] + grad_lnrho[i])
        + inv_rho * j_x_B[i]
        + c(prm.nu_visc) * (lap_u[i] + third * grad_div_u[i]
                            + c(2.0) * S_dot_glnrho[i])
        + c(prm.zeta) * grad_div_u[i]
        for i in range(3))

    # entropy (user_kernels.h entropy, lnT, heat_conduction)
    lnT = (c(prm.lnT0) + c(prm.gamma / prm.cp_sound) * ss.value
           + c(prm.gamma - 1.0) * (lnrho.value - c(prm.lnrho0)))
    rho = jnp.exp(lnrho.value)
    inv_pT = jnp.exp(-lnrho.value - lnT)
    contract_S = sum(S[i][k] * S[i][k] for i in range(3) for k in range(3))
    rhs = (c(prm.eta * prm.mu0) * _dot(j, j)
           + c(2.0 * prm.nu_visc) * rho * contract_S
           + c(prm.zeta) * rho * div_u * div_u)
    # heat conduction with chi = 0.001/(rho cp) (user_kernels.h:441-449)
    inv_cp = c(1.0 / prm.cp_sound)
    gamma_ = c(prm.gamma)
    first_term = gamma_ * inv_cp * ss.laplace + (gamma_ - c(1.0)) * lnrho.laplace
    second = tuple(gamma_ * inv_cp * grad_ss[i] + (gamma_ - c(1.0)) * grad_lnrho[i]
                   for i in range(3))
    third_t = tuple(gamma_ * (inv_cp * grad_ss[i] + grad_lnrho[i])
                    - grad_lnrho[i] for i in range(3))
    chi = c(0.001) * jnp.exp(-lnrho.value) * inv_cp
    heat = c(prm.cp_sound) * chi * (first_term + _dot(second, third_t))
    d_ss = -_dot(u, grad_ss) + inv_pT * rhs + heat

    return {"lnrho": d_lnrho, "uux": d_uu[0], "uuy": d_uu[1], "uuz": d_uu[2],
            "ax": d_aa[0], "ay": d_aa[1], "az": d_aa[2], "ss": d_ss}


class Astaroth:
    """Distributed MHD integrator over a TPU mesh."""

    def __init__(self, nx: int, ny: int, nz: int,
                 params: Optional[MhdParams] = None,
                 mesh_shape: Optional[Dim3Like] = None,
                 dtype=jnp.float32,
                 devices: Optional[Sequence] = None,
                 methods: Method = Method.PpermutePacked,
                 overlap: bool = False, kernel: str = "auto",
                 dcn_axis=None, dcn_groups=None,
                 exchange_every: Optional[int] = None,
                 boundary=None) -> None:
        self.prm = params or MhdParams()
        self.dd = DistributedDomain(nx, ny, nz, devices=devices)
        self.dd.set_radius(Radius.constant(RADIUS))
        self.dd.set_methods(methods)
        # temporal blocking: one depth-(s*R) exchange per s RK SUBSTEPS
        # (a substep is one stencil application; 3 substeps = 1
        # iteration). s that is a multiple of 3 keeps every blocked
        # group starting at RK substep 0 (alpha_0 == 0), so the w
        # accumulator never rides the wire; other depths exchange w too
        # when a group starts mid-iteration. Pallas fast paths map
        # s == 2 onto the fused substep-0+1 kernel; deeper blocking
        # runs the XLA temporal path (parallel/temporal.py).
        self._exchange_every = 0 if exchange_every is None \
            else max(int(exchange_every), 1)
        if self._exchange_every > 1:
            self.dd.set_exchange_every(self._exchange_every)
        if boundary is not None:
            self.dd.set_boundary(boundary)
        if dcn_axis is not None or dcn_groups is not None:
            self.dd.set_dcn_axis(dcn_axis, dcn_groups)
        if mesh_shape is not None:
            self.dd.set_mesh_shape(mesh_shape)
        elif dcn_axis is not None or dcn_groups is not None:
            # DCN tier with no explicit shape: normally realize()
            # derives the grid from NodePartition's two-level split —
            # but the halo fast paths need x unsharded, which that
            # split does not know (same rule as Jacobi3D; the f32 gate
            # matches the kernel-selection gate below)
            from ..models.jacobi import _dcn_xfree_shape
            from ..ops.pallas_stencil import on_tpu
            halo_want = (kernel == "halo"
                         or (kernel == "auto" and on_tpu()
                             and _fast_dtype_ok(dtype)))
            shape = _dcn_xfree_shape(Dim3(nx, ny, nz),
                                     self.dd._devices, dcn_axis,
                                     dcn_groups,
                                     "halo" if halo_want else "xla",
                                     align=8)
            if shape is not None:
                self.dd.set_mesh_shape(shape)
        else:
            from ..ops.pallas_stencil import on_tpu
            # auto only takes the halo megakernel on TPU AND f32 (the
            # kernel is f32-tuned; _build_step applies the same gate),
            # so don't warp the mesh for configs that will run XLA.
            # overlap keeps the same preference: the in-kernel RDMA
            # overlap path shares the halo kernels' x-unsharded contract
            if (len(self.dd._devices) > 1
                    and (kernel == "halo"
                         or (kernel == "auto" and on_tpu()
                             and _fast_dtype_ok(dtype)))):
                # prefer an x-unsharded decomposition so the fused halo
                # megakernel path is available (ops/pallas_halo.py)
                from ..partition import partition_dims_even_xfree
                shape = partition_dims_even_xfree(
                    Dim3(nx, ny, nz), len(self.dd._devices), align=8)
                if shape is not None:
                    self.dd.set_mesh_shape(shape)
        for q in FIELDS:
            self.dd.add_data(q, dtype)
        self.dd.realize()
        self._dtype = np.dtype(dtype)
        self._overlap = overlap
        if kernel not in ("auto", "wrap", "halo", "xla"):
            raise ValueError(
                f"kernel must be auto|wrap|halo|xla, got {kernel!r}")
        self._kernel = kernel
        # RK3 accumulators (interior-shaped, no halos; the XLA temporal
        # path stores them PADDED so the deep exchange can carry them)
        self._w: Optional[Dict[str, jnp.ndarray]] = None
        self._w_padded = False
        # interior-resident fast-path state (wrap/halo kernels); any
        # external write to dd.curr must go through sync_domain() — the
        # set_interior hook below keeps it coherent automatically
        self._inner: Optional[Dict[str, jnp.ndarray]] = None
        self._insert = None
        self.dd.on_interior_write(lambda name: self.sync_domain())
        self._build_step()

    # -- initial conditions (reference: astaroth/astaroth.cu:509-528) --
    def init(self) -> None:
        """hash-random all fields in [-1, 1); lnrho constant 0.5;
        radial-explosion shell velocity."""
        size = self.dd.size
        shape = zyx_shape(size)
        # the reference's hash init has no per-field seed, so all fields
        # get the identical array — compute it once and skip the four
        # fields overwritten below (astaroth.cu:509-528)
        noise = _hash_field(shape).astype(self._dtype)
        for q in ("ax", "ay", "az", "ss"):
            self.dd.set_interior(q, noise)
        self.dd.set_interior("lnrho",
                             np.full(shape, 0.5, dtype=self._dtype))
        ux, uy, uz = _radial_explosion(size, self.prm)
        self.dd.set_interior("uux", ux.astype(self._dtype))
        self.dd.set_interior("uuy", uy.astype(self._dtype))
        self.dd.set_interior("uuz", uz.astype(self._dtype))
        self._w = None

    # -- fused iteration ----------------------------------------------
    def _build_step(self) -> None:
        self._segment_builder = None
        self._segment_decline = None
        dd = self.dd
        radius = dd.radius
        counts = mesh_dim(dd.mesh)
        local = dd.local_size
        prm = self.prm
        pad_lo = radius.pad_lo()
        inv_ds = (1.0 / prm.dsx, 1.0 / prm.dsy, 1.0 / prm.dsz)
        method = pick_method(dd.methods)
        dt = prm.dt

        rem = dd.rem
        # bf16 stores half-width but must not EVALUATE the 6th-order
        # RHS in bf16 — same storage/compute split as the Pallas paths
        from ..ops.pallas_mhd import compute_dtype
        comp = compute_dtype(self._dtype)
        store = jnp.dtype(self._dtype)

        from ..topology import Boundary
        nonper = dd.boundary == Boundary.NONE
        s_every = dd.exchange_every

        def substep_fused(fields, w, s):
            fields = dispatch_exchange(fields, radius, counts, method,
                                       rem=rem, nonperiodic=nonper)
            with layer_scope(KERNEL):
                data = {q: FieldData(fields[q].astype(comp), inv_ds,
                                     pad_lo, local)
                        for q in FIELDS}
                rates = mhd_rates(data, prm, comp)
                alpha = jnp.asarray(RK3_ALPHA[s], comp)
                beta = jnp.asarray(RK3_BETA[s], comp)
                dt_ = jnp.asarray(dt, comp)
                new_u = {}
                new_w = {}
                for q in FIELDS:
                    wq = alpha * w[q].astype(comp) + dt_ * rates[q]
                    new_u[q] = (data[q].value + beta * wq).astype(store)
                    new_w[q] = wq.astype(store)
            new_f = {q: write_interior(fields[q], new_u[q], radius)
                     for q in FIELDS}
            return new_f, new_w

        def substep_overlap(fields, w, s):
            """Interior rates overlap the exchange (the reference's
            per-substep interior/exchange/exterior choreography,
            astaroth/astaroth.cu:552-646, as one program)."""
            from ..parallel.overlap import overlapped_update

            alpha = jnp.asarray(RK3_ALPHA[s], comp)
            beta = jnp.asarray(RK3_BETA[s], comp)
            dt_ = jnp.asarray(dt, comp)

            @layer_scope(KERNEL)
            def upd(blocks, dims, off):
                data = {q: FieldData(blocks[q].astype(comp), inv_ds,
                                     pad_lo, dims)
                        for q in FIELDS}
                rates = mhd_rates(data, prm, comp)
                out = {}
                for q in FIELDS:
                    w_blk = lax.slice(
                        w[q], (off[2], off[1], off[0]),
                        (off[2] + dims.z, off[1] + dims.y, off[0] + dims.x))
                    wq = alpha * w_blk.astype(comp) + dt_ * rates[q]
                    out[f"w:{q}"] = wq.astype(store)
                    out[f"f:{q}"] = (data[q].value
                                     + beta * wq).astype(store)
                return out

            fields_ex, parts = overlapped_update(fields, radius, counts,
                                                 method, upd,
                                                 nonperiodic=nonper)
            new_f = {q: write_interior(fields_ex[q], parts[f"f:{q}"],
                                       radius) for q in FIELDS}
            new_w = {q: parts[f"w:{q}"] for q in FIELDS}
            return new_f, new_w

        if self._overlap and rem != Dim3(0, 0, 0):
            raise NotImplementedError("overlap mode requires an evenly "
                                      "divisible grid")
        # single-chip fast path: the fused Pallas "solve" megakernel
        # with periodic wrap in-kernel (ops/pallas_mhd.py) — ~25x the
        # slicing formulation at 256^3
        from ..ops.pallas_mhd import mhd_tile
        tile = mhd_tile(self._dtype)
        aligned_t = (rem == Dim3(0, 0, 0)
                     and local.z % tile == 0 and local.y % tile == 0)
        aligned = aligned_t and not self._overlap
        # the Pallas paths assume periodic wrap; Boundary.NONE and
        # blocking depths beyond the fused substep-0+1 pair (s == 2)
        # run the XLA temporal path
        pallas_s_ok = s_every in (1, 2) and not nonper
        wrap_ok = counts == Dim3(1, 1, 1) and aligned and not nonper
        # multi-device fast path: interior-resident shards + slab
        # exchange + fused halo megakernel (ops/pallas_halo.py)
        halo_ok = counts.x == 1 and aligned and pallas_s_ok
        kernel = self._kernel
        # overlapped multi-device fast path: in-kernel RDMA slab
        # exchange hidden behind the fused interior compute
        # (ops/pallas_mhd_overlap.py) — explicit kernel='halo' +
        # overlap opts in anywhere (tests run it interpreted); 'auto'
        # takes it on real TPU hardware with f32 fields
        rdma_overlap_ok = (self._overlap and counts.x == 1
                           and aligned_t and pallas_s_ok)

        def _blocks_feasible(path: str) -> bool:
            """auto only: does the VMEM block planner find a legal
            shape for this Pallas path at this shard? An explicit
            kernel= request still raises the planner's
            TilingInfeasibleError (the operator asked for exactly that
            path); auto declines to the next path LOUDLY instead — the
            same catch-and-fall-back the Jacobi pair path got."""
            from ..analysis.tiling import TilingInfeasibleError
            from ..ops.pallas_halo import mhd_halo_blocks
            from ..ops.pallas_mhd import _fit_blocks

            blk_z, blk_y = (getattr(self, "_halo_blocks", None)
                            or (8, 32))
            isz = np.dtype(self._dtype).itemsize
            try:
                if path == "wrap":
                    _fit_blocks(local.z, local.y, blk_z, blk_y, tile,
                                X=local.x, itemsize=isz)
                else:
                    mhd_halo_blocks(local.z, local.y, blk_z, blk_y,
                                    tile, X=local.x, itemsize=isz)
                return True
            except TilingInfeasibleError as e:
                from ..utils.logging import LOG_WARN
                LOG_WARN(f"astaroth auto declines the {path} path: {e}")
                return False

        if rdma_overlap_ok:
            from ..ops.pallas_stencil import on_tpu
            if (kernel == "halo"
                    or (kernel == "auto" and on_tpu()
                        and _fast_dtype_ok(self._dtype)
                        and _blocks_feasible("halo"))):
                from ..utils.logging import LOG_INFO
                self.kernel_path = "halo-overlap"
                self._build_halo_overlap_step()
                LOG_INFO("astaroth kernel path: halo-overlap "
                         "(in-kernel RDMA)")
                return
        if kernel == "auto":
            from ..ops.pallas_stencil import on_tpu
            from ..utils.logging import LOG_INFO
            if on_tpu() and _fast_dtype_ok(self._dtype):
                kernel = ("wrap" if wrap_ok and _blocks_feasible("wrap")
                          else "halo" if halo_ok
                          and _blocks_feasible("halo") else "xla")
            else:
                kernel = "xla"
            why = ""
            if kernel == "xla" and on_tpu():
                blockers = []
                if not _fast_dtype_ok(self._dtype):
                    blockers.append(f"dtype {np.dtype(self._dtype).name}")
                if counts.x != 1:
                    blockers.append("x-axis sharded")
                if not aligned:
                    blockers.append(
                        f"uneven grid / z,y % {tile} != 0 / "
                        "overlap requested")
                if not blockers:
                    blockers.append("no legal VMEM block shape")
                why = f" (fast paths unavailable: {', '.join(blockers)})"
            LOG_INFO(f"astaroth kernel path: {kernel}{why}")
        if kernel == "wrap":
            if not wrap_ok:
                raise ValueError(
                    "kernel='wrap' needs a (1,1,1) mesh, even grid, z/y "
                    f"multiples of the dtype sublane tile ({tile}), "
                    "overlap off")
            self.kernel_path = "wrap"
            self._build_wrap_step()
            return
        if kernel == "halo":
            if not halo_ok:
                raise ValueError(
                    "kernel='halo' needs an x-unsharded mesh, even grid, "
                    f"local z/y multiples of the dtype sublane tile "
                    f"({tile}), overlap off, periodic boundaries, "
                    "exchange_every <= 2")
            self.kernel_path = "halo"
            self._build_halo_step()
            return
        if s_every > 1:
            self.kernel_path = (f"xla-temporal[s={s_every}]"
                                + ("-overlap" if self._overlap else ""))
            self._build_temporal_xla_step(comp, store, nonper)
            from ..utils.logging import LOG_INFO
            LOG_INFO(f"astaroth kernel path: {self.kernel_path}")
            return
        self.kernel_path = "xla-overlap" if self._overlap else "xla"
        substep = substep_overlap if self._overlap else substep_fused

        def shard_iter(fields, w):
            for s in range(3):
                fields, w = substep(fields, w, s)
            return fields, w

        spec = P("z", "y", "x")
        sm = jax.shard_map(shard_iter, mesh=dd.mesh,
                           in_specs=(spec, spec), out_specs=(spec, spec),
                           check_vma=False)
        self._iter = jax.jit(sm, donate_argnums=(0, 1))

        def shard_iters(fields, w, n):
            return lax.fori_loop(
                0, n, lambda _, fw: shard_iter(*fw), (fields, w))

        sm_n = jax.shard_map(shard_iters, mesh=dd.mesh,
                             in_specs=(spec, spec, P()),
                             out_specs=(spec, spec), check_vma=False)
        self._iter_n = jax.jit(sm_n, donate_argnums=(0, 1))
        self._set_segment_builder(lambda fw, c: shard_iter(*fw))

    def _set_segment_builder(self, advance_iters, stride: int = 1
                             ) -> None:
        """Megastep factory: the RK accumulators ride the fused
        segment as carry next to the fields, both donated end-to-end
        (the ``(fields, w)`` pair IS the carry contract's state
        pytree); the in-graph probe reads the PADDED fields after each
        full RK3 iteration. ``advance_iters((fields, w), c)`` advances
        ``c`` iterations per shard — ``c`` is the path's stride (one
        whole ``lcm(3, s)``-period group block on the temporal path,
        so every blocked group's RK phase stays static inside the
        segment) or a depth-1 tail iteration."""
        from ..parallel import megastep as ms

        dd = self.dd
        spec = P("z", "y", "x")
        fields_spec = {q: spec for q in FIELDS}

        def state_fn():
            self._ensure_w()
            return (dict(self.dd.curr), dict(self._w))

        def adopt(out):
            out_f, out_w = out
            self.dd.curr = dict(out_f)
            self._w = dict(out_w)

        self._segment_decline = None
        self._segment_builder = ms.SegmentCompiler(
            dd.mesh,
            ms.CarryContract(
                specs=(fields_spec, fields_spec),
                probe_view=lambda fw: {q: fw[0][q] for q in FIELDS},
                stride=stride),
            lambda fw, c, i: advance_iters(fw, c), state_fn, adopt)

    def _set_segment_decline(self, reason: str,
                             code: Optional[str] = None) -> None:
        self._segment_builder = None
        self._segment_decline = reason
        self._segment_decline_code = code

    def make_segment(self, check_every: int, probe_every: int = 1,
                     metrics=None):
        """ONE compiled program advancing ``check_every`` RK3
        iterations with the health probe fused in-graph
        (``parallel/megastep.py``); the ``w`` accumulators travel as
        segment carry. The XLA path unrolls per iteration; the
        temporal path chunks whole ``lcm(3, s)``-period groups (the w
        carry's group-straddle phases stay static) plus depth-1
        tails. The interior-resident Pallas fast paths return a falsy
        reason-carrying ``SegmentDecline`` (their state lives outside
        ``dd.curr`` in the extract/loop/insert program split) — the
        resilient driver reports it and falls back to stepwise
        dispatch there."""
        builder = getattr(self, "_segment_builder", None)
        if builder is None:
            from ..parallel import megastep as ms
            reason = (getattr(self, "_segment_decline", None)
                      or "no fused-segment builder for this path")
            code = (getattr(self, "_segment_decline_code", None)
                    or ms.DECLINE_NO_BUILDER)
            return ms.decline("astaroth", self.kernel_path, reason,
                              code=code)
        return builder(int(check_every), max(int(probe_every), 1),
                       metrics)

    def _build_temporal_xla_step(self, comp, store, nonper: bool) -> None:
        """Communication-avoiding XLA iteration: RK substeps run in
        groups of ``s = exchange_every`` through
        ``parallel/temporal.py`` — ONE depth-``s*R`` exchange per group,
        then ``s`` fused substeps on the shrinking window. When ``s``
        does not divide 3, groups straddle iteration boundaries, so the
        loop body covers ``lcm(3, s) / 3`` iterations (every group's RK
        phase is then static) and a group whose first substep has
        ``alpha != 0`` ships the ``w`` accumulator in the same deep
        exchange (pointwise reads only — the ring depth ``(s-1)*R``
        is covered by the uniform ``s*R`` slabs). ``w`` lives PADDED on
        this path so its halo ring has a home."""
        import math

        from ..parallel.exchange import shard_origin
        from ..parallel.temporal import temporal_shard_steps, validate_temporal

        dd = self.dd
        radius = dd.radius
        counts = mesh_dim(dd.mesh)
        local = dd.local_size
        prm = self.prm
        pad_lo = radius.pad_lo()
        inv_ds = (1.0 / prm.dsx, 1.0 / prm.dsy, 1.0 / prm.dsz)
        method = pick_method(dd.methods)
        dt = prm.dt
        rem = dd.rem
        gsize = dd.size
        s = dd.exchange_every
        overlap = self._overlap
        validate_temporal(radius, local, s, rem)
        period = math.lcm(3, s)
        self._w_padded = True
        w_keys = [f"w:{q}" for q in FIELDS]

        def make_update(start, origin):
            ox, oy, oz = origin

            @layer_scope(KERNEL)
            def update_fn(blocks, dims, off, k):
                sub = (start + k) % 3
                data = {q: FieldData(blocks[q].astype(comp), inv_ds,
                                     pad_lo, dims)
                        for q in FIELDS}
                rates = mhd_rates(data, prm, comp)
                alpha = jnp.asarray(RK3_ALPHA[sub], comp)
                beta = jnp.asarray(RK3_BETA[sub], comp)
                dt_ = jnp.asarray(dt, comp)
                if nonper:
                    from ..ops.stencil_kernels import global_coords
                    gz, gy, gx = global_coords(
                        (ox + off[0], oy + off[1], oz + off[2]), dims)
                    inside = ((gx >= 0) & (gx < gsize.x)
                              & (gy >= 0) & (gy < gsize.y)
                              & (gz >= 0) & (gz < gsize.z))
                out = {}
                for q in FIELDS:
                    # w is read POINTWISE: the window-center slice of
                    # its base-radius-padded block
                    wv = lax.slice(
                        blocks[f"w:{q}"],
                        (pad_lo.z, pad_lo.y, pad_lo.x),
                        (pad_lo.z + dims.z, pad_lo.y + dims.y,
                         pad_lo.x + dims.x))
                    wq = alpha * wv.astype(comp) + dt_ * rates[q]
                    uq = data[q].value + beta * wq
                    if nonper:
                        # the zero-Dirichlet exterior: ring cells beyond
                        # the global domain hold 0, exactly what a
                        # stepwise exchange would re-deliver
                        uq = jnp.where(inside, uq, jnp.zeros_like(uq))
                    out[f"w:{q}"] = wq.astype(store)
                    out[q] = uq.astype(store)
                return out

            return update_fn

        def group(f, w, origin, start, depth):
            fields = {q: f[q] for q in FIELDS}
            fields.update({f"w:{q}": w[q] for q in FIELDS})
            # the group's first substep is the only one reading w from
            # before the group; its window ring needs wire data only
            # when alpha != 0 and the window extends past the interior
            keys = list(FIELDS)
            if RK3_ALPHA[start] != 0.0 and depth > 1:
                keys += w_keys
            out = temporal_shard_steps(
                fields, radius, counts, method, make_update(start, origin),
                depth, alloc_steps=s, rem=rem, exchange_keys=keys,
                overlap=overlap and depth > 1, nonperiodic=nonper)
            return ({q: out[q] for q in FIELDS},
                    {q: out[f"w:{q}"] for q in FIELDS})

        def shard_iters(f, w, n):
            origin = shard_origin(local, rem)

            def period_body(_, fw):
                f, w = fw
                for g in range(period // s):
                    f, w = group(f, w, origin, (g * s) % 3, s)
                return f, w

            def tail_iter(_, fw):
                f, w = fw
                for sub in range(3):
                    f, w = group(f, w, origin, sub, 1)
                return f, w

            iters_per_period = period // 3
            f, w = lax.fori_loop(0, n // iters_per_period, period_body,
                                 (f, w))
            return lax.fori_loop(0, n % iters_per_period, tail_iter, (f, w))

        spec = P("z", "y", "x")
        fields_spec = {q: spec for q in FIELDS}
        sm_n = jax.shard_map(shard_iters, mesh=dd.mesh,
                             in_specs=(fields_spec, fields_spec, P()),
                             out_specs=(fields_spec, fields_spec),
                             check_vma=False)
        self._iter_n = jax.jit(sm_n, donate_argnums=(0, 1))
        self._iter = lambda f, w: self._iter_n(f, w,
                                               jnp.asarray(1, jnp.int32))

        iters_per_period = period // 3

        def advance_iters(fw, c):
            # one segment chunk, per shard: a whole lcm(3, s)-period
            # block (every group's RK phase static — the SAME group
            # sequence period_body runs, w shipping in the deep
            # exchange exactly where alpha != 0), or one depth-1 tail
            # iteration (3 per-substep groups)
            f, w = fw
            origin = shard_origin(local, rem)
            if c == iters_per_period:
                for g in range(period // s):
                    f, w = group(f, w, origin, (g * s) % 3, s)
            else:
                for sub in range(3):
                    f, w = group(f, w, origin, sub, 1)
            return f, w

        self._set_segment_builder(advance_iters, stride=iters_per_period)

    def _build_wrap_step(self) -> None:
        """Single-chip fused substeps on interior views (see
        ops/pallas_mhd.mhd_substep_wrap_pallas).

        Extract / substep-loop / insert are three SEPARATE jitted
        programs: composing them into one jit makes XLA schedule the
        Pallas loop an order of magnitude slower (measured 3.5s vs
        ~110ms per iteration at 256^3), while the split pieces run at
        full speed."""
        from ..ops.pallas_mhd import mhd_substep_wrap_pallas

        dd = self.dd
        if dd.exchange_every > 1:
            from ..utils.logging import LOG_WARN
            LOG_WARN("exchange_every has no effect on the single-chip "
                     "wrap path (it performs no exchange); fields still "
                     "carry the deepened allocation pads")
        alloc_r = dd.alloc_radius
        local = dd.local_size
        prm = self.prm
        dt = prm.dt

        @jax.jit
        def extract(fields):
            return {q: read_interior(p, alloc_r, local)
                    for q, p in fields.items()}

        # STENCIL_MHD_PAIR=1 opts into the fused substep-0+1 kernel
        # (one HBM pass for two of the three RK substeps; alpha_0 == 0
        # makes the pair independent of the incoming w) — experimental
        # until hardware-measured, so default off
        from ..utils.config import mhd_pair_requested
        pair_on = mhd_pair_requested()
        if pair_on:
            from ..ops.pallas_mhd import mhd_substep01_wrap_pallas
            from ..utils.logging import LOG_INFO
            LOG_INFO("astaroth wrap path: fused substep-0+1 kernel")

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def loop(inner, w, n):
            # dead-w elision: substep 0 never reads w (alpha_0 == 0,
            # w=None) and nothing reads substep 2's w (the next
            # iteration restarts at alpha_0 == 0; write_w=False) — the
            # carry keeps the last WRITTEN w so the fori_loop structure
            # is stable. Saves a full 8-field read + write sweep per
            # iteration vs the reference's unconditional w traffic
            # (astaroth/kernels.cu:63-90).
            def body(_, fw):
                f, wk = fw
                if pair_on:
                    f, wk = mhd_substep01_wrap_pallas(f, prm, dt)
                    f, _ = mhd_substep_wrap_pallas(f, wk, 2, prm, dt,
                                                   write_w=False)
                else:
                    f, wk = mhd_substep_wrap_pallas(f, None, 0, prm, dt)
                    f, wk = mhd_substep_wrap_pallas(f, wk, 1, prm, dt)
                    f, _ = mhd_substep_wrap_pallas(f, wk, 2, prm, dt,
                                                   write_w=False)
                return f, wk
            return lax.fori_loop(0, n, body, (inner, w))

        @functools.partial(jax.jit, donate_argnums=(0,))
        def insert(fields, inner):
            # halos go stale; nothing reads them before the next
            # exchange, and field() reads the interior only
            return {q: write_interior(fields[q], inner[q], alloc_r)
                    for q in fields}

        # interior-resident state between calls: step()-per-iteration
        # loops would otherwise pay extract+insert (3 extra full-field
        # HBM passes) every iteration. dd.curr is materialized lazily
        # via sync_domain() when the padded domain is accessed.
        self._insert = insert
        self._install_inner_iter(extract, loop)

    def _build_halo_step(self) -> None:
        """Multi-device fused substeps: interior-resident shards, thin
        slab ppermutes, one fused Pallas megakernel per substep — so an
        N-chip mesh keeps single-chip per-chip throughput (the analog
        of the reference's fused solve kernel running at every scale,
        astaroth/astaroth.cu:552-646; see ops/pallas_halo.py).

        Same extract / substep-loop / insert program split (and
        interior-resident caching) as wrap mode, but each program is
        shard_map'ped over the mesh."""
        from ..ops.pallas_halo import (R as HALO_R, mhd_halo_blocks,
                                       mhd_substep_halo_pallas)
        from ..ops.pallas_mhd import mhd_tile
        from ..parallel.exchange import exchange_interior_slabs

        dd = self.dd
        alloc_r = dd.alloc_radius
        local = dd.local_size
        counts = mesh_dim(dd.mesh)
        prm = self.prm
        dt = prm.dt
        tile = mhd_tile(self._dtype)   # 8 f32/f64, 16 bf16 slabs
        blk_z, blk_y = getattr(self, "_halo_blocks", None) or (8, 32)
        bz, by = mhd_halo_blocks(local.z, local.y, blk_z, blk_y, tile,
                                 X=local.x,
                                 itemsize=np.dtype(self._dtype).itemsize)
        spec = P("z", "y", "x")
        fields_spec = {q: spec for q in FIELDS}

        # STENCIL_MHD_PAIR=1: fused substep-0+1 kernel on the halo path
        # too — one radius-2R exchange + one HBM pass covers two of the
        # three RK substeps (same opt-in as the wrap path; needs the
        # slabs to carry 2R valid rows, hence 2R <= min(bz, tile))
        from ..utils.config import mhd_pair_requested
        pair_on = ((mhd_pair_requested() or self._exchange_every == 2)
                   and 2 * HALO_R <= min(bz, tile))
        if self._exchange_every == 2 and not pair_on:
            from ..utils.logging import LOG_WARN
            LOG_WARN("exchange_every=2 requested but the fused "
                     "substep-0+1 kernel cannot tile this shard; "
                     "falling back to per-substep exchanges")
        if pair_on:
            from ..ops.pallas_halo import mhd_substep01_halo_pallas
            from ..utils.logging import LOG_INFO
            LOG_INFO("astaroth halo path: fused substep-0+1 kernel")

        def extract_shard(fields):
            return {q: read_interior(p, alloc_r, local)
                    for q, p in fields.items()}

        extract = jax.jit(jax.shard_map(
            extract_shard, mesh=dd.mesh, in_specs=(fields_spec,),
            out_specs=fields_spec, check_vma=False))

        def exchange_all(f, radius_rows):
            return {q: exchange_interior_slabs(
                f[q], counts, rz=bz, ry=tile,
                radius_rows=radius_rows, y_z_extended=True)
                for q in FIELDS}

        def loop_shard(inner, w, n):
            # dead-w elision (see _build_wrap_step): substep 0 reads no
            # w, substep 2 writes none; the carry keeps the last
            # written w for fori_loop structural stability
            def body(_, fw):
                f, wk = fw
                if pair_on:
                    f, wk = mhd_substep01_halo_pallas(
                        f, exchange_all(f, 2 * HALO_R), prm, dt,
                        block_z=bz, block_y=by)
                    f, _ = mhd_substep_halo_pallas(
                        f, wk, exchange_all(f, HALO_R), 2, prm, dt,
                        block_z=bz, block_y=by, write_w=False)
                else:
                    f, wk = mhd_substep_halo_pallas(
                        f, None, exchange_all(f, HALO_R), 0, prm, dt,
                        block_z=bz, block_y=by)
                    f, wk = mhd_substep_halo_pallas(
                        f, wk, exchange_all(f, HALO_R), 1, prm, dt,
                        block_z=bz, block_y=by)
                    f, _ = mhd_substep_halo_pallas(
                        f, wk, exchange_all(f, HALO_R), 2, prm, dt,
                        block_z=bz, block_y=by, write_w=False)
                return f, wk
            return lax.fori_loop(0, n, body, (inner, w))

        loop = jax.jit(jax.shard_map(
            loop_shard, mesh=dd.mesh,
            in_specs=(fields_spec, fields_spec, P()),
            out_specs=(fields_spec, fields_spec), check_vma=False),
            donate_argnums=(0, 1))

        def insert_shard(fields, inner):
            return {q: write_interior(fields[q], inner[q], alloc_r)
                    for q in fields}

        self._insert = jax.jit(jax.shard_map(
            insert_shard, mesh=dd.mesh, in_specs=(fields_spec, fields_spec),
            out_specs=fields_spec, check_vma=False), donate_argnums=0)
        # exchange accounting for exchange_stats(): per iteration the
        # pair path does one radius-2R + one radius-R extended slab
        # round; the sequential path three radius-R rounds
        self._slab_exchange_cfg = dict(rz=bz, ry=tile, pair=pair_on)
        self._install_inner_iter(extract, loop)

    def _build_halo_overlap_step(self) -> None:
        """Overlapped multi-device fused substeps: per substep, ONE
        Pallas kernel issues the slab RDMA and computes the interior
        behind the in-flight DMAs, then thin strip kernels recompute
        the shard-edge blocks from the landed slabs (the reference's
        per-substep interior/exchange/exterior choreography,
        astaroth/astaroth.cu:552-646; see ops/pallas_mhd_overlap.py).
        Same extract/loop/insert program split and interior-resident
        caching as the halo path."""
        from ..ops.pallas_halo import R as HALO_R, mhd_halo_blocks
        from ..ops.pallas_mhd import mhd_tile
        from ..ops.pallas_mhd_overlap import mhd_substep_overlap

        dd = self.dd
        alloc_r = dd.alloc_radius
        local = dd.local_size
        counts = mesh_dim(dd.mesh)
        prm = self.prm
        dt = prm.dt
        tile = mhd_tile(self._dtype)   # 8 f32/f64, 16 bf16 slabs
        blk_z, blk_y = getattr(self, "_halo_blocks", None) or (8, 32)
        bz, by = mhd_halo_blocks(local.z, local.y, blk_z, blk_y, tile,
                                 X=local.x,
                                 itemsize=np.dtype(self._dtype).itemsize)
        spec = P("z", "y", "x")
        fields_spec = {q: spec for q in FIELDS}

        def extract_shard(fields):
            return {q: read_interior(p, alloc_r, local)
                    for q, p in fields.items()}

        extract = jax.jit(jax.shard_map(
            extract_shard, mesh=dd.mesh, in_specs=(fields_spec,),
            out_specs=fields_spec, check_vma=False))

        # STENCIL_MHD_PAIR composes with the overlap path too: one
        # radius-2R overlapped exchange + one fused pass covers RK
        # substeps 0+1, then substep 2 runs overlapped as usual
        from ..utils.config import mhd_pair_requested
        pair_on = ((mhd_pair_requested() or self._exchange_every == 2)
                   and 2 * HALO_R <= min(bz, tile))
        if self._exchange_every == 2 and not pair_on:
            from ..utils.logging import LOG_WARN
            LOG_WARN("exchange_every=2 requested but the fused "
                     "substep-0+1 kernel cannot tile this shard; "
                     "falling back to per-substep exchanges")
        if pair_on:
            from ..utils.logging import LOG_INFO
            LOG_INFO("astaroth halo-overlap path: fused substep-0+1")

        def loop_shard(inner, w, n):
            # dead-w elision (see _build_wrap_step): substep 0 reads no
            # w, substep 2 writes none; the carry keeps the last
            # written w for fori_loop structural stability
            def body(_, fw):
                f, wk = fw
                if pair_on:
                    f, wk = mhd_substep_overlap(f, None, 0, prm, dt,
                                                counts, block_z=bz,
                                                block_y=by, pair=True)
                    f, _ = mhd_substep_overlap(f, wk, 2, prm, dt,
                                               counts, block_z=bz,
                                               block_y=by,
                                               write_w=False)
                else:
                    f, wk = mhd_substep_overlap(f, None, 0, prm, dt,
                                                counts, block_z=bz,
                                                block_y=by)
                    f, wk = mhd_substep_overlap(f, wk, 1, prm, dt,
                                                counts, block_z=bz,
                                                block_y=by)
                    f, _ = mhd_substep_overlap(f, wk, 2, prm, dt,
                                               counts, block_z=bz,
                                               block_y=by,
                                               write_w=False)
                return f, wk
            return lax.fori_loop(0, n, body, (inner, w))

        loop = jax.jit(jax.shard_map(
            loop_shard, mesh=dd.mesh,
            in_specs=(fields_spec, fields_spec, P()),
            out_specs=(fields_spec, fields_spec), check_vma=False),
            donate_argnums=(0, 1))

        def insert_shard(fields, inner):
            return {q: write_interior(fields[q], inner[q], alloc_r)
                    for q in fields}

        self._insert = jax.jit(jax.shard_map(
            insert_shard, mesh=dd.mesh, in_specs=(fields_spec, fields_spec),
            out_specs=fields_spec, check_vma=False), donate_argnums=0)
        # same wire traffic as the sequential halo path (pair: one
        # radius-2R + one radius-R round; else 3 radius-R rounds per
        # iteration), issued in-kernel
        self._slab_exchange_cfg = dict(rz=bz, ry=tile, pair=pair_on)
        self._install_inner_iter(extract, loop)

    def _install_inner_iter(self, extract, loop) -> None:
        """Shared interior-resident iteration protocol for the wrap and
        halo fast paths: ``self._inner`` caches the interior state
        between calls; ``sync_domain()`` flushes it into ``dd.curr``
        (and runs automatically before any ``dd.set_interior``)."""
        def iteration_n(fields, w, n):
            inner = self._inner
            if inner is None:
                inner = extract(fields)
            inner, w = loop(inner, w, n)
            self._inner = dict(inner)
            return fields, w

        self._iter_n = iteration_n
        self._iter = lambda f, w: iteration_n(f, w, jnp.asarray(1, jnp.int32))
        # the interior-resident fast paths keep their state OUTSIDE
        # dd.curr in a three-program extract/loop/insert split (fusing
        # extract+loop+insert into one program measured an order of
        # magnitude slower — see _build_wrap_step); a megastep over
        # dd.curr would advance stale state, so the path declines
        # loudly and the driver runs its already-fused loop stepwise
        from ..parallel.megastep import DECLINE_INTERIOR_RESIDENT_STATE
        self._set_segment_decline(
            "interior-resident extract/loop/insert split keeps state "
            "outside dd.curr (one fused program measured ~10x slower)",
            code=DECLINE_INTERIOR_RESIDENT_STATE)

    def exchange_stats(self) -> dict:
        """Per-iteration exchange accounting for the BUILT compute path
        (whole-mesh bytes, the ``exchange_bytes_total`` convention) —
        honest numbers for the fused fast paths that never call
        ``dd.exchange()`` (reference per-iteration exchange stats:
        src/stencil.cu:1005-1008,1174-1181; astaroth.cu:668-676)."""
        from ..ops.pallas_halo import R as HALO_R
        from ..parallel.exchange import interior_slab_bytes

        path = self.kernel_path
        if path == "wrap":
            return {"path": path, "bytes_per_iteration": 0,
                    "rounds_per_iteration": 0.0}
        counts = mesh_dim(self.dd.mesh)
        local = self.dd.local_size
        cfg = getattr(self, "_slab_exchange_cfg", None)
        if cfg is not None and path in ("halo", "halo-overlap"):
            shard = (local.z, local.y, local.x)
            item = self._dtype.itemsize
            n = counts.flatten() * len(FIELDS)

            def rnd(r):
                return interior_slab_bytes(shard, counts, r, item,
                                           y_z_extended=True) * n

            if cfg["pair"]:
                return {"path": path,
                        "bytes_per_iteration": rnd(2 * HALO_R) + rnd(HALO_R),
                        "rounds_per_iteration": 2.0}
            return {"path": path, "bytes_per_iteration": 3 * rnd(HALO_R),
                    "rounds_per_iteration": 3.0}
        s = self.dd.exchange_every
        if s > 1:
            # one deep exchange per s substeps; groups starting at an
            # alpha != 0 substep also carry the 8 w accumulators (same
            # dtypes/geometry as the fields -> exactly 2x the bytes)
            import math
            period = math.lcm(3, s)
            starts = [(g * s) % 3 for g in range(period // s)]
            per_ex = float(self.dd.exchange_bytes_total())
            iters = period // 3
            return {"path": path,
                    "bytes_per_iteration": sum(
                        per_ex * (2.0 if RK3_ALPHA[st] != 0.0 else 1.0)
                        for st in starts) / iters,
                    "rounds_per_iteration": len(starts) / iters}
        return {"path": path,
                "bytes_per_iteration": 3.0 * self.dd.exchange_bytes_total(),
                "rounds_per_iteration": 3.0}

    def measure_exchange_seconds(self, reps: int = 5) -> float:
        """Estimated exchange seconds per ITERATION, measured
        standalone per round config (the fused loops exchange inside
        one XLA program where the cost cannot be timed separately) —
        the same per-iteration convention as
        ``Jacobi3D.measure_exchange_seconds``. Returns 0.0 on the wrap
        path."""
        from ..ops.pallas_halo import ESUB, R as HALO_R

        path = self.kernel_path
        if path == "wrap":
            return 0.0
        cfg = getattr(self, "_slab_exchange_cfg", None)
        if cfg is not None and path in ("halo", "halo-overlap"):
            from ..parallel.exchange import measure_slab_exchange_seconds

            def rnd(r):
                return measure_slab_exchange_seconds(
                    self.dd.mesh, self.dd.local_size, self._dtype,
                    rz=cfg["rz"], ry=cfg.get("ry", ESUB),
                    radius_rows=r,
                    y_z_extended=True, nfields=len(FIELDS), reps=reps)

            if cfg["pair"]:
                return rnd(2 * HALO_R) + rnd(HALO_R)
            return 3 * rnd(HALO_R)
        import time

        from ..utils.timers import device_sync
        self.dd.exchange()
        device_sync(self.dd.curr[FIELDS[0]])
        t0 = time.perf_counter()
        for _ in range(reps):
            self.dd.exchange()
        device_sync(self.dd.curr[FIELDS[0]])
        # rounds per iteration: 3 stepwise, 3/s under temporal blocking
        rounds = self.exchange_stats()["rounds_per_iteration"]
        return rounds * (time.perf_counter() - t0) / reps

    def sync_domain(self) -> None:
        """Materialize interior-resident fast-path state back into the
        padded ``dd.curr`` fields (no-op otherwise). Runs automatically
        before ``dd.set_interior`` writes (init, checkpoint restore);
        call it manually before reading/writing ``dd.curr`` directly."""
        if self._inner is not None:
            self.dd.curr = dict(self._insert(self.dd.curr, self._inner))
            self._inner = None

    def _ensure_w(self) -> None:
        if self._w is None:
            from jax.sharding import NamedSharding

            from ..local_domain import raw_size
            sharding = NamedSharding(self.dd.mesh, P("z", "y", "x"))
            dim = self.dd.placement.dim()
            per_shard = (raw_size(self.dd.local_size, self.dd.alloc_radius)
                         if self._w_padded else self.dd.local_size)
            shape = zyx_shape(per_shard * dim)
            # np.zeros + EXPLICIT device_put: _ensure_w runs inside
            # the fused-segment dispatch, which is guarded by
            # jax.transfer_guard("disallow") — jnp.zeros would lift
            # its fill scalar through an implicit transfer
            self._w = {q: jax.device_put(
                np.zeros(shape, dtype=self._dtype), sharding)
                for q in FIELDS}

    def step(self) -> None:
        """One full RK3 iteration (3 substeps, 3 exchanges)."""
        self._ensure_w()
        out_f, out_w = self._iter(self.dd.curr, self._w)
        self.dd.curr = dict(out_f)
        self._w = dict(out_w)

    def run(self, iters: int) -> None:
        with get_tracer().span(RUN, iters=iters):
            self._ensure_w()
            out_f, out_w = self._iter_n(self.dd.curr, self._w,
                                        jnp.asarray(iters, jnp.int32))
            self.dd.curr = dict(out_f)
            self._w = dict(out_w)

    def block(self) -> None:
        from ..utils.timers import device_sync
        inner = self._inner
        with get_tracer().span(BLOCK):
            device_sync(inner["lnrho"] if inner is not None
                        else self.dd.curr["lnrho"])

    def field(self, name: str) -> np.ndarray:
        inner = self._inner
        if inner is not None:
            # fast paths keep the interior resident: the cached array IS
            # the (sharded) global interior, no halo stripping needed
            return np.asarray(inner[name])
        return self.dd.interior_to_host(name)

    # -- resilient run loop (stencil_tpu/resilience) -------------------
    def run_resilient(self, n_steps: int, policy=None,
                      ckpt_dir: Optional[str] = None, faults=None):
        """``n_steps`` RK3 iterations under the checkpoint-rollback
        recovery driver. The RK accumulators ride the checkpoint as
        ``extra`` arrays; interior-resident fast-path state is flushed
        (``sync_domain``) before every save and invalidated on restore.
        Returns the :class:`~stencil_tpu.resilience.ResilienceReport`."""
        from ..resilience.driver import run_resilient

        size = self.dd.size

        def rebuild(cfg):
            from .jacobi import _dcn_request_kwargs
            new = Astaroth(size.x, size.y, size.z, params=self.prm,
                           mesh_shape=tuple(self.dd.placement.dim()),
                           dtype=self._dtype, devices=self.dd._devices,
                           methods=cfg.method, kernel=self._kernel,
                           overlap=self._overlap,
                           exchange_every=cfg.exchange_every,
                           boundary=self.dd.boundary,
                           **_dcn_request_kwargs(self.dd))
            # adopt in place; re-point the interior-write hook at the
            # surviving handle so sync_domain keeps working
            new.dd._on_interior_write.clear()
            self.__dict__.update(new.__dict__)
            self.dd.on_interior_write(lambda name: self.sync_domain())
            return self.dd, self.step, self.make_segment

        def on_restore(extras):
            # restored state replaces everything the fast paths cached
            self._inner = None
            self._w = dict(extras) if extras else None

        return run_resilient(
            self.dd, self.step, n_steps, policy=policy,
            ckpt_dir=ckpt_dir, faults=faults, rebuild=rebuild,
            extra_fn=lambda: self._w, on_restore=on_restore,
            fields_fn=lambda: (self._inner if self._inner is not None
                               else self.dd.curr),
            pre_checkpoint=self.sync_domain,
            # always passed: paths with no builder return a
            # reason-carrying decline the driver reports
            make_segment=self.make_segment,
            perf_entry="astaroth")


# ----------------------------------------------------------------------
# initial-condition fields (reference: astaroth/astaroth.cu:84-200)
# ----------------------------------------------------------------------
def _hash64(x: np.ndarray) -> np.ndarray:
    """splitmix64-style avalanche (reference: astaroth.cu:84-89)."""
    x = x.astype(np.uint64)
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x = x ^ (x >> np.uint64(31))
    return x


def _hash_field(shape_zyx) -> np.ndarray:
    """'bad random numbers from -1 to 1' (reference: astaroth.cu:92-114):
    val = hash(x) ^ hash(y) ^ hash(z) scaled to [-1, 1)."""
    nz, ny, nx = shape_zyx
    hz = _hash64(np.arange(nz))[:, None, None]
    hy = _hash64(np.arange(ny))[None, :, None]
    hx = _hash64(np.arange(nx))[None, None, :]
    h = hx ^ hy ^ hz
    val = h.astype(np.float64) / float(np.iinfo(np.uint64).max)
    return (val - 0.5) * 2.0


def _radial_explosion(size: Dim3, prm: MhdParams):
    """Gaussian shell of radially outward velocity
    (reference: astaroth.cu:136-200): amplitude 1, shell radius 0.8,
    width 0.2, origin (0.01, 32 dsy, 50 dsz); components via the unit
    radial vector (algebraically equal to the reference's spherical-
    angle decomposition, without the branch ladder)."""
    ampl, shell_r, width = 1.0, 0.8, 0.2
    ox, oy, oz = 0.01, 32 * prm.dsy, 50 * prm.dsz
    z, y, x = np.meshgrid(np.arange(size.z), np.arange(size.y),
                          np.arange(size.x), indexing="ij")
    xx = x * prm.dsx - ox
    yy = y * prm.dsy - oy
    zz = z * prm.dsz - oz
    rr = np.sqrt(xx * xx + yy * yy + zz * zz)
    u_rad = ampl * np.exp(-((rr - shell_r) ** 2) / (2.0 * width * width))
    with np.errstate(invalid="ignore", divide="ignore"):
        inv_r = np.where(rr > 0, 1.0 / np.where(rr > 0, rr, 1.0), 0.0)
    return (u_rad * xx * inv_r, u_rad * yy * inv_r, u_rad * zz * inv_r)
