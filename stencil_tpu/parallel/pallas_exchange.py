"""Pallas remote-DMA halo exchange: the manual-transport data plane.

The true analog of the reference's hand-built transports: where
``exchange.py`` lets XLA lower ``lax.ppermute`` into ICI collectives,
this module issues explicit inter-chip RDMA — each shard writes its
boundary slabs *directly into its neighbors' halo memory* over the ICI
torus, the TPU equivalent of the reference's direct-write colocated
senders (reference: include/stencil/tx_colocated.cuh:30-76
ColoHaloSender — IPC-shared destination allocations written by a
translate kernel, then event+notify). The semaphore choreography
replaces the reference's IPC-event + MPI-notify rendezvous
(reference: src/tx_ipc.cpp:20-105):

* a neighbor barrier (signal left+right, wait 2) guarantees the
  destination buffers are quiescent before any remote write — the
  "you may write" rendezvous;
* per-direction DMA send/recv semaphore pairs replace the IPC event:
  ``wait()`` on the descriptor blocks until both our outgoing slab has
  left and the incoming slab has landed.

Each axis sweep moves full cross-section slabs (other-dim halos
included), so edge/corner data propagates across sweeps exactly as in
the ppermute engine. Off-TPU the kernels run under the Pallas TPU
interpreter, which emulates inter-device DMA on the host mesh — the
analog of the reference exercising IPC transports on one node.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..geometry import Dim3, Radius
from ..telemetry.spans import EXCHANGE, layer_scope
from .exchange import AXIS_NAME, AXIS_TO_DIM, exchange_shard

# collective_id namespace for this module's barrier semaphores; one id
# per grid axis so interleaved per-axis kernels never alias barriers
_COLLECTIVE_ID_BASE = 11


def _axis_slice(ndim: int, dim: int, lo: int, hi: int) -> Tuple:
    idx = [slice(None)] * ndim
    idx[dim] = slice(lo, hi)
    return tuple(idx)


def _interpret_mode():
    from ..ops.pallas_stencil import on_tpu
    return False if on_tpu() else pltpu.InterpretParams()


def _exchange_axis_pallas(arr: jnp.ndarray, axis: int, r_lo: int, r_hi: int,
                          n_dev: int, interpret) -> jnp.ndarray:
    """One axis sweep: remote-write both boundary slabs into the
    periodic neighbors' halo regions."""
    dim = AXIS_TO_DIM[axis]
    name = AXIS_NAME[axis]
    alloc = arr.shape[dim]
    interior = alloc - r_lo - r_hi
    nd = arr.ndim

    def kern(in_ref, out_ref, send_sem, recv_sem):
        nd32 = jnp.int32(n_dev)
        my = lax.axis_index(name)
        right = lax.rem(my + jnp.int32(1), nd32)
        left = lax.rem(my + nd32 - jnp.int32(1), nd32)

        # rendezvous: both neighbors must have entered this kernel
        # (their buffers quiescent) before we write into them
        bsem = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(bsem, inc=1, device_id={name: left})
        pltpu.semaphore_signal(bsem, inc=1, device_id={name: right})
        pltpu.semaphore_wait(bsem, 2)

        copies = []
        if r_lo > 0:
            # right neighbor's lo halo [0, r_lo) <- our interior hi edge
            copies.append(pltpu.make_async_remote_copy(
                src_ref=out_ref.at[_axis_slice(nd, dim, r_lo + interior - r_lo,
                                               r_lo + interior)],
                dst_ref=out_ref.at[_axis_slice(nd, dim, 0, r_lo)],
                send_sem=send_sem.at[0],
                recv_sem=recv_sem.at[0],
                device_id={name: right},
            ))
        if r_hi > 0:
            # left neighbor's hi halo [r_lo+interior, alloc) <- our
            # interior lo edge
            copies.append(pltpu.make_async_remote_copy(
                src_ref=out_ref.at[_axis_slice(nd, dim, r_lo, r_lo + r_hi)],
                dst_ref=out_ref.at[_axis_slice(nd, dim, r_lo + interior,
                                               alloc)],
                send_sem=send_sem.at[1],
                recv_sem=recv_sem.at[1],
                device_id={name: left},
            ))
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

    return pl.pallas_call(
        kern,
        name="exchange_axis",
        out_shape=jax.ShapeDtypeStruct(arr.shape, arr.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            collective_id=_COLLECTIVE_ID_BASE + axis, has_side_effects=True),
        interpret=interpret,
    )(arr)


@layer_scope(EXCHANGE)
def exchange_shard_pallas(arr: jnp.ndarray, radius: Radius,
                          mesh_counts: Dim3,
                          axis_order: Tuple[int, ...] = (0, 1, 2),
                          interpret: Optional[object] = None) -> jnp.ndarray:
    """Fill all halos of one padded (z,y,x) shard with explicit ICI RDMA.
    Same contract as ``exchange.exchange_shard``: call inside
    ``shard_map`` over mesh axes ('x','y','z')."""
    if interpret is None:
        interpret = _interpret_mode()
    for a in axis_order:
        r_lo = radius.face(a, -1)
        r_hi = radius.face(a, 1)
        if r_lo == 0 and r_hi == 0:
            continue
        n_dev = mesh_counts[a]
        if n_dev == 1:
            # periodic self-neighbor: a local slab copy, no DMA
            # (the same-GPU PeerAccessSender analog, tx_cuda.cuh:41-113)
            from .exchange import _single_axis_radius
            arr = exchange_shard(arr, _single_axis_radius(radius, a),
                                 mesh_counts, axis_order=(a,))
            continue
        arr = _exchange_axis_pallas(arr, a, r_lo, r_hi, n_dev, interpret)
    return arr
