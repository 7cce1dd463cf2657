"""The multi-chip Jacobi halo path as a strong-scaled grid runs it:
``Jacobi3D(kernel="halo")`` on four devices, the x-free mesh the solver
picks for four ((1, 1, 4), as at 512^3), the two-step pair kernel fed
by radius-2 z slabs, chunks of ``run(32)``. Checked against a
whole-grid float32 ``jnp.roll`` reference written here, and its
``stencil.run`` span and ``exchange_stats()`` against the
collective-permutes of the compiled program. Four of the eight virtual
CPU devices, kernels interpreted."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from stencil_tpu.models.jacobi import Jacobi3D
from stencil_tpu.telemetry.spans import RUN, Tracer, set_tracer

X, Y, Z = 32, 32, 64
CHIPS = 4
#: float32 ulp at 1; field values stay in [0, 1]
ULP = float(np.finfo(np.float32).eps)


def solver():
    j = Jacobi3D(X, Y, Z, dtype=np.float32, devices=jax.devices()[:CHIPS],
                 kernel="halo")
    assert j.kernel_path == "halo" and j.step_stride == 2
    assert list(j.dd.placement.dim()) == [1, 1, CHIPS]
    return j


def roll_reference(state, steps):
    """``steps`` periodic 7-point averages of the (z, y, x) ``state`` with
    the hot (T=1) sphere at (x/3, y/2, z/2) and the cold (T=0) one at
    (2x/3, y/2, z/2), radius x/10, re-imposed after each step
    (reference: bin/jacobi3d.cu:40-85), in float32."""
    gz, gy, gx = np.ogrid[:Z, :Y, :X]
    r2 = (X // 10) ** 2
    hot = (gx - X // 3) ** 2 + (gy - Y // 2) ** 2 + (gz - Z // 2) ** 2 <= r2
    cold = ((gx - 2 * X // 3) ** 2 + (gy - Y // 2) ** 2
            + (gz - Z // 2) ** 2 <= r2)

    def step(_, u):
        s = sum(jnp.roll(u, d, a) for a in range(3) for d in (1, -1))
        return jnp.where(hot, 1.0, jnp.where(cold, 0.0, s / 6.0)
                         ).astype(jnp.float32)

    return np.asarray(jax.jit(lambda u: lax.fori_loop(0, steps, step, u))(
        jnp.asarray(state, jnp.float32)))


@pytest.mark.parametrize("chunks", [(32, 32), (32, 5), (3,)])
def test_halo_run_matches_roll_reference(chunks):
    """Chunks of ``run(k)`` advance the seeded field as the reference
    does: even ``k`` on the pair kernel alone, odd ``k`` with the
    single-step tail after it."""
    j = solver()
    rng = np.random.default_rng(sum(chunks))
    state = rng.random((Z, Y, X), dtype=np.float32)
    j.dd.set_interior("temp", state)
    for k in chunks:
        j.run(k)
    got = j.temperature()
    steps = sum(chunks)
    # the kernel sums the six neighbours in another order than the
    # reference and multiplies by a rounded 1/6 where it divides by 6:
    # up to 2 ulp a step, and averaging never grows an earlier error
    assert np.abs(got - roll_reference(state, steps)).max() <= \
        2 * steps * ULP


# -- the compiled program's collective-permutes ------------------------

ELEM_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
              "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
              "u64": 8}
CP = re.compile(r"^\s*(?:ROOT )?%\S+ = (.*?) "
                r"(collective-permute(?:-done)?)\(")


def computations(hlo_text):
    """Compiled HLO text split into ``{computation name: its lines}``."""
    comps, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    return comps


def reachable(comps, root):
    """``root`` and every computation it calls, at any depth."""
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)",
                               line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                todo += re.findall(r"%([\w.\-]+)", group)
    return seen


def cp_bytes(line):
    """Bytes a ``collective-permute`` (or the ``-done`` of an async one)
    receives: every array of its result; 0 for any other line."""
    m = CP.match(line)
    if not m:
        return 0
    total = 0
    for dtype, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]", m.group(1)):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * ELEM_BYTES[dtype]
    return total


def loop_bodies(hlo_text):
    """``{loop body: (bytes its collective-permutes receive in one
    iteration, the Pallas kernels it calls)}`` for every ``while`` whose
    body moves data between devices."""
    comps = computations(hlo_text)
    out = {}
    for lines in comps.values():
        for line in lines:
            m = re.search(r" while\(.*\bbody=%([\w.\-]+)", line)
            if not m:
                continue
            inner = reachable(comps, m.group(1))
            text = [ln for c in inner for ln in comps[c]]
            nbytes = sum(cp_bytes(ln) for ln in text)
            if nbytes:
                kernels = {k for ln in text for k in re.findall(
                    r"/stencil\.kernel/(\w+)/", ln)}
                out[m.group(1)] = (nbytes, kernels)
    return out


def compiled_text(fn, *args):
    return fn.lower(*args).compile().as_text()


def test_halo_span_and_exchange_stats_match_the_compiled_program():
    """Per shard, a pair call (2 steps) receives two radius-2 z slabs and
    a single-step call two 1-row slabs; the run loop chains two calls an
    iteration, so its pair-kernel body receives two such rounds and its
    tail body two 1-row rounds; no other collective runs.
    ``exchange_stats()`` prices the steady state from the pair call,
    and each chunk's ``stencil.run`` span splits its steps and counts
    its bytes from both calls."""
    j = solver()
    text = compiled_text(j._step_n, j.dd.curr["temp"],
                         jnp.asarray(32, jnp.int32))
    bodies = sorted(loop_bodies(text).values(), key=lambda b: -b[0])
    assert [k for _, k in bodies] == [{"jacobi7_halon"}, {"jacobi7_halo"}]
    (pair_bytes, _), (single_bytes, _) = bodies
    assert pair_bytes == 2 * (2 * 2 * Y * X * 4)
    assert single_bytes == 2 * (2 * 1 * Y * X * 4)
    pair_round, single_round = pair_bytes // 2, single_bytes // 2

    stats = j.exchange_stats()
    assert stats["bytes_per_iteration"] == pair_round / 2 * CHIPS
    assert stats["rounds_per_iteration"] == 0.5

    tracer = Tracer()
    prev = set_tracer(tracer)
    try:
        for iters in (32, 5, 1):
            j.run(iters)
    finally:
        set_tracer(prev)
    got = [sp.attrs for sp in tracer.finished() if sp.name == RUN]
    want = []
    for iters in (32, 5, 1):
        group, tail = iters // 2 * 2, iters % 2
        want.append({"iters": iters, "group_steps": group,
                     "tail_steps": tail, "loop_steps": iters // 4 * 4,
                     "wire_bytes": (group // 2 * pair_round
                                    + tail * single_round) * CHIPS})
    assert got == want
    assert got[0]["wire_bytes"] == stats["bytes_per_iteration"] * 32


def test_astaroth_exchange_stats_match_the_compiled_program():
    """The MHD XLA path on a (1, 1, 4) mesh: one loop body per RK3
    iteration, whose collective-permutes receive what
    ``exchange_stats()`` claims a shard puts on the wire an iteration
    (three radius-3 exchanges of the 8 fields along z)."""
    from stencil_tpu.models.astaroth import Astaroth

    m = Astaroth(16, 16, 64, mesh_shape=(1, 1, CHIPS), dtype=np.float32,
                 devices=jax.devices()[:CHIPS], kernel="xla")
    assert m.kernel_path == "xla"
    m._ensure_w()
    text = compiled_text(m._iter_n, m.dd.curr, m._w,
                         jnp.asarray(1, jnp.int32))
    (nbytes, _), = loop_bodies(text).values()
    # 3 substeps x 2 directions x 3 rows x the padded 22 x 22 face x
    # 8 fields x 4 B
    assert nbytes == 3 * 2 * 3 * 22 * 22 * 8 * 4
    assert m.exchange_stats()["bytes_per_iteration"] == nbytes * CHIPS
