"""The solvers name their layers in the programs they trace: every halo
transfer sits under the ``stencil.exchange`` scope, the write of the
interior back into the padded field under ``stencil.interior``, the
stencil arithmetic under ``stencil.kernel``. Checked in the traced
program (each eqn's name stack) and in the compiled one (each op's
``op_name`` metadata, which a profile reports as ``tf_op``), on four of
the eight virtual CPU devices: mesh (1, 1, 4), 16^3 per shard."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu.analysis.jaxprs import iter_eqns
from stencil_tpu.telemetry import get_tracer
from stencil_tpu.telemetry.spans import (EXCHANGE, INTERIOR, KERNEL,
                                         LAYER_SCOPES, RUN, layer_scope)

LOCAL = (16, 16, 16)
#: model and kernel= path -> interior write-backs in one traced program
CASES = {"astaroth-xla": 24, "jacobi-xla": 1, "jacobi-halo": 1}


def build(case):
    devices = jax.devices()[:4]
    if case.startswith("astaroth"):
        from stencil_tpu.models.astaroth import Astaroth

        m = Astaroth(16, 16, 64, mesh_shape=(1, 1, 4), dtype=np.float32,
                     devices=devices, kernel="xla")
        m._ensure_w()
        return m._iter_n, (m.dd.curr, m._w, jnp.asarray(1, jnp.int32))
    from stencil_tpu.models.jacobi import Jacobi3D

    m = Jacobi3D(16, 16, 64, mesh_shape=(1, 1, 4), dtype=np.float32,
                 devices=devices, kernel=case.split("-")[1])
    assert m.kernel_path == case.split("-")[1]
    return m._step_n, (m.dd.curr["temp"], jnp.asarray(2, jnp.int32))


def op_names(hlo_text, opcode):
    """op_name of every instruction of ``opcode`` in compiled HLO."""
    out = []
    for line in hlo_text.splitlines():
        if re.search(rf"= \S+ {re.escape(opcode)}\(", line):
            m = re.search(r'op_name="([^"]*)"', line)
            out.append(m.group(1) if m else "")
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_layers_are_named(case):
    fn, args = build(case)
    # a host span open around the trace names nothing inside it
    with get_tracer().span(RUN, iters=1):
        jaxpr = jax.make_jaxpr(fn)(*args)
        text = fn.lower(*args).compile().as_text()

    eqns = list(iter_eqns(jaxpr.jaxpr))
    stacks = {id(e): str(e.source_info.name_stack) for e in eqns}
    writes = [e for e in eqns if e.primitive.name == "dynamic_update_slice"
              and tuple(e.invars[1].aval.shape) == LOCAL]
    assert len(writes) == CASES[case]
    assert all(INTERIOR in stacks[id(e)] for e in writes)
    perms = [e for e in eqns if e.primitive.name == "ppermute"]
    assert perms and all(EXCHANGE in stacks[id(e)] for e in perms)

    cps = (op_names(text, "collective-permute")
           + op_names(text, "collective-permute-start"))
    assert cps and all(f"/{EXCHANGE}/" in n for n in cps), cps
    assert any(f"/{INTERIOR}/" in n
               for n in op_names(text, "dynamic-update-slice"))
    assert f"/{KERNEL}/" in text
    assert RUN not in text


def test_wrap_run_span_counts_loop_and_tail_steps():
    """On the one-chip wrap path (pair kernel, two calls per loop
    iteration) `stencil.run` says how many steps the copy-free loop
    body advanced, how many the tails, and the extent the kernels step
    (`stored_zyx`); other paths say none of it."""
    from stencil_tpu.models.jacobi import Jacobi3D
    from stencil_tpu.telemetry.spans import Tracer, set_tracer

    j = Jacobi3D(16, 16, 16, mesh_shape=(1, 1, 1), dtype=np.float32,
                 devices=jax.devices()[:1], kernel="wrap")
    x = Jacobi3D(16, 16, 16, mesh_shape=(1, 1, 1), dtype=np.float32,
                 devices=jax.devices()[:1], kernel="xla")
    tracer = Tracer()
    prev = set_tracer(tracer)
    try:
        for iters in (32, 5, 1):
            j.run(iters)
        x.run(1)
    finally:
        set_tracer(prev)
    attrs = [sp.attrs for sp in tracer.finished() if sp.name == RUN]
    grid = "16x16x16"
    assert attrs == [
        {"iters": 32, "loop_steps": 32, "tail_steps": 0,
         "stored_zyx": grid},
        {"iters": 5, "loop_steps": 4, "tail_steps": 1, "stored_zyx": grid},
        {"iters": 1, "loop_steps": 0, "tail_steps": 1, "stored_zyx": grid},
        {"iters": 1},
    ]


def test_layer_scope_is_a_named_scope_only_for_layers():
    @layer_scope(EXCHANGE)
    def sweep(x, depth):
        # a decorated function traced inside itself keeps one scope per
        # call and restores the outer stack on the way out
        return sweep(x + 1, depth - 1) if depth else x * 2

    def f(x):
        with layer_scope(KERNEL):
            y = sweep(x, 1)
        return y - 1

    eqns = jax.make_jaxpr(f)(jnp.ones(3)).jaxpr.eqns
    stacks = [str(e.source_info.name_stack) for e in eqns]
    assert stacks == [f"{KERNEL}/{EXCHANGE}",
                      f"{KERNEL}/{EXCHANGE}/{EXCHANGE}", ""]
    assert set(LAYER_SCOPES) == {KERNEL, EXCHANGE, INTERIOR}
    with pytest.raises(ValueError):
        with layer_scope(RUN):
            pass
