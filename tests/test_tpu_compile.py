"""Compile the router kernels and the simulator for a described TPU v5e.

Nothing runs: JAX's TPU compiler is installed here and compiles for a
chip that is described, not attached, so these tests catch what the
chip's compiler (Mosaic for the Pallas kernels) refuses — an in-kernel
gather, an integer argmax, a VMEM overflow — at no chip time.  Shapes
are the paper's 7x7 narrow/wide mesh and a 16x16 one.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one given this file
loads the TPU library.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.noc_sim.router import N_FIELDS
from repro.kernels import noc_router
from repro.noc import (FaultModel, NocSpec, RoutingPolicy, Workload,
                       sim_cache_clear)
from repro.noc.api import _depths, _dyn_scalars, jitter_table, stack_schedules
from repro.noc.backends import _resolve_tables, get_backend
from repro.noc.engine import compiled_sim


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without the chip: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """The backends pick interpret mode from ``jax.default_backend()``,
    which is the CPU here; a compile for the chip must take the TPU
    branch.  The simulators traced that way are dropped afterwards so
    no later CPU test reuses them."""
    monkeypatch.setattr(noc_router, "_interpret_default", lambda: False)
    yield
    sim_cache_clear()


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _smoke_workload():
    return Workload.make("uniform_random",
                         rates={"narrow": 0.05, "wide": 0.25},
                         counts={"narrow": 40, "wide": 40}, seed=0,
                         write_frac=0.5)


def _spec(n: int, **kw) -> NocSpec:
    return NocSpec.narrow_wide(n, n, cycles=8000, **kw)


@pytest.mark.parametrize("n", [7, 16])
def test_fused_kernel_compiles(one_chip, n):
    spec = _spec(n)
    N = len(spec.channels) * spec.n_routers
    P, D, F = 5, spec.channels[0].depth, N_FIELDS

    def s(*shape):
        return _shape(one_chip, shape)

    args = (s(N, P, D, F), s(N, P), s(N, P), s(N, P, F), s(N, P), s(N, P),
            s(N, P), s(N, P), s(N, P), s(N, P, F), s(N))
    compiled = _compile(functools.partial(
        noc_router.fused_fabric_step_pallas, interpret=False), *args)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("variant", ["vc2", "link_mask"])
def test_fused_step_compiles(one_chip, mosaic, variant):
    """The whole pallas_fused cycle (jnp front + kernel) for the 7x7
    mesh: VC-expanded ports, and the fault path's link-mask operand."""
    if variant == "vc2":
        spec = _spec(7, routing=RoutingPolicy.xy(n_vcs=2))
    else:
        spec = _spec(7, faults=FaultModel(link_events=((0, 1, 10, 50),)))
    kw = {"faults": spec.faults} if spec.faults is not None else {}
    net = get_backend("pallas_fused")(spec.topology, spec.routing, **kw)
    nbr, _, _, _ = _resolve_tables(spec.topology, spec.routing, spec.faults)
    R, P = nbr.shape
    C, D = len(spec.channels), spec.channels[0].depth
    state = jax.tree.map(lambda a: _shape(one_chip, a.shape, a.dtype),
                         jax.eval_shape(lambda: net.init(C, D)))
    args = [state, _shape(one_chip, (C, R), jnp.bool_),
            _shape(one_chip, (C, R, N_FIELDS)), _shape(one_chip, (C,))]
    if spec.faults is not None:
        args.append(_shape(one_chip, (R, P), jnp.bool_))
    compiled = _compile(net.step, *args)
    assert "tpu_custom_call" in compiled.as_text()


def test_arbiter_kernel_compiles(one_chip):
    args = [_shape(one_chip, (49, 5)) for _ in range(5)]
    compiled = _compile(functools.partial(
        noc_router.router_arbiter_pallas, interpret=False), *args)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("backend", ["jnp", "pallas_fused"])
def test_simulator_compiles(one_chip, mosaic, backend):
    """The whole jitted simulator of the 7x7 smoke spec — the program
    ``chip_smoke.py`` runs — compiles for one v5e chip."""
    spec = _spec(7)
    times, dests, writes = stack_schedules(
        spec, _smoke_workload().schedules(spec))
    sl, mo, bb = _dyn_scalars(spec, None, None, None)
    operands = (times, dests, writes, sl, mo, bb, jitter_table(spec),
                _depths(spec))
    fn = compiled_sim(spec, times.shape[-1], backend)
    args = [_shape(one_chip, np.shape(x), np.asarray(x).dtype)
            for x in operands]
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (backend == "pallas_fused")
