"""NI rings that wrap: the program against the plain reference.

A 3x3 narrow/wide mesh at the smallest ``resp_q_cap`` the analyzer
accepts (8: one below a class's ``max_outstanding`` is refused) and the
default W-ring capacity, under enough traffic that every ring carrying
it wraps at least twice at every NI in the horizon.  Every
``SimResult`` field must equal ``bench/reference/mesh_axi.py`` on the
same schedules, through ``simulate`` and through a 3-point ``sweep``
(the vmapped path).  A probe run of the engine's own step checks that
the traffic wraps the rings without overflowing them: past capacity a
push overwrites a pending entry, which the engine does not check, so
equality there would prove nothing.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.noc_sim.energy import PAPER
from repro.noc import NocSpec, Workload, simulate, stack_schedules, sweep
from repro.noc.api import _depths, _dyn_scalars, jitter_table
from repro.noc.backends import get_backend
from repro.noc.engine import (SimState, build_flow_plan, init_ni,
                              make_step)

REPO = Path(__file__).resolve().parents[1]
CAP = 8
CYCLES = 3500
WIDE_RATES = (0.3, 0.35, 0.4)


def _load(rel: str):
    path = REPO / rel
    mod = importlib.util.spec_from_file_location(
        "rings_" + path.stem, path)
    module = importlib.util.module_from_spec(mod)
    mod.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def bench():
    return _load("bench/reference/mesh_axi.py"), _load("bench/compare.py")


def _spec() -> NocSpec:
    return NocSpec.narrow_wide(3, 3, cycles=CYCLES, resp_q_cap=CAP)


def _config(spec: NocSpec) -> dict:
    """The reference's configuration of ``spec``."""
    return {
        "topology": {"kind": "mesh", "nx": spec.topology.nx,
                     "ny": spec.topology.ny, "routing": "xy"},
        "channels": [{"name": c.name, "depth": c.depth,
                      "width_bits": c.width_bits} for c in spec.channels],
        "classes": [{"name": c.name, "burst_beats": c.burst_beats,
                     "max_outstanding": c.max_outstanding,
                     "payload_bits": c.payload_bits}
                    for c in spec.classes],
        "class_map": dict(spec.class_map),
        "service_lat": spec.service_lat, "cycles": spec.cycles,
        "resp_q_cap": spec.resp_q_cap,
        "pj_per_byte_hop": PAPER.pj_per_byte_hop,
    }


def _workload(write_frac: float, wide_rate: float, seed: int) -> Workload:
    return Workload.make("uniform_random",
                         rates={"narrow": 0.08, "wide": wide_rate},
                         counts={"narrow": 64, "wide": 48}, seed=seed,
                         write_frac=write_frac)


def _ring_probe(spec: NocSpec, times, dests, writes):
    """Peak occupancy ``tail - head`` at the end of any cycle, and the
    pushes over the horizon, of every ring of every NI: ``(B, R, n_q)``
    each, for ``B`` stacked schedules."""
    plan = build_flow_plan(spec)
    net = get_backend("jnp")(spec.topology, spec.routing)
    step = make_step(spec, plan, times.shape[-1], net.step)
    sl, mo, bb = _dyn_scalars(spec, None, None, None)
    n_ch, R = plan.n_ch, spec.n_routers

    def one(t, d, w):
        dyn = {"times": jnp.moveaxis(t, 0, 1),
               "dests": jnp.moveaxis(d, 0, 1),
               "writes": jnp.moveaxis(w, 0, 1),
               "service_lat": jnp.asarray(sl), "max_out": jnp.asarray(mo),
               "burst_beats": jnp.asarray(bb),
               "jitter": jnp.asarray(jitter_table(spec)),
               "depths": jnp.asarray(_depths(spec))}
        zv = jnp.zeros((n_ch, spec.routing.n_vcs), jnp.int32)
        state = SimState(net.init(n_ch, max(_depths(spec))),
                         init_ni(R, plan, spec.resp_q_cap), jnp.int32(0),
                         jnp.zeros((n_ch,), jnp.int32), jnp.int32(0),
                         jnp.int32(0), zv, zv, ())

        def body(carry, _):
            st, peak = carry
            st, _ = step(dyn, st, None)
            return (st, jnp.maximum(peak, st.ni.rq_tail - st.ni.rq_head)), None

        (final, peak), _ = jax.lax.scan(
            body, (state, jnp.zeros_like(state.ni.rq_tail)), None,
            length=spec.cycles)
        return peak, final.ni.rq_tail

    peak, pushes = jax.jit(jax.vmap(one))(times, dests, writes)
    return np.asarray(peak), np.asarray(pushes)


@pytest.mark.parametrize("write_frac", [0.0, 1.0, 0.5],
                         ids=["reads", "writes", "mixed"])
@pytest.mark.parametrize("call", ["simulate", "sweep"])
def test_wrapping_rings_match_reference(bench, call, write_frac):
    ref, compare = bench
    spec = _spec()
    plan = build_flow_plan(spec)
    rates = WIDE_RATES[:1] if call == "simulate" else WIDE_RATES
    wls = [_workload(write_frac, r, seed=11 + j)
           for j, r in enumerate(rates)]
    scheds = [stack_schedules(spec, wl.schedules(spec)) for wl in wls]
    T = max(s[0].shape[-1] for s in scheds)
    times, dests, writes = (
        np.stack([np.pad(s[k], ((0, 0), (0, 0), (0, T - s[k].shape[-1])),
                         constant_values=fill) for s in scheds])
        for k, fill in ((0, 1 << 30), (1, 0), (2, 0)))

    # the traffic wraps every ring it uses at least twice at every NI,
    # never past its capacity (the heaviest read point fills a ring)
    peak, pushes = _ring_probe(spec, times, dests, writes)
    caps = np.asarray([CAP] * plan.n_rq + [plan.w_cap] * plan.n_cls)
    assert np.all(peak <= caps)
    used = pushes.max(axis=(0, 1)) > 0
    assert np.all(pushes[:, :, used] >= 2 * caps[used])
    n_w = plan.n_rq + np.arange(plan.n_cls)
    assert used[n_w].all() == (write_frac > 0)

    if call == "simulate":
        got = [simulate(spec, wls[0])]
    else:
        got = sweep([(spec, wl) for wl in wls])
    want = ref.simulate(_config(spec), times, dests, writes)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert bool(g.drained)
        assert compare.differences(compare.flatten(g), w) == {}
