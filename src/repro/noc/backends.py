"""Pluggable engine backends behind the one ``simulate()`` surface.

A backend turns a :class:`~repro.noc.topology.Topology` into the
network-level primitives the cycle engine consumes, for ALL physical
channels at once: the engine carries one *stacked* state (every array
has a leading ``n_ch`` axis) and each cycle makes a single backend call
that advances every channel of the fabric together.  That stacking is
the fused hot loop's first win — n_ch identical router updates become
one batched update instead of n_ch separate op sequences in the scan
body.

* ``"jnp"``          — the pure-jnp reference
  (:func:`~repro.core.noc_sim.router.make_fabric_step` vmapped over
  the channel axis),
* ``"pallas"``       — same fabric step with phase-B arbitration
  replaced by the Pallas router-arbiter kernel
  (``kernels/noc_router.py``),
* ``"pallas_fused"`` — the cross-row front of the cycle (drain,
  neighbor push, injection, route lookup:
  :func:`~repro.core.noc_sim.router.fabric_front`) in jnp, then the
  row-local rest (arbitration + output-register and FIFO update) in
  ONE Pallas kernel over channel-folded router rows
  (:func:`~repro.kernels.noc_router.fused_fabric_step_pallas`).

Both Pallas backends compile for TPU v5e (guarded by
``tests/test_tpu_compile.py``); they run in interpret mode on the CPU
only, where they show correctness and nothing about speed.

The protocol:

* ``init(n_ch, depth_max)`` — fresh stacked
  :class:`~repro.core.noc_sim.router.NetState`, arrays shaped
  ``(n_ch, R, ...)`` with FIFOs sized by the static ``depth_max``;
* ``step(state, inject_valid (C, R), inject_flit (C, R, F),
  depths (C,))`` — one cycle; ``depths`` is the *traced* per-channel
  FIFO depth (≤ ``depth_max``), so depth sweeps share one compilation.
  Returns ``(state, inj_ok (C, R), deliver_valid (C, R),
  deliver_flit (C, R, F), link_moves (C,))``.

With a :class:`~repro.noc.faults.FaultModel` (``faults=``) the step
takes one extra traced operand — ``link_mask (R, P') bool`` marking
virtual output ports whose physical link is currently dead (shared by
every channel: the fault is physical).  Masked links drop their grants;
flits wait under backpressure, nothing is lost.  ``faults=None`` (the
default) builds the original mask-free step, so healthy specs stay
bit-identical.  Static dead links/nodes additionally swap the route
table for the fault-aware cut-out tables
(:func:`repro.noc.faults.cut_tables`).

A backend factory takes ``(topology, routing=None, faults=None)``: with a
:class:`~repro.noc.routing.RoutingPolicy` the fabric runs on that
policy's compiled VC/plane-expanded tables (each non-local physical
port unrolled into ``n_vcs`` virtual ports, route tables widened to
``n_planes`` virtual destination planes) and the same step machinery
advances every VC; ``None`` keeps the topology's own base tables —
bit-identical to the pre-VC engine, as is the default
``RoutingPolicy.xy(n_vcs=1)``.

Backends are **flow-agnostic**: they move int32 flits whose ``kind``
field encodes the (class, AXI flow) pair — AR/R reads and AW/W/B
writes look identical down here, only the NI model in ``engine.py``
interprets kinds.  That is what lets one fabric implementation serve
the full AXI4 transaction set unchanged.  Backends are
equivalence-tested flit-for-flit on the paper presets, torus, and
express meshes, including mixed read/write traffic
(``tests/test_noc_api.py -k backend``, ``tests/test_noc_axi4.py``).
Register custom engines with :func:`register_backend`; select one with
``simulate(spec, wl, backend="pallas_fused")``.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.noc_sim.router import (N_FIELDS, NetState, fabric_front,
                                       fabric_tables, make_fabric_step)
from .topology import Topology

__all__ = ["Network", "BACKENDS", "register_backend", "get_backend",
           "list_backends"]


class Network(NamedTuple):
    """All physical channels of one fabric, as the engine sees them."""
    init: Callable[[int, int], NetState]  # (n_ch, depth_max) -> state
    step: Callable                        # (state, iv, flit, depths) -> ...


BACKENDS: dict[str, Callable[..., Network]] = {}


def register_backend(name: str):
    """Register ``fn(topology, routing=None, faults=None) -> Network``
    under ``name``."""
    def deco(fn):
        BACKENDS[name] = fn
        return fn
    return deco


def list_backends() -> list[str]:
    return sorted(BACKENDS)


def _resolve_tables(topo: Topology, routing, faults=None):
    """``(nbr, opp, route, n_vcs)`` — the policy's compiled expanded
    tables, or the topology's base tables when ``routing`` is None.
    A ``FaultModel`` with static dead links/nodes (and ``reroute=True``)
    swaps in the fault-aware cut-out route table instead."""
    if faults is not None and faults.has_static and faults.reroute:
        from .faults import cut_tables
        from .routing import RoutingPolicy
        rt = cut_tables(topo, routing or RoutingPolicy(), faults)
        return rt.nbr, rt.opp, rt.route, rt.n_vcs
    if routing is None:
        nbr, opp, route = topo.tables()
        return nbr, opp, route, 1
    rt = routing.compile(topo)
    return rt.nbr, rt.opp, rt.route, rt.n_vcs


def get_backend(name: str) -> Callable[..., Network]:
    try:
        return BACKENDS[name]
    except KeyError:
        raise KeyError(
            f"unknown backend {name!r}; have {list_backends()}") from None


def _stacked_init(R: int, P: int) -> Callable[[int, int], NetState]:
    def init(n_ch: int, depth_max: int) -> NetState:
        return NetState(
            fifo=jnp.zeros((n_ch, R, P, depth_max, N_FIELDS), jnp.int32),
            count=jnp.zeros((n_ch, R, P), jnp.int32),
            rr_ptr=jnp.zeros((n_ch, R, P), jnp.int32),
            oreg=jnp.zeros((n_ch, R, P, N_FIELDS), jnp.int32),
            oreg_v=jnp.zeros((n_ch, R, P), jnp.bool_),
            lock_in=jnp.full((n_ch, R, P), -1, jnp.int32),
        )
    return init


def _vmapped_network(topo: Topology, routing=None, arbiter=None,
                     faults=None) -> Network:
    nbr, opp, route, n_vcs = _resolve_tables(topo, routing, faults)
    R, P = nbr.shape
    masked = faults is not None
    one = make_fabric_step(nbr, opp, route, arbiter=arbiter, n_vcs=n_vcs,
                           masked=masked)
    # the link mask is shared across channels (the fault is physical)
    axes = (0, 0, 0, 0, None) if masked else (0, 0, 0, 0)
    return Network(init=_stacked_init(R, P),
                   step=jax.vmap(one, in_axes=axes))


@register_backend("jnp")
def _jnp_backend(topo: Topology, routing=None, faults=None) -> Network:
    return _vmapped_network(topo, routing, faults=faults)


@register_backend("pallas")
def _pallas_backend(topo: Topology, routing=None, faults=None) -> Network:
    from repro.kernels.noc_router import router_arbiter_pallas

    def arbiter(out_port, beat, rr_ptr, oreg_free, lock_in):
        winner, pop, new_ptr, new_lock = router_arbiter_pallas(
            out_port, beat, rr_ptr, oreg_free, lock_in)
        return winner, pop.astype(jnp.bool_), new_ptr, new_lock

    return _vmapped_network(topo, routing, arbiter=arbiter, faults=faults)


@register_backend("pallas_fused")
def _pallas_fused_backend(topo: Topology, routing=None,
                          faults=None) -> Network:
    from repro.kernels.noc_router import fused_fabric_step_pallas

    nbr, opp, route, n_vcs = _resolve_tables(topo, routing, faults)
    R, P = nbr.shape
    tables = fabric_tables(nbr, opp, route)

    def front(state, inject_valid, inject_flit, depth, link_mask=None):
        return fabric_front(state, inject_valid, inject_flit, depth,
                            tables, n_vcs=n_vcs, link_mask=link_mask)

    # the link mask is shared across channels (the fault is physical)
    fronts = jax.vmap(front, in_axes=(0, 0, 0, 0, None)
                      if faults is not None else (0, 0, 0, 0))

    def step(state: NetState, inject_valid, inject_flit, depths,
             *fault_args):
        f = fronts(state, inject_valid, inject_flit, depths, *fault_args)
        C = state.count.shape[0]

        def rows(a):                     # (C, R, ...) -> (C*R, ...)
            return a.reshape(C * R, *a.shape[2:])

        out = fused_fabric_step_pallas(
            *map(rows, state), rows(f.drain), rows(f.out_port),
            rows(f.recv_valid), rows(f.recv_flit),
            jnp.repeat(depths.astype(jnp.int32), R))
        fifo, count, rr_ptr, oreg, oreg_v, lock_in = (
            a.reshape(C, R, *a.shape[1:]) for a in out)
        new_state = NetState(fifo=fifo, count=count, rr_ptr=rr_ptr,
                             oreg=oreg, oreg_v=oreg_v > 0, lock_in=lock_in)
        return (new_state, f.inj_ok, f.deliver_valid, f.deliver_flit,
                f.link_moves)

    return Network(init=_stacked_init(R, P), step=step)
