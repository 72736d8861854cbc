"""Vectorized cycle-level model of one FlooNoC physical network.

Faithful to §III-C / §V of the paper:
* input-buffered routers (depth-2 FIFO, registered ready/valid backpressure,
  full throughput),
* **two-cycle router**: an output elastic buffer (register) per port — the
  configuration the paper uses to close timing on the long physical routing
  channels (zero-load: 4 traversals x 2 cycles = 8 router cycles per
  round trip),
* deterministic table-driven routing — the fabric is described by three
  static tables (neighbor / opposite-port / routing, see
  ``repro.noc.topology``), so one step function covers the paper's XY
  mesh, the torus wrap-around variant, and >5-port express-link routers,
* round-robin output arbitration with wormhole burst locking,
* each physical link class (narrow_req / narrow_rsp / wide) is its own
  complete network instance; *within* a network, virtual channels are
  modelled by table expansion (see ``repro.noc.routing``): each
  non-local physical port is unrolled into ``n_vcs`` virtual ports with
  their own FIFO, output register, round-robin pointer and wormhole
  lock, so the ordinary port-level arbitration below *is* VC-aware
  arbitration.  The only genuinely new behaviour is drain
  serialization (``n_vcs > 1``): one physical link still moves at most
  one flit per cycle, so phase A picks a single ready VC per physical
  port, highest VC index (the escape VC) first,
* single-flit packets (header bits travel on parallel lines, no
  header/tail flits).

State layout (R routers, P ports [directions..., Local last], D fifo
depth, F flit fields):
  fifo    : (R, P, D, F) int32   input FIFOs, slot 0 = head
  count   : (R, P)       int32   input occupancy
  rr_ptr  : (R, P)       int32   round-robin pointer per OUT port
  oreg    : (R, P, F)    int32   output elastic buffer
  oreg_v  : (R, P)       bool
  lock_in : (R, P)       int32   wormhole lock (input idx holding the
                                 output, or -1)

Flit fields: [dest_router, src_router, inject_time, kind, txn_id, beat].
``kind`` encodes the (traffic class, AXI flow) pair via
:func:`repro.core.flit.flow_kind` — the fabric never decodes it (flits
of AR/R reads and AW/W/B writes route identically); only the NI model
in ``repro.noc.engine`` interprets kinds.
The per-cycle update (`make_fabric_step`) is the hot loop.  It is two
halves: :func:`fabric_front`, everything read *across* router rows
(drain, neighbor push, NI injection, route lookup — all from the
cycle-start state), and :func:`fabric_update`, the row-local rest
(arbitration, output-register and FIFO update).  Every backend and the
row-sharded farm share the front; the update's arbitration is pluggable
(``arbiter=``), and the fused Pallas kernel in ``kernels/noc_router.py``
replaces the whole update — see ``repro.noc.backends``.

Two hot-path properties this module guarantees (the backends and the
padded-depth sweep mode rely on them):

* the neighbor push is expressed as a static *gather* through the
  precomputed inverse link map (:func:`feeder_tables`) — every input
  port has at most one feeder link, so the seed's per-output-port
  scatter loop and the single gather are exactly equivalent (validated
  at table-build time, not assumed);
* the FIFO depth is a **traced operand**: state is sized by the static
  ``fifo.shape[2]`` max, occupancy checks compare against the dynamic
  ``depth``, so one compilation serves every depth up to the max
  flit-for-flit identically to a natively-sized build.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

F_DEST, F_SRC, F_TIME, F_KIND, F_TXN, F_BEAT = range(6)
N_FIELDS = 6
NO_PORT = 99


class NetState(NamedTuple):
    fifo: jax.Array     # (R, P, D, F)
    count: jax.Array    # (R, P)
    rr_ptr: jax.Array   # (R, P)
    oreg: jax.Array     # (R, P, F)
    oreg_v: jax.Array   # (R, P)
    lock_in: jax.Array  # (R, P) wormhole: input port holding each output (-1)


def init_fabric_state(R: int, P: int, depth: int = 2) -> NetState:
    return NetState(
        fifo=jnp.zeros((R, P, depth, N_FIELDS), jnp.int32),
        count=jnp.zeros((R, P), jnp.int32),
        rr_ptr=jnp.zeros((R, P), jnp.int32),
        oreg=jnp.zeros((R, P, N_FIELDS), jnp.int32),
        oreg_v=jnp.zeros((R, P), jnp.bool_),
        lock_in=jnp.full((R, P), -1, jnp.int32),
    )


def arbiter_jnp(out_port: jax.Array, beat: jax.Array, rr_ptr: jax.Array,
                oreg_free: jax.Array, lock_in: jax.Array):
    """Reference phase-B arbitration: round-robin over requesting input
    heads into free output registers, honoring wormhole locks.

    ``out_port[r, i]`` is the routed output port of input head ``i``
    (``NO_PORT`` when the head slot is empty); ``beat`` its remaining
    burst beats.  Returns ``(winner, pop, new_ptr, new_lock)`` with
    ``winner[r, o]`` the granted input per output (-1: none) and
    ``pop[r, i]`` bool.  The round-robin pointer only advances on
    *unlocked* grants — a wormhole-held output keeps its arbitration
    state, exactly like the engine always behaved (the seed Pallas
    kernel advanced it on locked grants too; that parity bug is fixed
    on both sides).
    """
    R, P = out_port.shape
    o_ids = jnp.arange(P)[None, None, :]
    i_ids = jnp.arange(P)[None, :, None]
    req = (out_port[:, :, None] == o_ids) & oreg_free.astype(bool)[:, None, :]
    locked = lock_in[:, None, :] >= 0
    req &= (~locked) | (i_ids == lock_in[:, None, :])

    prio = (i_ids - rr_ptr[:, None, :]) % P
    score = jnp.where(req, prio, NO_PORT)
    best = jnp.min(score, axis=1)                     # (R, P_out)
    granted = best < NO_PORT
    is_best = (score == best[:, None, :]) & req
    winner = jnp.argmax(is_best.astype(jnp.int32), axis=1)
    winner = jnp.where(granted, winner, -1)

    pop = jnp.any((i_ids == winner[:, None, :]) & granted[:, None, :], axis=2)
    new_ptr = jnp.where(granted & (lock_in < 0), (winner + 1) % P, rr_ptr)

    w_beat = jnp.sum(jnp.where((i_ids == winner[:, None, :])
                               & granted[:, None, :], beat[:, :, None], 0),
                     axis=1)
    new_lock = jnp.where(granted & (w_beat > 1), winner,
                         jnp.where(granted, -1, lock_in))
    return winner, pop, new_ptr, new_lock


def feeder_tables(nbr: np.ndarray,
                  opp: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Invert the link map: ``src_r[r, p]``/``src_o[r, p]`` name the
    router+output-port whose drain feeds input port ``p`` of router
    ``r`` (-1: no feeder).  Raises if two links feed one input port —
    the property that makes the scatter-form neighbor push and the
    gather-form used by the hot loop exactly equivalent.
    """
    R, P = nbr.shape
    # np.nonzero walks C order, so (t_idx, o_idx) lists the wired links
    # exactly as the old  for t: for o:  double loop visited them
    t_idx, o_idx = np.nonzero(nbr[:, :P - 1] >= 0)
    r, p = nbr[t_idx, o_idx], opp[t_idx, o_idx]
    flat = r * P + p
    order = np.argsort(flat, kind="stable")     # ties keep t-major order
    sf = flat[order]
    dup = sf[1:] == sf[:-1]
    if dup.any():
        i_new = order[1:][dup].min()            # first offending link
        i_old = order[np.searchsorted(sf, flat[i_new])]
        raise ValueError(
            f"input port {int(r[i_new])}:{int(p[i_new])} is fed by two "
            f"links ({int(t_idx[i_old])}:{int(o_idx[i_old])} and "
            f"{int(t_idx[i_new])}:{int(o_idx[i_new])})")
    src_r = np.full((R, P), -1, np.int64)
    src_o = np.full((R, P), -1, np.int64)
    src_r[r, p] = t_idx
    src_o[r, p] = o_idx
    for a in (src_r, src_o):
        a.setflags(write=False)
    return src_r, src_o


class FabricTables(NamedTuple):
    """A fabric's static tables as device constants, in the form the
    cycle reads them: ``nbr``/``opp``/``route`` as in
    ``repro.noc.topology``, plus the inverse link map flattened to one
    ``src_flat[r, p] = feeder_row * P + feeder_port`` index with its
    ``has_feed`` mask (:func:`feeder_tables`)."""
    nbr: jax.Array       # (R, P) int32
    opp: jax.Array       # (R, P) int32
    route: jax.Array     # (R, n_dest) int32
    has_feed: jax.Array  # (R, P) bool
    src_flat: jax.Array  # (R, P) int32


def fabric_tables(nbr: np.ndarray, opp: np.ndarray,
                  route: np.ndarray) -> FabricTables:
    src_r, src_o = feeder_tables(nbr, opp)
    P = nbr.shape[1]
    return FabricTables(
        nbr=jnp.asarray(nbr, jnp.int32), opp=jnp.asarray(opp, jnp.int32),
        route=jnp.asarray(route, jnp.int32),
        has_feed=jnp.asarray(src_r >= 0),
        src_flat=jnp.asarray(np.clip(src_r, 0, None) * P
                             + np.clip(src_o, 0, None), jnp.int32))


class Front(NamedTuple):
    """Everything one cycle reads *across* router rows — all of it a
    function of the cycle-start state (the fabric's registers are
    registered, so nothing a router decides this cycle is seen by its
    neighbours before the next)."""
    drain: jax.Array          # (R, P) bool  output registers that move
    recv_valid: jax.Array     # (R, P) bool  input ports receiving a flit
    recv_flit: jax.Array      # (R, P, F)    ... and that flit
    out_port: jax.Array       # (R, P) int32 routed output per head
    inj_ok: jax.Array         # (R,) bool    NI injection accepted
    deliver_valid: jax.Array  # (R,) bool    Local output drained to the NI
    deliver_flit: jax.Array   # (R, F)
    link_moves: jax.Array     # ()  int32    non-local flits moved


def serialize_drain(ready: jax.Array, n_vcs: int) -> jax.Array:
    """At most one drained VC per physical link: highest ready VC index
    wins (escape-VC priority).  Identity when ``n_vcs == 1``."""
    if n_vcs == 1:
        return ready
    R, P = ready.shape
    e = ready[:, :P - 1].reshape(R, (P - 1) // n_vcs, n_vcs)
    rank = jnp.where(e, jnp.arange(n_vcs)[None, None, :], -1)
    win = e & (rank == jnp.max(rank, axis=2, keepdims=True))
    return jnp.concatenate([win.reshape(R, P - 1), ready[:, P - 1:]],
                           axis=1)


def fabric_front(state: NetState, inject_valid: jax.Array,
                 inject_flit: jax.Array, depth: jax.Array,
                 tables: FabricTables, *, n_vcs: int = 1,
                 link_mask: jax.Array | None = None,
                 ext=None) -> Front:
    """Phase A (output-register drain), the neighbor push, NI injection
    and the route lookup — the cross-row half of one cycle.

    ``ext`` maps a per-row array onto the row space the tables index:
    the identity for a whole fabric, the halo-extended strip for a
    row-sharded one (``repro.noc.farm``).  ``link_mask (R, P) bool``
    (fault injection) marks output ports whose link is dead this cycle:
    they never drain, so flits wait under ordinary backpressure."""
    if ext is None:
        def ext(x):
            return x
    R, P = state.count.shape
    L = P - 1
    is_local = jnp.arange(P)[None, :] == L
    # downstream input-FIFO occupancy (registered, cycle start)
    count_x = ext(state.count)
    ds_count = count_x[jnp.clip(tables.nbr, 0, count_x.shape[0] - 1),
                       tables.opp]
    can_drain = jnp.where(is_local, True,        # Local: NI always sinks
                          (tables.nbr >= 0) & (ds_count < depth))
    if link_mask is not None:
        can_drain &= ~link_mask
    drain = serialize_drain(state.oreg_v & can_drain, n_vcs)

    # pushes into neighbor input FIFOs, as ONE static gather through
    # the inverse link map (each input port has at most one feeder,
    # so this is exactly the seed's per-output-port scatter loop)
    recv_valid = tables.has_feed & ext(drain).reshape(-1)[tables.src_flat]
    recv_flit = jnp.where(
        recv_valid[:, :, None],
        ext(state.oreg).reshape(-1, N_FIELDS)[tables.src_flat], 0)

    # NI injection into the Local input port (cycle-start occupancy)
    inj_ok = inject_valid & (state.count[:, L] < depth)
    recv_valid = recv_valid.at[:, L].set(inj_ok)
    recv_flit = recv_flit.at[:, L].set(
        jnp.where(inj_ok[:, None], inject_flit, 0))

    heads = state.fifo[:, :, 0, :]                               # (R, P, F)
    out_port = tables.route[jnp.arange(R)[:, None], heads[:, :, F_DEST]]
    out_port = jnp.where(state.count > 0, out_port, NO_PORT)
    link_moves = jnp.sum(jnp.where(is_local, 0, drain.astype(jnp.int32)))
    return Front(drain=drain, recv_valid=recv_valid, recv_flit=recv_flit,
                 out_port=out_port, inj_ok=inj_ok,
                 deliver_valid=drain[:, L], deliver_flit=state.oreg[:, L, :],
                 link_moves=link_moves)


def fabric_update(state: NetState, front: Front, depth: jax.Array,
                  arbiter=None) -> NetState:
    """Phase B (arbitration into freed output registers) and the input
    FIFO pop/push — the row-local half of one cycle, which the fused
    Pallas kernel replaces (``kernels/noc_router.py``).

    Wormhole: a multi-flit packet (burst) locks its output port from
    the first beat until the tail beat (F_BEAT <= 1) has passed, so
    burst beats are never interleaved — the paper's burst semantics."""
    arb = arbiter_jnp if arbiter is None else arbiter
    R = state.count.shape[0]
    r_idx = jnp.arange(R)[:, None]
    heads = state.fifo[:, :, 0, :]                               # (R, P, F)
    oreg_free = (~state.oreg_v) | front.drain
    winner, pop, new_ptr, new_lock = arb(
        front.out_port, heads[:, :, F_BEAT], state.rr_ptr, oreg_free,
        state.lock_in)

    any_grant = winner >= 0
    flit_to_oreg = heads[r_idx, jnp.clip(winner, 0)]             # (R, P, F)
    new_oreg_v = (state.oreg_v & ~front.drain) | any_grant
    new_oreg = jnp.where(any_grant[:, :, None], flit_to_oreg, state.oreg)

    D = state.fifo.shape[2]                              # static max depth
    shifted = jnp.concatenate(
        [state.fifo[:, :, 1:, :], jnp.zeros_like(state.fifo[:, :, :1, :])],
        axis=2)
    fifo = jnp.where(pop[:, :, None, None], shifted, state.fifo)
    count = state.count - pop.astype(jnp.int32)

    slot = jnp.clip(count, 0, D - 1)
    write = front.recv_valid & (count < depth)
    onehot_slot = jax.nn.one_hot(slot, D, dtype=jnp.bool_)       # (R, P, D)
    sel = write[:, :, None] & onehot_slot
    fifo = jnp.where(sel[..., None], front.recv_flit[:, :, None, :], fifo)
    count = count + write.astype(jnp.int32)
    return NetState(fifo=fifo, count=count, rr_ptr=new_ptr, oreg=new_oreg,
                    oreg_v=new_oreg_v, lock_in=new_lock)


def make_fabric_step(nbr: np.ndarray, opp: np.ndarray, route: np.ndarray,
                     arbiter=None, n_vcs: int = 1, masked: bool = False):
    """Build the one-cycle update for a fabric described by static
    tables (see ``repro.noc.topology``): ``nbr[r, p]`` neighbor router
    per output port (-1 none, local port last), ``opp[r, p]`` the input
    port the link feeds, ``route[r, d]`` the routed output port.  The
    step is :func:`fabric_front` then :func:`fabric_update`.

    ``arbiter`` replaces the phase-B arbitration (same signature and
    semantics as :func:`arbiter_jnp`) — the hook the Pallas backend
    plugs into.

    ``n_vcs > 1`` declares the tables VC-expanded (``repro.noc.routing``):
    the ``P - 1`` non-local ports are ``(P - 1) / n_vcs`` physical links
    x ``n_vcs`` virtual channels, port ``p = link * n_vcs + vc``.  The
    update is identical except phase A drains at most one VC per
    physical link per cycle, preferring the highest ready VC index — the
    escape VC, so dateline traffic can always make progress.  With the
    default ``n_vcs=1`` the built step is the exact original (the
    serialization branch is not even traced).

    Returns ``step(state, inject_valid, inject_flit, depth) ->
    (new_state, inject_ok (R,), deliver_valid (R,), deliver_flit (R, F),
    link_moves scalar)``.  ``depth`` is the *dynamic* FIFO depth (traced
    int32, ``1 <= depth <= state.fifo.shape[2]``); the state arrays are
    sized by the static max so depth sweeps share one compilation.

    ``masked=True`` (fault injection, ``repro.noc.faults``) appends one
    traced operand: ``step(state, iv, iflit, depth, link_mask)`` with
    ``link_mask (R, P) bool`` marking output ports whose link is
    currently dead.  A masked link simply never drains — flits wait in
    the output register under ordinary backpressure (no loss), and heal
    transparently when the mask clears.  The default build does not
    trace the mask at all, keeping the healthy path bit-identical.
    """
    if (nbr.shape[1] - 1) % n_vcs:
        raise ValueError(
            f"{nbr.shape[1] - 1} non-local ports do not fold into "
            f"{n_vcs} VCs")
    tables = fabric_tables(nbr, opp, route)

    def step(state: NetState, inject_valid: jax.Array,
             inject_flit: jax.Array, depth: jax.Array, *fault_args):
        link_mask = fault_args[0] if masked else None
        front = fabric_front(state, inject_valid, inject_flit, depth,
                             tables, n_vcs=n_vcs, link_mask=link_mask)
        return (fabric_update(state, front, depth, arbiter), front.inj_ok,
                front.deliver_valid, front.deliver_flit, front.link_moves)

    return step
