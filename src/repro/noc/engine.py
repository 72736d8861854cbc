"""Generalized N-channel FlooNoC cycle engine — fused hot loop with a
full AXI4 flow model.

Every traffic class decomposes into the five AXI channels
(:data:`repro.core.flit.AXI_FLOWS`): reads are AR -> R, writes are
AW -> W -> B.  The *fabric* (``make_fabric_step`` + every backend in
:mod:`repro.noc.backends`) stays completely flow-agnostic — routers
move int32 flits whose ``kind`` field encodes (class, flow); only the
batched NI model here interprets kinds.  The NI write path (paper
§III-A, journal version's end-to-end parallel streams):

* **AW injection** — a scheduled write becomes a single-flit AW
  candidate on its ``aw`` channel, gated by the issuing *lane*'s write
  ROB budget: reads and writes hold separate credits, and a class's
  ``max_outstanding`` is split (near-)evenly across its ``n_streams``
  AXI ID lanes, NOT pooled per (NI, class) — two streams of one class
  stall independently (journal version's parallel multi-stream ROB);
* **W data trailing the AW grant** — the moment an AW wins injection,
  a W burst entry (``burst_beats`` beats) is pushed into the class's
  W ring; its beats stream onto the ``w`` channel from the next cycle
  on, wormhole-atomic exactly like R response bursts;
* **B responses on the response plumbing** — when the last W beat
  lands, the target NI pushes a single-flit B entry into the response
  ring of the class's ``b`` channel (sharing the ring — and therefore
  the FIFO order — with R entries mapped to the same channel), ready
  after the class's service latency; B delivery at the source
  completes the write and frees its ROB slot.

Per channel, the injection policy is derived from which flows the
``class_map`` routes onto it:

* one response ring, nothing else      -> direct streaming (paper's
  dedicated narrow_rsp network),
* request-direction flows only         -> static priority: single-flit
  address flows (AR/AW, latency-critical classes first), then W rings;
  a started W burst is atomic and pins the channel,
* response rings and request flows mixed -> per-NI round-robin over
  [response rings..., one slot per class with request-direction flows]
  with burst atomicity (the wide-only ablation).  Within a class slot,
  AR/AW beat a fresh W burst; a started W burst pins the slot.

The candidate structure is built so that **read-only traffic is
flit-for-flit identical to the pre-AXI4 engine** (golden-checked): W
rings and AW/B flows that never carry traffic never win arbitration,
never advance round-robin state differently, and never reorder pushes.

Service latency is a per-class *(mean, jitter)* distribution: the
``service_lat`` operand is a per-class vector and a seeded static
jitter table adds a per-request offset (indexed by txn id) to every
R/B ready time — both traced, so latency-distribution sweeps vmap like
every other knob, and ``jitter=0`` reproduces the fixed-latency model
exactly.

The per-cycle structure keeps the fused-hot-loop shape: ONE stacked
fabric call for all channels, batched ``(R, n_cls)`` NI state (one
column per (class, AXI ID stream) *lane* — see :class:`FlowPlan`), the
response rings as one field-major ``(6, n_rq, resp_q_cap, R)`` array
(the per-class W rings live in a separate small ``(6, n_cls, w_cap,
R)`` array — W occupancy is bounded by the write ROB credit, so it
never pays the response-ring capacity) read and pushed with one-hot
selects over the slot axis, no scatter or gather, and FIFO depth as a
traced operand (padded-depth sweeps share one compilation).  The
engine also watches liveness: ``max_stall_cycles`` (longest streak
with transactions in flight but zero fabric activity) and ``drained``
(every scheduled transaction completed) make deadlock observable, and
per-VC FIFO occupancy (sum + peak per channel) shows *where* flits sit
when the spec's :class:`~repro.noc.routing.RoutingPolicy` runs
multiple virtual channels — a wedged single-VC torus pins VC0 full
while the escape VC of a ``n_vcs>=2`` dateline policy keeps draining.

The routing policy is threaded through statically: the backend gets
``(spec.topology, spec.routing)`` and runs on the policy's compiled
VC/plane-expanded tables; for multi-plane policies (O1TURN, Valiant)
the NI picks each transaction's plane with a deterministic hash of
(source, destination, txn id) folded into the flit's *virtual*
destination ``plane * R + dest``, so every beat of a burst — and every
retransmission of the same txn — takes the same path while different
transactions spread across planes.

Static structure (topology, channel list, max FIFO depth, class->
channel flow map, horizon) keys one jitted simulator per backend in a
stats-instrumented cache (:func:`sim_cache_stats`); dynamic knobs
(schedules incl. the write mask, per-class service latency + jitter
table, outstanding limits, burst lengths, FIFO depths) are traced
operands so ``jax.vmap`` batches whole sweeps in one jit.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import itertools
import threading
import time
from collections import OrderedDict
from dataclasses import replace
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.flit import flow_kind
from repro.core.noc_sim.router import (F_BEAT, F_KIND, F_SRC, F_TIME,
                                       F_TXN)
from .backends import get_backend
from .spec import NocSpec

BIG = 1 << 30

# ring-entry field order, shared by the response and W ring arrays
Q_READY, Q_DEST, Q_BEATS, Q_TIME0, Q_TXN, Q_KIND = range(6)
N_QFIELDS = 6

# static length of the seeded per-class jitter table (prime, so txn-id
# indexing doesn't alias power-of-two burst/count periodicities)
JITTER_TABLE_LEN = 251

# the scan step's phases, in cycle order: every operation of the step
# runs under exactly one of these outermost ``jax.named_scope``s, so a
# profiler trace attributes device time per phase by op name
PHASES = ("noc.ni_issue", "noc.fabric", "noc.ni_rings", "noc.stats")


class ShardInfo(NamedTuple):
    """Row-sharding geometry for a spatially-partitioned fabric step
    (:mod:`repro.noc.farm` tier b).  ``make_step(..., shard=)`` builds
    the NI update for ``local_R`` contiguous router rows living on one
    device of a ``shard_map`` mesh axis ``axis`` with ``n`` shards;
    per-cycle scalar reductions (stall streak, VC occupancy) become
    ``lax.psum`` over that axis so every shard observes the global
    value, keeping sharded runs flit-for-flit identical to the
    single-device engine.  ``None`` (the default everywhere) leaves the
    healthy single-device program byte-identical."""
    axis: str
    n: int
    local_R: int
    global_R: int


def req_kind(cls_idx: int) -> int:
    """Legacy two-flow kind tag (pinned baseline engine only)."""
    return 2 * cls_idx


def rsp_kind(cls_idx: int) -> int:
    """Legacy two-flow kind tag (pinned baseline engine only)."""
    return 2 * cls_idx + 1


class ChannelPlan(NamedTuple):
    """Legacy read-shaped plan (kept for the pinned baseline engine and
    collectives-derivation tests): request flows are the AR channels,
    response queues the R channels — exactly the pre-AXI4 vocabulary."""
    n_cls: int
    n_ch: int
    n_q: int
    queue_of_class: tuple[int, ...]   # class -> response queue id
    reqs_on: tuple[tuple[int, ...], ...]   # channel -> req class ids (prio order)
    queues_on: tuple[tuple[int, ...], ...]  # channel -> rsp queue ids


def build_channel_plan(spec: NocSpec) -> ChannelPlan:
    n_cls, n_ch = len(spec.classes), len(spec.channels)
    # queues: one per distinct response channel, in first-appearance order
    rsp_ch_of_q: list[int] = []
    queue_of_class = []
    for cls in spec.classes:
        ch = spec.rsp_channel(cls.name)
        if ch not in rsp_ch_of_q:
            rsp_ch_of_q.append(ch)
        queue_of_class.append(rsp_ch_of_q.index(ch))
    # per-channel request classes, latency-critical (single-beat) first
    reqs_on = []
    for c in range(n_ch):
        ids = [i for i, cls in enumerate(spec.classes)
               if spec.req_channel(cls.name) == c]
        ids.sort(key=lambda i: (spec.classes[i].burst_beats > 1, i))
        reqs_on.append(tuple(ids))
    queues_on = tuple(
        tuple(q for q, ch in enumerate(rsp_ch_of_q) if ch == c)
        for c in range(n_ch))
    return ChannelPlan(n_cls, n_ch, len(rsp_ch_of_q),
                       tuple(queue_of_class), tuple(reqs_on), queues_on)


class FlowPlan(NamedTuple):
    """Static routing of the five AXI flows onto channels and rings,
    derived from a NocSpec (the *logical* half of the fabric; the
    physical half is the spec's :class:`~repro.noc.topology.Topology`).

    The plan's unit is the **lane** — one (class, AXI ID stream) pair.
    A class declaring ``n_streams=S`` contributes S consecutive lanes
    (class-major order), each with its own schedule pointer, its own
    slice of the class's per-direction ROB credits, its own W ring and
    its own round-robin slot, so independent streams never
    false-serialize (journal version's end-to-end parallel multi-stream
    support).  With every class at the default ``n_streams=1`` lanes
    coincide with classes and the plan is field-for-field the pre-
    stream plan — ``n_cls`` keeps its name but counts lanes.

    Ring space: response rings (one per distinct channel carrying any
    R or B flow, first-appearance order) come first, then one W ring
    per lane (id ``n_rq + lane``).  Head/tail/started bookkeeping is
    one stacked ``(R, n_q)`` set, with ``head_sent`` beside it: the
    beats of each head entry already sent, so an entry is written once,
    at push, and its remaining beats are its pushed ``Q_BEATS`` minus
    that count.  The entry storage is field-major and split: response
    rings are ``(6, n_rq, resp_q_cap, R)`` and W rings ``(6, n_lanes,
    w_cap, R)``.  Routers sit on the minor (lane) axis, so a one-hot
    select over the slots is a dense elementwise pass and the head read
    sums across slots, not across lanes.  ``w_cap`` is derived from the
    classes' declared ``max_outstanding`` — a W ring can never hold
    more pending bursts than the write ROB grants credits, so it doesn't
    pay the big response-ring capacity (raising ``max_outstanding``
    above the declared value via the traced override can overflow the
    W ring, the same unchecked-overflow contract as ``resp_q_cap``).
    """
    n_cls: int                       # number of LANES (see class doc)
    n_ch: int
    n_rq: int                        # response rings (channel-keyed)
    n_q: int                         # n_rq + n_lanes (per-lane W rings)
    w_cap: int                       # static W-ring capacity per lane
    rq_of_r: tuple[int, ...]         # lane -> ring its R entries enter
    rq_of_b: tuple[int, ...]         # lane -> ring its B entries enter
    chan_of_q: tuple[int, ...]       # every queue -> physical channel
    # channel -> ordered single-flit address-flow slots ((lane, "ar"|"aw"))
    singles_on: tuple[tuple[tuple[int, str], ...], ...]
    wqs_on: tuple[tuple[int, ...], ...]   # channel -> W ring ids
    rqs_on: tuple[tuple[int, ...], ...]   # channel -> response ring ids
    # channel -> lane ids with ANY request-direction flow on it (the
    # round-robin lane slots of mixed channels), prio order
    rr_classes: tuple[tuple[int, ...], ...]
    push_order_r: tuple[int, ...]    # R-push sequential order (lane ids)
    cls_of_lane: tuple[int, ...]     # lane -> declaring class index
    stream_of_lane: tuple[int, ...]  # lane -> AXI ID stream within class


def build_flow_plan(spec: NocSpec) -> FlowPlan:
    n_ch = len(spec.channels)
    # lanes: one per (class, stream), class-major — every class with
    # n_streams=1 contributes exactly one lane, so single-stream specs
    # reproduce the per-class plan verbatim
    lanes = [(ci, s) for ci, c in enumerate(spec.classes)
             for s in range(c.n_streams)]
    n_ln = len(lanes)
    lane_cls = [spec.classes[ci] for ci, _ in lanes]
    ch_of = {f: [spec.flow_channel(c.name, f) for c in lane_cls]
             for f in ("ar", "aw", "w", "r", "b")}
    # response rings: channel-keyed, first-appearance order over the R
    # flows then the B flows — R-only specs get exactly the pre-AXI4
    # ring order, B flows sharing an R channel share its ring (and its
    # FIFO order: the shared-channel ablation covers acks too).  Lanes
    # of one class share that class's channels, so streams share rings;
    # deliveries de-mux on the lane-specific flit kind.
    ring_ch: list[int] = []
    for ch in [*ch_of["r"], *ch_of["b"]]:
        if ch not in ring_ch:
            ring_ch.append(ch)
    n_rq = len(ring_ch)
    prio = sorted(range(n_ln),
                  key=lambda l: (lane_cls[l].burst_beats > 1, l))
    singles_on = tuple(
        tuple((i, f) for i in prio for f in ("ar", "aw")
              if ch_of[f][i] == c)
        for c in range(n_ch))
    wqs_on = tuple(tuple(n_rq + i for i in prio if ch_of["w"][i] == c)
                   for c in range(n_ch))
    rqs_on = tuple(tuple(q for q in range(n_rq) if ring_ch[q] == c)
                   for c in range(n_ch))
    rr_classes = tuple(
        tuple(i for i in prio
              if c in (ch_of["ar"][i], ch_of["aw"][i], ch_of["w"][i]))
        for c in range(n_ch))
    # sequential R-push order of the read-only engine: channel-major,
    # then the channel's priority order — preserves exact ring-slot
    # ordering when several lanes push one shared ring per cycle
    push_order_r = tuple(i for c in range(n_ch) for i in prio
                         if ch_of["ar"][i] == c)
    return FlowPlan(
        n_cls=n_ln, n_ch=n_ch, n_rq=n_rq, n_q=n_rq + n_ln,
        w_cap=max(2, max(c.max_outstanding for c in spec.classes)),
        rq_of_r=tuple(ring_ch.index(ch) for ch in ch_of["r"]),
        rq_of_b=tuple(ring_ch.index(ch) for ch in ch_of["b"]),
        chan_of_q=tuple(ring_ch) + tuple(ch_of["w"]),
        singles_on=singles_on, wqs_on=wqs_on, rqs_on=rqs_on,
        rr_classes=rr_classes, push_order_r=push_order_r,
        cls_of_lane=tuple(ci for ci, _ in lanes),
        stream_of_lane=tuple(s for _, s in lanes))


class _PlanArrays(NamedTuple):
    """Static index/selector arrays derived from a FlowPlan, shared by
    every cycle of the batched NI update.  Kept as *numpy* so index
    lookups stay concrete at trace time (a jnp constant would turn
    ``ar_ch[i]`` into a traced op inside the scan body).  All arrays
    are lane-indexed; the flit ``kind`` encodes (lane, flow), so a
    stream's identity rides the fabric's opaque kind field and
    deliveries de-mux back to the issuing lane."""
    ar_ch: np.ndarray         # (n_lanes,) channel per flow
    aw_ch: np.ndarray
    w_ch: np.ndarray
    r_ch: np.ndarray
    b_ch: np.ndarray
    ar_kinds: np.ndarray      # (n_cls,) flit kind tags per flow
    aw_kinds: np.ndarray
    r_kinds: np.ndarray
    w_kinds: np.ndarray
    b_kinds: np.ndarray
    # response-ring push machinery: slot s in [0, 2*n_lanes) is the R
    # push of lane s or the B push of lane s-n_lanes, each one masked
    # select into its ring (W pushes go to the per-lane W-ring array,
    # where each ring has exactly one pusher — no ordering needed).
    q_of_slot: np.ndarray     # (2*n_lanes,) destination ring per push slot
    push_before: np.ndarray   # (2n, 2n) 1 where slot j pushes the same
    #                           ring as slot i earlier in sequential order
    q_onehot: np.ndarray      # (2*n_lanes, n_rq) slot -> ring one-hot


def _plan_arrays(spec: NocSpec, plan: FlowPlan) -> _PlanArrays:
    n_cls = plan.n_cls
    lane_cls = [spec.classes[ci] for ci in plan.cls_of_lane]
    ch = {f: np.asarray([spec.flow_channel(c.name, f)
                         for c in lane_cls], np.int32)
          for f in ("ar", "aw", "w", "r", "b")}
    kinds = {f: np.asarray([flow_kind(i, f) for i in range(n_cls)],
                           np.int32) for f in ("ar", "aw", "r", "w", "b")}
    q_of_slot = np.concatenate([
        np.asarray(plan.rq_of_r, np.int64),
        np.asarray(plan.rq_of_b, np.int64)])
    # sequential order: R pushes (read-only engine's channel-major
    # order) first, then B pushes — read-only traffic never activates
    # the trailing slots, so its slot order is exact
    pos = np.empty(2 * n_cls, np.int64)
    pos[list(plan.push_order_r)] = np.arange(n_cls)
    pos[n_cls:] = np.arange(n_cls, 2 * n_cls)
    push_before = ((pos[None, :] < pos[:, None])
                   & (q_of_slot[None, :] == q_of_slot[:, None])
                   ).astype(np.int32)
    q_onehot = (q_of_slot[:, None] == np.arange(plan.n_rq)[None, :]
                ).astype(np.int32)
    return _PlanArrays(
        ar_ch=ch["ar"], aw_ch=ch["aw"], w_ch=ch["w"], r_ch=ch["r"],
        b_ch=ch["b"], ar_kinds=kinds["ar"], aw_kinds=kinds["aw"],
        r_kinds=kinds["r"], w_kinds=kinds["w"], b_kinds=kinds["b"],
        q_of_slot=q_of_slot.astype(np.int32), push_before=push_before,
        q_onehot=q_onehot)


class NIState(NamedTuple):
    ptr: jax.Array          # (R, n_cls) schedule pointers
    out_r: jax.Array        # (R, n_cls) outstanding reads (ROB credits)
    out_w: jax.Array        # (R, n_cls) outstanding writes (write ROB)
    rq_head: jax.Array      # (R, n_q) rsp rings first, then W rings
    rq_tail: jax.Array      # (R, n_q)
    head_sent: jax.Array    # (R, n_q) beats of the head entry sent
    rq: jax.Array           # (6, n_rq, resp_q_cap, R) response rings
    wq: jax.Array           # (6, n_cls, w_cap, R) per-class W rings
    w_started: jax.Array    # (R, n_q) burst mid-stream (inject atomicity)
    inj_rr: jax.Array       # (R, n_ch) mixed-channel round-robin
    # per-class read metrics: (R, n_cls), measured at the requester
    lat_sum: jax.Array
    lat_max: jax.Array
    done: jax.Array
    beats_rx: jax.Array
    first_t: jax.Array
    last_t: jax.Array
    # per-class write metrics: latency/done at the issuing NI (B
    # arrival), W-beat counts/span at the receiving NI
    w_lat_sum: jax.Array
    w_lat_max: jax.Array
    w_done: jax.Array
    w_beats_rx: jax.Array
    w_first_t: jax.Array
    w_last_t: jax.Array


class FaultState(NamedTuple):
    """NI robustness state, live only when the spec carries a
    :class:`~repro.noc.faults.FaultModel` (``spec.faults is None``
    compiles all of this out — the healthy program is untouched).

    The pending table tracks every in-flight transaction per (NI, lane):
    ``p_cap`` slots hold (txn id, dest, original issue time, current
    attempt start / retry due time, retries left, direction).  A slot is
    free when ``pend_txn < 0``; inserts take the first free slot and
    completions match by txn id, so late or duplicate responses (a
    retried transaction whose original eventually arrives) are
    recognized and dropped instead of double-freeing ROB credits.
    ``p_cap = 2 * w_cap`` covers the read + write ROB budgets; raising
    ``max_outstanding`` past the declared value via the traced override
    can overflow it — the same unchecked-overflow contract as
    ``resp_q_cap`` and the W rings."""
    pend_txn: jax.Array     # (R, n_cls, p_cap) int32, -1 = free slot
    pend_dest: jax.Array    # (R, n_cls, p_cap)
    pend_t0: jax.Array      # (R, n_cls, p_cap) original issue cycle
    pend_at: jax.Array      # (R, n_cls, p_cap) attempt start / retry due
    pend_left: jax.Array    # (R, n_cls, p_cap) retries left
    pend_wait: jax.Array    # (R, n_cls, p_cap) bool: attempt in flight
    pend_wr: jax.Array      # (R, n_cls, p_cap) bool: write transaction
    # degradation counters
    retries: jax.Array      # (R, n_cls) retry re-injections
    timeouts: jax.Array     # (R, n_cls) watchdog firings
    slverr: jax.Array       # (R, n_cls) SLVERR error responses
    dlv_fault: jax.Array    # (R, n_cls) completions while a fault active
    beats_fault: jax.Array  # (R, n_cls) data beats rx while fault active
    flc: jax.Array          # scalar: sum over cycles of #dead links
    fcyc: jax.Array         # scalar: cycles with any fault active


def fault_p_cap(plan: "FlowPlan") -> int:
    """Pending-table capacity per lane: reads + writes each hold up to
    ``w_cap`` (= max declared ``max_outstanding``) credits."""
    return 2 * plan.w_cap


def init_faults(R: int, n_cls: int, p_cap: int) -> FaultState:
    z3 = jnp.zeros((R, n_cls, p_cap), jnp.int32)
    b3 = jnp.zeros((R, n_cls, p_cap), jnp.bool_)
    z2 = jnp.zeros((R, n_cls), jnp.int32)
    return FaultState(
        pend_txn=jnp.full((R, n_cls, p_cap), -1, jnp.int32),
        pend_dest=z3, pend_t0=z3, pend_at=z3, pend_left=z3,
        pend_wait=b3, pend_wr=b3,
        retries=z2, timeouts=z2, slverr=z2, dlv_fault=z2, beats_fault=z2,
        flc=jnp.int32(0), fcyc=jnp.int32(0))


class SimState(NamedTuple):
    net: NamedTuple         # stacked NetState, (n_ch, R, ...) leaves
    ni: NIState
    cycle: jax.Array
    moves: jax.Array        # (n_ch,) link traversals per channel
    cur_stall: jax.Array    # scalar: current zero-activity streak
    max_stall: jax.Array    # scalar: longest such streak
    vc_occ_sum: jax.Array   # (n_ch, n_vcs) summed per-VC FIFO occupancy
    vc_occ_max: jax.Array   # (n_ch, n_vcs) peak per-VC FIFO occupancy
    fs: NamedTuple | tuple = ()   # FaultState, or () when faults=None


def init_ni(R: int, plan: FlowPlan, cap: int) -> NIState:
    zc = jnp.zeros((R, plan.n_cls), jnp.int32)
    zq = jnp.zeros((R, plan.n_q), jnp.int32)
    big = jnp.full((R, plan.n_cls), BIG, jnp.int32)
    return NIState(
        ptr=zc, out_r=zc, out_w=zc, rq_head=zq, rq_tail=zq, head_sent=zq,
        rq=jnp.zeros((N_QFIELDS, plan.n_rq, cap, R), jnp.int32),
        wq=jnp.zeros((N_QFIELDS, plan.n_cls, plan.w_cap, R), jnp.int32),
        w_started=jnp.zeros((R, plan.n_q), jnp.bool_),
        inj_rr=jnp.zeros((R, plan.n_ch), jnp.int32),
        lat_sum=zc, lat_max=zc, done=zc, beats_rx=zc,
        first_t=big, last_t=zc,
        w_lat_sum=zc, w_lat_max=zc, w_done=zc, w_beats_rx=zc,
        w_first_t=big, w_last_t=zc)


def _one_hot(slot: jax.Array, cap: int) -> jax.Array:
    """``(..., R)`` ring slots as a ``(..., cap, R)`` mask over the slot
    axis."""
    return jnp.arange(cap)[:, None] == slot[..., None, :]


def _ring_read(ring: jax.Array, head: jax.Array) -> jax.Array:
    """The entries at ``head`` ``(R, Q)`` of a ``(6, Q, cap, R)`` ring
    array, ``(6, R, Q)``: a one-hot masked sum over the slot axis (the
    other slots add zero), one dense pass with no gather."""
    at = _one_hot(head.T % ring.shape[-2], ring.shape[-2])
    return jnp.sum(jnp.where(at, ring, 0), axis=-2).transpose(0, 2, 1)


def make_step(spec: NocSpec, plan: FlowPlan, T: int, net_step,
              shard: ShardInfo | None = None):
    """Build the per-cycle transition. Dynamic operands arrive via the
    closure-free ``dyn`` dict (schedules + write mask + scalar knobs +
    jitter table + depths); ``net_step`` is the backend's stacked
    one-cycle fabric update (:class:`repro.noc.backends.Network`).

    ``shard`` (row-sharded farm mode, :mod:`repro.noc.farm`) narrows the
    NI update to that shard's ``local_R`` contiguous router rows: the
    shard's own NI state holds its local rows only, while the *global*
    row id (``local + axis_index * local_R``) is what
    enters every flit's src field and the multi-plane hash — those ids
    travel the fabric and come back as response destinations, so they
    must live in the global router id space.  Per-cycle liveness /
    occupancy scalars are psummed over the shard axis.  ``shard=None``
    builds the exact single-device program."""
    R = spec.n_routers if shard is None else shard.local_R
    R_virt = spec.n_routers        # global id space (plane folding, src)
    cap = spec.resp_q_cap
    w_cap = plan.w_cap
    pa = _plan_arrays(spec, plan)
    n_planes = spec.routing.n_planes
    n_vcs = spec.routing.n_vcs
    rows = jnp.arange(R)
    rq_ids = jnp.arange(plan.n_rq)
    n_cls = plan.n_cls

    # fault machinery is built ONLY when the spec declares a FaultModel:
    # the healthy program below is literally the pre-fault code path
    faulted = spec.faults is not None
    if faulted and shard is not None:
        raise NotImplementedError(
            "row-sharded simulation does not support FaultModel specs "
            "yet (the event link-masks and retry jitter are keyed to "
            "global rows); run faulted specs unsharded")
    if faulted:
        from .faults import dynamic_events
        _, _, _masks = dynamic_events(spec.topology, spec.routing,
                                      spec.faults, spec.cycles)
        M_np = np.asarray(_masks)            # (E, R, P') static per-event
        p_cap = fault_p_cap(plan)
        lane_ids = jnp.arange(n_cls)
        p_ids = jnp.arange(p_cap)

    def cycle(phase, dyn, state: SimState):
        phase("noc.ni_issue")
        times, dests = dyn["times"], dyn["dests"]     # (R, n_cls, T)
        writes = dyn["writes"]                        # (R, n_cls, T)
        service_lat = dyn["service_lat"]              # (n_cls,)
        jitter = dyn["jitter"]                        # (n_cls, JT)
        max_out, burst_beats = dyn["max_out"], dyn["burst_beats"]
        ni = state.ni
        now = state.cycle
        # global router id of each local row: what flits carry as src
        # (responses route back to it) and what the plane hash keys on
        rows_g = rows if shard is None \
            else rows + jax.lax.axis_index(shard.axis) * R

        if faulted:
            # ---- link mask from the event schedule ----------------------
            ev_fail, ev_heal = dyn["ev_fail"], dyn["ev_heal"]   # (E,)
            timeout = dyn["timeout"]                   # (n_cls,) lanes
            max_retries = dyn["max_retries"]           # scalar
            backoff = dyn["backoff"]                   # scalar
            fs = state.fs
            dead_e = (ev_fail <= now) & (now < ev_heal)          # (E,)
            link_mask = jnp.any(
                dead_e[:, None, None] & jnp.asarray(M_np), axis=0)

            # ---- watchdog scan: timeout -> retry or SLVERR --------------
            act = fs.pend_txn >= 0
            tmo = timeout[None, :, None]
            to = act & fs.pend_wait & (tmo > 0) & (now - fs.pend_at >= tmo)
            exh = to & (fs.pend_left <= 0)             # retries exhausted
            rearm = to & (fs.pend_left > 0)
            # exponential backoff with seeded jitter (reuses the service-
            # jitter table, keyed off (txn, attempt, NI) so concurrent
            # retries desynchronize instead of thundering back together)
            used = jnp.clip(max_retries - fs.pend_left, 0, 16)
            jidx = (fs.pend_txn * 7 + used * 13
                    + rows[:, None, None] * 131) % JITTER_TABLE_LEN
            jt_l = jnp.asarray(dyn["jitter"], jnp.int32)
            joff = jnp.abs(jt_l[lane_ids[None, :, None], jidx])
            due_at = now + (backoff << used) + joff
            # SLVERR: drop the transaction, free its ROB credit — the
            # requester observes an error response instead of data
            ni = ni._replace(
                out_r=ni.out_r - jnp.sum(
                    exh & ~fs.pend_wr, axis=2).astype(jnp.int32),
                out_w=ni.out_w - jnp.sum(
                    exh & fs.pend_wr, axis=2).astype(jnp.int32))
            fs = fs._replace(
                pend_txn=jnp.where(exh, -1, fs.pend_txn),
                pend_wait=fs.pend_wait & ~to,
                pend_left=fs.pend_left - rearm.astype(jnp.int32),
                pend_at=jnp.where(rearm, due_at, fs.pend_at),
                timeouts=fs.timeouts
                + jnp.sum(to, axis=2).astype(jnp.int32),
                slverr=fs.slverr + jnp.sum(exh, axis=2).astype(jnp.int32))

            # ---- retry candidate per lane: first backoff-expired slot ---
            rdy = (fs.pend_txn >= 0) & ~fs.pend_wait & (fs.pend_at <= now)
            has_rt = jnp.any(rdy, axis=2)              # (R, n_cls)
            rslot = jnp.argmax(rdy, axis=2)

            def _take_slot(a, s):
                return jnp.take_along_axis(a, s[:, :, None],
                                           axis=2)[:, :, 0]

            r_txn = _take_slot(fs.pend_txn, rslot)
            r_dest = _take_slot(fs.pend_dest, rslot)
            r_wr = _take_slot(fs.pend_wr, rslot)

        # ---- source side: per-class AR/AW candidates (ROB gated) --------
        p = jnp.clip(ni.ptr, 0, T - 1)[:, :, None]
        t_sel = jnp.take_along_axis(times, p, axis=2)[:, :, 0]
        is_wr = jnp.take_along_axis(writes, p, axis=2)[:, :, 0] > 0
        due = (ni.ptr < T) & (t_sel <= now)            # (R, n_cls)
        want_ar = due & ~is_wr & (ni.out_r < max_out[None, :])
        want_aw = due & is_wr & (ni.out_w < max_out[None, :])
        req_d = jnp.take_along_axis(dests, p, axis=2)[:, :, 0]
        txn_src = ni.ptr
        if faulted:
            # a pending retry preempts the lane's fresh candidate: same
            # injection machinery, but dest/txn come from the pending
            # table and no new schedule entry is consumed
            want_ar = jnp.where(has_rt, ~r_wr, want_ar)
            want_aw = jnp.where(has_rt, r_wr, want_aw)
            req_d = jnp.where(has_rt, r_dest, req_d)
            txn_src = jnp.where(has_rt, r_txn, ni.ptr)

        # ---- ring heads (response rings + W rings), all at once ---------
        h = jnp.concatenate([
            _ring_read(ni.rq, ni.rq_head[:, :plan.n_rq]),
            _ring_read(ni.wq, ni.rq_head[:, plan.n_rq:])],
            axis=2)                                    # (6, R, n_q)
        have = ni.rq_head < ni.rq_tail
        h_ready = have & (h[Q_READY] <= now)
        h_dest, h_time0, h_txn, h_kind = (h[Q_DEST], h[Q_TIME0], h[Q_TXN],
                                          h[Q_KIND])
        h_beats = h[Q_BEATS] - ni.head_sent            # beats left to send
        h_held = ni.w_started & (h_beats > 0)          # burst mid-stream

        # ---- per-channel injection policy (small static loop) -----------
        sel_ar: dict[int, jax.Array] = {}   # class -> AR selected
        sel_aw: dict[int, jax.Array] = {}   # class -> AW selected
        sel_q: dict[int, jax.Array] = {}    # ring -> head streamed
        hold_of_ch: dict[int, jax.Array] = {}
        iv_cols, flit_cols = [], []
        zero = jnp.zeros((R,), jnp.int32)
        false = jnp.zeros((R,), jnp.bool_)

        def pick_head(q, s, dest, kind, txn, time, beat):
            sel_q[q] = sel_q.get(q, false) | s
            return (jnp.where(s, h_dest[:, q], dest),
                    jnp.where(s, h_kind[:, q], kind),
                    jnp.where(s, h_txn[:, q], txn),
                    jnp.where(s, h_time0[:, q], time),
                    jnp.where(s, h_beats[:, q], beat))

        def pick_single(i, fl, s, dest, kind, txn, beat):
            if fl == "ar":
                sel_ar[i] = sel_ar.get(i, false) | s
                kind_v = int(pa.ar_kinds[i])
            else:
                sel_aw[i] = sel_aw.get(i, false) | s
                kind_v = int(pa.aw_kinds[i])
            return (jnp.where(s, req_d[:, i], dest),
                    jnp.where(s, kind_v, kind),
                    jnp.where(s, txn_src[:, i], txn),
                    jnp.where(s, 1, beat))

        for c in range(plan.n_ch):
            singles = plan.singles_on[c]
            wqs, rqs = plan.wqs_on[c], plan.rqs_on[c]
            rr_cls = plan.rr_classes[c]
            dest = kind = txn = beat = zero
            time = jnp.broadcast_to(now, (R,)).astype(jnp.int32)
            if not singles and not wqs and not rqs:    # idle channel
                valid = false
            elif not singles and not wqs and len(rqs) == 1:
                # dedicated response channel: stream the ring head
                q = rqs[0]
                valid = h_ready[:, q]
                sel_q[q] = valid
                dest, kind, txn = h_dest[:, q], h_kind[:, q], h_txn[:, q]
                time, beat = h_time0[:, q], h_beats[:, q]
            elif not rqs:
                # request-direction channel: a started W burst pins the
                # channel; else static priority — address flows
                # (latency-critical classes first), then fresh W bursts
                taken = false
                for q in wqs:
                    s = h_held[:, q] & ~taken
                    taken = taken | s
                    dest, kind, txn, time, beat = pick_head(
                        q, s, dest, kind, txn, time, beat)
                for i, fl in singles:
                    cand = want_ar[:, i] if fl == "ar" else want_aw[:, i]
                    s = cand & ~taken
                    taken = taken | s
                    dest, kind, txn, beat = pick_single(
                        i, fl, s, dest, kind, txn, beat)
                for q in wqs:
                    s = h_ready[:, q] & ~taken
                    taken = taken | s
                    dest, kind, txn, time, beat = pick_head(
                        q, s, dest, kind, txn, time, beat)
                valid = taken
            else:
                # mixed channel: round-robin over [response rings...,
                # class slots...] with burst atomicity — an in-flight
                # burst (response or W) excludes everything else
                cand = [("rq", q) for q in rqs] + [("cls", i)
                                                   for i in rr_cls]
                n_cand = len(cand)

                def cls_valid(i):
                    v = false
                    if int(pa.ar_ch[i]) == c:
                        v = v | want_ar[:, i]
                    if int(pa.aw_ch[i]) == c:
                        v = v | want_aw[:, i]
                    if int(pa.w_ch[i]) == c:
                        v = v | h_ready[:, plan.n_rq + i]
                    return v

                cand_valid = jnp.stack(
                    [h_ready[:, q] for q in rqs]
                    + [cls_valid(i) for i in rr_cls], axis=1)
                rr = ni.inj_rr[:, c] % n_cand
                order = (jnp.arange(n_cand)[None, :] + rr[:, None]) % n_cand
                ordered = jnp.take_along_axis(cand_valid, order, axis=1)
                first = jnp.argmax(ordered, axis=1)
                has_any = jnp.any(cand_valid, axis=1)
                choice = jnp.take_along_axis(order, first[:, None],
                                             axis=1)[:, 0]
                hold = false
                for k, q in enumerate(rqs):
                    hq = h_held[:, q]
                    choice = jnp.where(hq & ~hold, k, choice)
                    hold = hold | hq
                for k2, i in enumerate(rr_cls):
                    if int(pa.w_ch[i]) != c:
                        continue
                    hq = h_held[:, plan.n_rq + i]
                    choice = jnp.where(hq & ~hold, len(rqs) + k2, choice)
                    hold = hold | hq
                hold_of_ch[c] = hold
                valid0 = has_any | hold

                valid = false
                for k, (tag, idx) in enumerate(cand):
                    if tag == "rq":
                        s = valid0 & (choice == k) & h_ready[:, idx]
                        valid = valid | s
                        dest, kind, txn, time, beat = pick_head(
                            idx, s, dest, kind, txn, time, beat)
                        continue
                    # class slot: held W first, then AR/AW, then fresh W
                    i = idx
                    s_slot = valid0 & (choice == k)
                    taken_in = false
                    wq = plan.n_rq + i if int(pa.w_ch[i]) == c else None
                    if wq is not None:
                        s = s_slot & h_held[:, wq]
                        taken_in = taken_in | s
                        dest, kind, txn, time, beat = pick_head(
                            wq, s, dest, kind, txn, time, beat)
                    if int(pa.ar_ch[i]) == c:
                        s = s_slot & want_ar[:, i] & ~taken_in
                        taken_in = taken_in | s
                        dest, kind, txn, beat = pick_single(
                            i, "ar", s, dest, kind, txn, beat)
                    if int(pa.aw_ch[i]) == c:
                        s = s_slot & want_aw[:, i] & ~taken_in
                        taken_in = taken_in | s
                        dest, kind, txn, beat = pick_single(
                            i, "aw", s, dest, kind, txn, beat)
                    if wq is not None:
                        s = s_slot & h_ready[:, wq] & ~taken_in
                        taken_in = taken_in | s
                        dest, kind, txn, time, beat = pick_head(
                            wq, s, dest, kind, txn, time, beat)
                    valid = valid | taken_in
            iv_cols.append(valid)
            if n_planes > 1:
                # multi-plane policy: deterministic per-(src, dest, txn)
                # plane choice, folded into the *virtual* destination
                # plane*R + dest.  Every beat of a burst (constant
                # dest/txn at its ring head) hashes to the same plane,
                # so wormhole trains never straddle paths.
                plane = (rows_g * 7 + dest * 13 + txn * 31) % n_planes
                dest = plane * R_virt + dest
            flit = jnp.stack([dest, rows_g, time, kind, txn, beat], axis=1)
            flit_cols.append(jnp.where(valid[:, None], flit, 0))

        # ---- ONE stacked fabric step for every channel ------------------
        iv = jnp.stack(iv_cols)                        # (n_ch, R)
        iflit = jnp.stack(flit_cols)                   # (n_ch, R, F)
        phase("noc.fabric")
        if faulted:
            net, ok_ch, dv_ch, df_ch, lm = net_step(
                state.net, iv, iflit, dyn["depths"], link_mask)
        else:
            net, ok_ch, dv_ch, df_ch, lm = net_step(
                state.net, iv, iflit, dyn["depths"])

        # per-VC input-FIFO occupancy (non-local ports; virtual port
        # q = link * n_vcs + vc under the routing policy's table fold)
        phase("noc.stats")
        occ = jnp.sum(net.count[:, :, :-1].reshape(
            net.count.shape[0], R, -1, n_vcs), axis=(1, 2))   # (n_ch, V)
        if shard is not None:      # fabric-wide occupancy, every shard
            occ = jax.lax.psum(occ, shard.axis)
        vc_occ_sum = state.vc_occ_sum + occ
        vc_occ_max = jnp.maximum(state.vc_occ_max, occ)

        # ---- pointer / ROB / ring-head updates --------------------------
        phase("noc.ni_rings")
        inj_ar = jnp.stack(
            [ok_ch[int(pa.ar_ch[i])] & sel_ar[i]
             if i in sel_ar else false for i in range(n_cls)], axis=1)
        inj_aw = jnp.stack(
            [ok_ch[int(pa.aw_ch[i])] & sel_aw[i]
             if i in sel_aw else false for i in range(n_cls)], axis=1)
        sent = jnp.stack(
            [ok_ch[plan.chan_of_q[q]] & sel_q[q]
             if q in sel_q else false for q in range(plan.n_q)], axis=1)
        inj_rr = ni.inj_rr
        for c, hold in hold_of_ch.items():
            inj_rr = inj_rr.at[:, c].add((ok_ch[c] & ~hold).astype(jnp.int32))

        txn0 = txn_src        # injected txn per lane (== pre-advance ptr
        #                       for fresh issues; pending txn on a retry)
        if faulted:
            # retries advance no pointer and consume no fresh credit —
            # the transaction still owns its original ROB slot
            inj_any = inj_ar | inj_aw
            fresh = inj_any & ~has_rt
            retry_inj = inj_any & has_rt
            inj = fresh.astype(jnp.int32)
            cr_ar = (inj_ar & ~has_rt).astype(jnp.int32)
            cr_aw = (inj_aw & ~has_rt).astype(jnp.int32)
        else:
            inj = (inj_ar | inj_aw).astype(jnp.int32)
            cr_ar = inj_ar.astype(jnp.int32)
            cr_aw = inj_aw.astype(jnp.int32)
        # the ring keeps each entry as pushed: a sent beat only counts
        # in head_sent, which restarts at 0 when the head advances
        left = h_beats - sent.astype(jnp.int32)
        ni = ni._replace(
            ptr=ni.ptr + inj, out_r=ni.out_r + cr_ar,
            out_w=ni.out_w + cr_aw, inj_rr=inj_rr,
            rq_head=ni.rq_head + (sent & (left <= 0)).astype(jnp.int32),
            head_sent=jnp.where(sent, jnp.where(left > 0,
                                                ni.head_sent + 1, 0),
                                ni.head_sent),
            w_started=jnp.where(sent, left > 0, ni.w_started))

        if faulted:
            # pending-table bookkeeping: fresh issues insert at the first
            # free slot; a granted retry re-arms its slot's watchdog
            oh_r = (p_ids[None, None, :] == rslot[:, :, None]) \
                & retry_inj[:, :, None]
            slot_f = jnp.argmax(fs.pend_txn < 0, axis=2)
            oh_f = (p_ids[None, None, :] == slot_f[:, :, None]) \
                & fresh[:, :, None]
            now3 = jnp.broadcast_to(now, oh_f.shape).astype(jnp.int32)
            fs = fs._replace(
                pend_txn=jnp.where(oh_f, txn0[:, :, None], fs.pend_txn),
                pend_dest=jnp.where(oh_f, req_d[:, :, None],
                                    fs.pend_dest),
                pend_t0=jnp.where(oh_f, now3, fs.pend_t0),
                pend_at=jnp.where(oh_f | oh_r, now3, fs.pend_at),
                pend_wait=fs.pend_wait | oh_f | oh_r,
                pend_wr=jnp.where(oh_f, is_wr[:, :, None], fs.pend_wr),
                pend_left=jnp.where(
                    oh_f, jnp.broadcast_to(max_retries, oh_f.shape
                                           ).astype(jnp.int32),
                    fs.pend_left),
                retries=fs.retries + retry_inj.astype(jnp.int32))

        # ---- deliveries: gather each flow through its static channel ----
        def flow_dv(ch_arr, kind_arr):
            dv = dv_ch[ch_arr].T                       # (R, n_cls)
            df = jnp.moveaxis(df_ch[ch_arr], 0, 1)     # (R, n_cls, F)
            return dv & (df[..., F_KIND] == kind_arr[None, :]), df

        is_ar, df_ar = flow_dv(pa.ar_ch, pa.ar_kinds)
        is_w, df_w = flow_dv(pa.w_ch, pa.w_kinds)
        is_r, df_r = flow_dv(pa.r_ch, pa.r_kinds)
        is_b, df_b = flow_dv(pa.b_ch, pa.b_kinds)
        is_w_last = is_w & (df_w[..., F_BEAT] <= 1)

        # ---- ring pushes: one masked select per push slot ---------------
        # response slot layout: [R pushes | B pushes] per class; the
        # slot's ring slot = its ring's tail + #earlier same-ring
        # pushes, so same-cycle pushes into one ring land on distinct
        # slots and the selects commute.  W pushes land in the
        # per-class W-ring array, where each ring has exactly one
        # pusher per cycle (its own AW grant): one select for all
        sl = service_lat[None, :].astype(jnp.int32)
        jt = jnp.asarray(jitter, jnp.int32)

        def jit_of(txn, src):                          # (R, n_cls) offsets
            # key the per-request draw by (issuing NI, txn id) so the
            # jitter decorrelates across sources — same-j transactions
            # at different NIs must not share an offset (the table
            # length is prime, so the affine fold cycles through all
            # of it); with a zero table this is exactly the
            # deterministic model
            idx = ((txn + 131 * src) % JITTER_TABLE_LEN)[:, :, None]
            return jnp.take_along_axis(
                jnp.broadcast_to(jt[None, :, :],
                                 (R, n_cls, JITTER_TABLE_LEN)),
                idx, axis=2)[:, :, 0]

        bb = jnp.broadcast_to(burst_beats[None, :], (R, n_cls))
        push_r = jnp.stack([
            now + sl + jit_of(df_ar[..., F_TXN], df_ar[..., F_SRC]),
            df_ar[..., F_SRC], bb, df_ar[..., F_TIME],
            df_ar[..., F_TXN],
            jnp.broadcast_to(pa.r_kinds[None, :], (R, n_cls)),
        ])                                             # (6, R, n_cls)
        push_b = jnp.stack([
            now + sl + jit_of(df_w[..., F_TXN], df_w[..., F_SRC]),
            df_w[..., F_SRC], jnp.ones((R, n_cls), jnp.int32),
            df_w[..., F_TIME], df_w[..., F_TXN],
            jnp.broadcast_to(pa.b_kinds[None, :], (R, n_cls)),
        ])
        push_w = jnp.stack([
            jnp.broadcast_to(now + 1, (R, n_cls)), req_d, bb,
            jnp.broadcast_to(now, (R, n_cls)), txn0,
            jnp.broadcast_to(pa.w_kinds[None, :], (R, n_cls)),
        ]).astype(jnp.int32)
        active = jnp.concatenate([is_ar, is_w_last], axis=1)
        push_val = jnp.concatenate([push_r, push_b],
                                   axis=2).astype(jnp.int32)
        offset = jnp.einsum("rj,ij->ri", active.astype(jnp.int32),
                            jnp.asarray(pa.push_before))
        tail_of_slot = ni.rq_tail[:, pa.q_of_slot]     # (R, 2*n_cls)
        slot_p = (tail_of_slot + offset) % cap
        # slot-major push operands: each slot's select reads whole rows
        # (strided column slices cost XLA's CPU backend ~2.5x per call)
        vals, slots, on = jnp.moveaxis(push_val, 2, 0), slot_p.T, active.T
        rq = ni.rq
        for s, q in enumerate(pa.q_of_slot):
            at = _one_hot(slots[s], cap) & on[s]         # (cap, R)
            at = at & (rq_ids == q)[:, None, None]       # (n_rq, cap, R)
            rq = jnp.where(at, vals[s][:, None, None, :], rq)
        tail_w = ni.rq_tail[:, plan.n_rq:].T           # (n_cls, R)
        at_w = _one_hot(tail_w % w_cap, w_cap) & inj_aw.T[:, None, :]
        wq = jnp.where(at_w, jnp.moveaxis(push_w, 1, 2)[:, :, None], ni.wq)
        tail_inc = jnp.concatenate(
            [active.astype(jnp.int32) @ pa.q_onehot,
             inj_aw.astype(jnp.int32)], axis=1)        # (R, n_q)
        ni = ni._replace(rq=rq, wq=wq, rq_tail=ni.rq_tail + tail_inc)

        # ---- per-class per-direction metrics, vectorized ----------------
        phase("noc.stats")
        last_r = is_r & (df_r[..., F_BEAT] <= 1)
        if faulted:
            # completion gating through the pending table: only a
            # response matching a live pending txn completes (a stale
            # duplicate after a retry, or after SLVERR, is dropped);
            # latency is measured from the ORIGINAL issue time, so a
            # retried transaction pays its full end-to-end delay
            eq_r = (fs.pend_txn == df_r[..., F_TXN][:, :, None]) \
                & ~fs.pend_wr & (fs.pend_txn >= 0)
            hit_r = last_r & jnp.any(eq_r, axis=2)
            t0_r = jnp.take_along_axis(
                fs.pend_t0, jnp.argmax(eq_r, axis=2)[:, :, None],
                axis=2)[:, :, 0]
            lat_r = jnp.where(hit_r, now - t0_r, 0)
            li_r = hit_r.astype(jnp.int32)
            eq_b = (fs.pend_txn == df_b[..., F_TXN][:, :, None]) \
                & fs.pend_wr & (fs.pend_txn >= 0)
            hit_b = is_b & jnp.any(eq_b, axis=2)
            t0_b = jnp.take_along_axis(
                fs.pend_t0, jnp.argmax(eq_b, axis=2)[:, :, None],
                axis=2)[:, :, 0]
            lat_b = jnp.where(hit_b, now - t0_b, 0)
            li_b = hit_b.astype(jnp.int32)
            clear = (eq_r & last_r[:, :, None]) | (eq_b & is_b[:, :, None])
            fs = fs._replace(
                pend_txn=jnp.where(clear, -1, fs.pend_txn))
        else:
            lat_r = jnp.where(last_r, now - df_r[..., F_TIME], 0)
            li_r = last_r.astype(jnp.int32)
            lat_b = jnp.where(is_b, now - df_b[..., F_TIME], 0)
            li_b = is_b.astype(jnp.int32)
        ni = ni._replace(
            beats_rx=ni.beats_rx + is_r.astype(jnp.int32),
            first_t=jnp.where(is_r, jnp.minimum(ni.first_t, now),
                              ni.first_t),
            last_t=jnp.where(is_r, jnp.maximum(ni.last_t, now),
                             ni.last_t),
            done=ni.done + li_r,
            lat_sum=ni.lat_sum + lat_r,
            lat_max=jnp.maximum(ni.lat_max, lat_r),
            out_r=ni.out_r - li_r,
            w_beats_rx=ni.w_beats_rx + is_w.astype(jnp.int32),
            w_first_t=jnp.where(is_w, jnp.minimum(ni.w_first_t, now),
                                ni.w_first_t),
            w_last_t=jnp.where(is_w, jnp.maximum(ni.w_last_t, now),
                               ni.w_last_t),
            w_done=ni.w_done + li_b,
            w_lat_sum=ni.w_lat_sum + lat_b,
            w_lat_max=jnp.maximum(ni.w_lat_max, lat_b),
            out_w=ni.out_w - li_b,
        )

        # ---- liveness: stall streak while transactions are in flight ----
        activity = (jnp.any(iv & ok_ch) | jnp.any(dv_ch)
                    | (jnp.sum(lm) > 0))
        pending = jnp.any((ni.out_r + ni.out_w) > 0)
        if shard is not None:      # global liveness: stall streaks must
            flags = jax.lax.psum(   # agree bit-for-bit across shards
                jnp.stack([activity, pending]).astype(jnp.int32),
                shard.axis)
            activity, pending = flags[0] > 0, flags[1] > 0
        cur = jnp.where(pending & ~activity, state.cur_stall + 1, 0)
        new_moves = state.moves + lm.astype(jnp.int32)
        if faulted:
            # degradation counters: what kept flowing while links were down
            fault_on = jnp.any(link_mask)
            fs = fs._replace(
                flc=fs.flc + jnp.sum(dead_e.astype(jnp.int32)),
                fcyc=fs.fcyc + fault_on.astype(jnp.int32),
                dlv_fault=fs.dlv_fault + jnp.where(fault_on,
                                                   li_r + li_b, 0),
                beats_fault=fs.beats_fault + jnp.where(
                    fault_on,
                    is_r.astype(jnp.int32) + is_w.astype(jnp.int32), 0))
        return SimState(net, ni, now + 1, new_moves, cur,
                        jnp.maximum(state.max_stall, cur),
                        vc_occ_sum, vc_occ_max,
                        fs if faulted else state.fs)

    def step(dyn, state: SimState, _):
        # one named scope at a time: phase(name) leaves the current
        # phase's scope and enters the next one's (metadata only — the
        # traced computation is the same with or without the scopes)
        with contextlib.ExitStack() as scope:
            def phase(name):
                scope.close()
                scope.enter_context(jax.named_scope(name))
            return cycle(phase, dyn, state), None

    return step


# --------------------------------------------------------------------- #
# compiled-simulator cache (stats-instrumented, partitioned per backend)
# --------------------------------------------------------------------- #
SIM_CACHE_MAXSIZE = 256          # per backend partition

_caches: dict[str, OrderedDict] = {}
_stats = {"hits": 0, "misses": 0, "evictions": 0}
_cache_lock = threading.Lock()


def sim_cache_stats() -> dict:
    """Cache behavior of :func:`compiled_sim` (and the farm wrappers in
    :mod:`repro.noc.farm`, which live in their own partitions —
    ``"farm[n]:backend"`` / ``"rowshard[n]:backend"`` — so a sharded
    sweep at a fixed device count compiles once and every later sweep
    at that count is a hit, never a silent per-device-count recompile):
    ``misses`` counts actual simulator builds (one jit compilation
    each), ``hits`` reuses, and ``evictions`` should stay 0 for any
    sane sweep — each partition holds :data:`SIM_CACHE_MAXSIZE`
    entries, so a 70-spec grid compiles each spec exactly once
    (tested)."""
    with _cache_lock:
        return {**_stats,
                "size": sum(len(c) for c in _caches.values()),
                "partitions": {b: len(c) for b, c in _caches.items()}}


def _cache_get(partition: str, key):
    """Look up a compiled function in one stats-instrumented LRU
    partition (``None`` = miss, already counted).  The partition string
    is free-form — ``compiled_sim`` uses the backend name, the farm
    wrappers embed their device count — so differently-sharded builds
    of one spec never collide *or* evict each other."""
    with _cache_lock:
        part = _caches.setdefault(partition, OrderedDict())
        if key in part:
            part.move_to_end(key)
            _stats["hits"] += 1
            return part[key]
        _stats["misses"] += 1
        return None


def _cache_put(partition: str, key, fn):
    """Insert a freshly-built compiled function; evicts LRU entries
    beyond :data:`SIM_CACHE_MAXSIZE` per partition.  Returns ``fn``."""
    with _cache_lock:
        part = _caches.setdefault(partition, OrderedDict())
        part[key] = fn
        part.move_to_end(key)
        while len(part) > SIM_CACHE_MAXSIZE:
            part.popitem(last=False)
            _stats["evictions"] += 1
    return fn


def sim_cache_clear() -> None:
    with _cache_lock:
        _caches.clear()
        _programs.clear()
        _stats.update(hits=0, misses=0, evictions=0)


# --------------------------------------------------------------------- #
# host spans: profiler annotations plus a process-wide table
# --------------------------------------------------------------------- #
_spans: dict[str, list] = {}            # name -> [count, seconds]
_span_lock = threading.Lock()
_call_ids = itertools.count(1)
_call: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "repro_noc_call", default=None)


@contextlib.contextmanager
def span(name: str):
    """Time the block as host span ``name``: a
    ``jax.profiler.TraceAnnotation`` on the profiler's clock, carrying
    ``call=<id>`` of the API call it belongs to, and one count plus its
    seconds in :func:`span_stats`."""
    call = _call.get()
    t = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(
                name, **({} if call is None else {"call": call})):
            yield
    finally:
        dt = time.perf_counter() - t
        with _span_lock:
            entry = _spans.setdefault(name, [0, 0.0])
            entry[0] += 1
            entry[1] += dt


@contextlib.contextmanager
def call_span(name: str):
    """The parent span of one API call (``noc.simulate``/``noc.sweep``)
    under a fresh per-process call id, which every span inside it
    carries.  A call made inside another (a sweep's one-point group
    runs through ``simulate``) stays part of the outer call: no second
    parent, no new id."""
    if _call.get() is not None:
        yield
        return
    token = _call.set(next(_call_ids))
    try:
        with span(name):
            yield
    finally:
        _call.reset(token)


def span_stats() -> dict:
    """Host time of the API's phases since process start (or
    :func:`span_stats_clear`): ``{name: {"count": n, "seconds": s}}``.
    Every ``simulate``/``sweep`` call is one ``noc.simulate`` /
    ``noc.sweep`` span holding ``noc.verify``, ``noc.schedules``,
    ``noc.build`` (only where a program had to be built: simulator,
    trace, lowering, compile or cache load), ``noc.dispatch`` (copies to
    the device and enqueue), ``noc.wait`` (until the device program
    ends) and ``noc.results`` (copies to the host, ``SimResult``)."""
    with _span_lock:
        return {k: {"count": c, "seconds": s}
                for k, (c, s) in _spans.items()}


def span_stats_clear() -> None:
    with _span_lock:
        _spans.clear()


# --------------------------------------------------------------------- #
# compiled programs: a simulator lowered for one argument layout
# --------------------------------------------------------------------- #
_programs: OrderedDict = OrderedDict()


def compiled_program(key, get, args, in_axes=None):
    """The program that runs ``args``: the cached jitted simulator
    ``get()`` returns (vmapped over ``in_axes`` when given), lowered and
    compiled for these argument shapes.  ``key`` names the simulator
    (the arguments of the ``get`` call); with the shapes it keys a cache
    of programs beside the simulator cache, so the vmapped form is
    traced once, not on every call.  Everything a call has to build —
    the simulator, the trace, lowering and compilation (or a persistent
    cache load) — runs under one ``noc.build`` span; a call that finds
    its program opens none, and ``get()`` counts the simulator cache's
    hit as before."""
    pkey = (key, in_axes,
            tuple((np.shape(a), np.result_type(a)) for a in args))
    with _cache_lock:
        prog = _programs.get(pkey)
        if prog is not None:
            _programs.move_to_end(pkey)
    if prog is not None:
        get()
        return prog
    with span("noc.build"):
        fn = get()
        prog = fn if in_axes is None \
            else jax.jit(jax.vmap(fn, in_axes=in_axes))
        prog.lower(*args).compile()
    with _cache_lock:
        _programs[pkey] = prog
        while len(_programs) > SIM_CACHE_MAXSIZE:
            _programs.popitem(last=False)
    return prog


def _depth_normalized(spec: NocSpec, max_depth: int | None):
    """(key spec, static max depth): the compiled simulator is depth-
    agnostic up to the static max, so the cache key replaces every
    channel depth with that max — specs differing only in FIFO depth
    share one compilation."""
    depths = tuple(ch.depth for ch in spec.channels)
    d_max = max(depths) if max_depth is None else int(max_depth)
    if d_max < max(depths):
        raise ValueError(
            f"max_depth={max_depth} below spec channel depths {depths}")
    key_spec = spec.with_(channels=tuple(
        replace(ch, depth=d_max) for ch in spec.channels))
    return key_spec, d_max


def compiled_sim(spec: NocSpec, T: int, backend: str = "jnp", *,
                 max_depth: int | None = None):
    """One jitted simulator per (depth-normalized spec, horizon,
    backend) triple, from a stats-instrumented per-backend cache.

    Returns ``fn(times, dests, writes, service_lat, max_out,
    burst_beats, jitter, depths)`` — plus, when the spec carries a
    :class:`~repro.noc.faults.FaultModel`, five extra traced operands
    ``(ev_fail, ev_heal, timeout_cycles, max_retries, backoff_base)``
    (the first two from :func:`repro.noc.faults.dynamic_events`, the
    rest per-class/scalar robustness knobs) and eight extra raw outputs
    (the degradation counters).  ``times``/``dests``/``writes``
    are (n_lanes, R, T) int32 schedules — one row per (class, AXI ID
    stream) lane, class-major, so with every class at ``n_streams=1``
    that is exactly the per-class (n_cls, R, T) layout
    (:func:`repro.noc.stack_schedules` builds them either way) and
    ``writes`` marks AXI write transactions.  The knobs stay
    per-CLASS — the ``service_lat`` vector, the (n_cls,
    JITTER_TABLE_LEN) service-jitter offset table,
    ``max_out``/``burst_beats`` — and are expanded to lanes inside the
    jit (each lane gets ``max_out[cls]//S`` credits, earlier streams
    take the remainder); with the per-channel FIFO ``depths`` vector
    all are traced, so the whole function is vmappable over a leading
    batch axis for rate/seed/latency/depth sweeps in a single jit.

    ``max_depth`` pads the FIFO state to a larger static bound than the
    spec declares, letting one compilation serve every depth up to that
    bound (the padded-depth sweep mode); results are flit-for-flit
    identical to a natively-sized build.  ``backend`` selects who runs
    the fabric hot loop (see :mod:`repro.noc.backends`); every backend
    must produce identical results behind this one surface.

    Off-CPU the big ``times``/``dests``/``writes`` operands are DONATED
    (the scan carry workspace aliases them): pass numpy arrays (always
    safe — a fresh device buffer is created per call, which is what
    every ``repro.noc`` caller does) or fresh device arrays; reusing a
    jnp array across calls on GPU/TPU raises "Array has been deleted".
    """
    key_spec, d_max = _depth_normalized(spec, max_depth)
    key = (key_spec, T)
    fn = _cache_get(backend, key)
    if fn is not None:
        return fn
    return _cache_put(backend, key, _build_sim(key_spec, T, backend, d_max))


def _build_sim(spec: NocSpec, T: int, backend: str, d_max: int):
    plan = build_flow_plan(spec)
    bk = get_backend(backend)
    faulted = spec.faults is not None
    # only pass faults= when present: custom two-arg backend factories
    # (and the healthy jaxpr) stay exactly as before
    network = bk(spec.topology, spec.routing, faults=spec.faults) \
        if faulted else bk(spec.topology, spec.routing)
    step = make_step(spec, plan, T, network.step)
    n_ch, R = plan.n_ch, spec.n_routers
    n_vcs = spec.routing.n_vcs

    # lane expansion of the per-CLASS traced knobs: static gather
    # indices (class of each lane) plus the credit split — lane s of a
    # class with S streams gets max_out//S credits, the first
    # max_out%S lanes one extra.  Single-stream specs skip the gather
    # entirely so their jaxpr (and goldens) are untouched.
    multi_stream = any(c.n_streams > 1 for c in spec.classes)
    cls_of = np.asarray(plan.cls_of_lane, np.int32)
    s_of = np.asarray(plan.stream_of_lane, np.int32)
    S_of = np.asarray([spec.classes[ci].n_streams
                       for ci in plan.cls_of_lane], np.int32)

    def to_lanes(service_lat, max_out, burst_beats, jitter):
        if not multi_stream:
            return service_lat, max_out, burst_beats, jitter
        mo_c = max_out[cls_of]
        mo = mo_c // S_of + (s_of < mo_c % S_of)
        return (service_lat[cls_of], mo, burst_beats[cls_of],
                jitter[cls_of])

    def _run(times, dests, writes, service_lat, max_out, burst_beats,
             jitter, depths, fault_ops):
        state = SimState(network.init(n_ch, d_max),
                         init_ni(R, plan, spec.resp_q_cap), jnp.int32(0),
                         jnp.zeros((n_ch,), jnp.int32), jnp.int32(0),
                         jnp.int32(0),
                         jnp.zeros((n_ch, n_vcs), jnp.int32),
                         jnp.zeros((n_ch, n_vcs), jnp.int32),
                         init_faults(R, plan.n_cls, fault_p_cap(plan))
                         if faulted else ())
        service_lat, max_out, burst_beats, jitter = to_lanes(
            service_lat, max_out, burst_beats, jitter)
        times = jnp.moveaxis(times, 0, 1)              # (R, n_lanes, T)
        dyn = {"times": times,
               "dests": jnp.moveaxis(dests, 0, 1),
               "writes": jnp.moveaxis(writes, 0, 1),
               "service_lat": service_lat, "max_out": max_out,
               "burst_beats": burst_beats, "jitter": jitter,
               "depths": jnp.asarray(depths, jnp.int32)}
        if faulted:
            ev_fail, ev_heal, tout, max_retries, backoff = fault_ops
            tout = jnp.asarray(tout, jnp.int32)        # (n_classes,)
            if multi_stream:
                tout = tout[cls_of]                    # expand to lanes
            dyn.update(ev_fail=jnp.asarray(ev_fail, jnp.int32),
                       ev_heal=jnp.asarray(ev_heal, jnp.int32),
                       timeout=tout,
                       max_retries=jnp.asarray(max_retries, jnp.int32),
                       backoff=jnp.asarray(backoff, jnp.int32))
        final, _ = jax.lax.scan(functools.partial(step, dyn), state, None,
                                length=spec.cycles)
        ni = final.ni
        n_sched = jnp.sum(times < BIG, axis=2)         # (R, n_cls)
        drained = (jnp.all(ni.ptr >= n_sched) & jnp.all(ni.out_r == 0)
                   & jnp.all(ni.out_w == 0))
        raw = {
            "done": ni.done, "lat_sum": ni.lat_sum, "lat_max": ni.lat_max,
            "beats_rx": ni.beats_rx, "first_t": ni.first_t,
            "last_t": ni.last_t,
            "w_done": ni.w_done, "w_lat_sum": ni.w_lat_sum,
            "w_lat_max": ni.w_lat_max, "w_beats_rx": ni.w_beats_rx,
            "w_first_t": ni.w_first_t, "w_last_t": ni.w_last_t,
            "link_moves": final.moves,
            "max_stall_cycles": final.max_stall, "drained": drained,
            "vc_occ_sum": final.vc_occ_sum,
            "vc_occ_max": final.vc_occ_max,
        }
        if faulted:
            fst = final.fs
            raw.update({
                "retries": fst.retries, "timeouts": fst.timeouts,
                "slverr": fst.slverr,
                "delivered_despite_fault": fst.dlv_fault,
                "beats_under_fault": fst.beats_fault,
                "faulted_link_cycles": fst.flc,
                "fault_cycles": fst.fcyc,
                "undone": (jnp.maximum(n_sched - ni.ptr, 0)
                           + ni.out_r + ni.out_w),
            })
        return raw

    if faulted:
        @jax.jit
        def run(times, dests, writes, service_lat, max_out, burst_beats,
                jitter, depths, ev_fail, ev_heal, timeout_cycles,
                max_retries, backoff_base):
            return _run(times, dests, writes, service_lat, max_out,
                        burst_beats, jitter, depths,
                        (ev_fail, ev_heal, timeout_cycles, max_retries,
                         backoff_base))
    else:
        @jax.jit
        def run(times, dests, writes, service_lat, max_out, burst_beats,
                jitter, depths):
            return _run(times, dests, writes, service_lat, max_out,
                        burst_beats, jitter, depths, None)

    return run
