"""Device-parallel simulation farm: shard sweeps and giant meshes.

Two tiers, both behind the unchanged ``simulate``/``sweep`` surface
(:mod:`repro.noc.api`), both plain ``jax.shard_map`` over the local
device mesh (CPU hosts get devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``):

**Tier (a) — spec-grid sharding** (``sweep(points, devices=N)``).
A sweep group is already one vmapped jit over stacked per-point
operands; the farm wraps that same vmapped simulator in a ``shard_map``
whose ``specs`` axis splits the batch across devices.  The frozen
:class:`~repro.noc.spec.NocSpec` partitions into

* the **static half** (:func:`partition_spec` -> a depth-normalized,
  hashable spec) which keys the compilation and is *closed over* —
  it never crosses the shard_map boundary, exactly like the engine's
  static/traced split, and
* the **dynamic half** (schedules, per-channel FIFO depths, per-class
  knob vectors, the jitter table) which rides through as traced
  operands, the schedules and depths sharded on ``specs`` and the
  group-constant knobs replicated.

Uneven grids are padded by repeating the last point (the pad lanes are
sliced off the gathered result before it becomes a
:class:`~repro.noc.result.SimResult`), so every group size works on
every device count.  Per-point results are bit-identical to the
single-device vmapped sweep: the per-point program is unchanged integer
arithmetic — sharding only changes *where* each lane runs.

**Tier (b) — spatial row-sharding** (``simulate(spec, wl,
shard=RowShard(n))``).  One big fabric's router rows split into ``n``
contiguous strips of ``ny / n`` mesh rows; each device advances its
strip's routers + NIs locally and the only cross-shard traffic is the
per-cycle **halo exchange** of boundary-row link state
(:func:`repro.dist.backend.halo_permute` neighbor ``ppermute``):

* downstream input-FIFO occupancy of the facing boundary rows (the
  drain decision's backpressure input), exchanged *before* phase A,
* the boundary rows' drain decisions + output registers (the neighbor
  push's payload), exchanged *after* phase A,

because those two gathers are the complete cross-row coupling of the
synchronous fabric step — everything else in
:func:`~repro.core.noc_sim.router.make_fabric_step` is row-local.
Local tables come from one ``lax.dynamic_slice`` of the global route
tables at ``axis_index * local_R``; neighbor/feeder row ids remap into
the ``[north halo | local | south halo]`` extended index space with a
single mod-``R_g`` affine (torus wrap falls out of the modulus; mesh
edges read ``ppermute``'s zero fill, which the ``nbr >= 0`` masks
already ignore).  Liveness and occupancy scalars are ``lax.psum``-ed
per cycle (see :class:`~repro.noc.engine.ShardInfo`), so the sharded
run is **flit-for-flit identical** to the single-device engine — the
equivalence tests compare entire ``SimResult`` trees.

Compiled farm simulators live in their own partitions of the engine's
stats-instrumented cache (``"farm[N]:backend"`` / ``"rowshard[N]"``),
so repeated sharded sweeps at a fixed device count never silently
recompile (``bench_sweep_scaling`` asserts the miss count).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh as _DeviceMesh, PartitionSpec as P

from repro.core.noc_sim.router import (FabricTables, NetState,
                                       fabric_front, fabric_update,
                                       feeder_tables)
from repro.dist.backend import halo_permute
from .api import (_check_dead_traffic, _depths, _dyn_scalars, _fault_ops,
                  _strip_depths, jitter_table, stack_schedules)
from .backends import _resolve_tables, _stacked_init
from .engine import (BIG, ShardInfo, SimState, _cache_get, _cache_put,
                     _depth_normalized, build_flow_plan, compiled_program,
                     compiled_sim, init_ni, make_step, span)
from .result import SimResult
from .spec import NocSpec
from .topology import Mesh, Torus

__all__ = ["RowShard", "partition_spec", "merge_spec", "farm_batch",
           "compiled_farm_sweep", "compiled_rowshard_sim", "fold_shards"]

ROW_AXIS = "rows"          # tier (b) shard_map axis name
SPEC_AXIS = "specs"        # tier (a) shard_map axis name


# --------------------------------------------------------------------- #
# static / dynamic NocSpec partition (tier a)
# --------------------------------------------------------------------- #
def partition_spec(spec: NocSpec) -> tuple[NocSpec, dict[str, np.ndarray]]:
    """Split a frozen spec into the **static half** (a hashable
    depth-normalized spec that keys the compilation and is closed over
    by the shard_mapped simulator) and the **dynamic half** (the traced
    knob arrays that cross the shard_map boundary as operands: per-
    channel FIFO ``depths``, the per-class ``service_lat`` /
    ``max_outstanding`` / ``burst_beats`` vectors, and the seeded
    ``jitter`` table).

    The static half still *declares* ``max_outstanding`` etc. — those
    values size state arrays (W rings, ROB-bounded pending tables)
    statically — but the values the engine compares against at runtime
    are the dynamic vectors, which is why a whole sweep group shares
    one compilation.  :func:`merge_spec` is the exact inverse:
    ``merge_spec(*partition_spec(s)) == s`` for every spec (tested by
    hypothesis round-trip)."""
    static = _strip_depths(spec)
    sl, mo, bb = _dyn_scalars(spec, None, None, None)
    dyn = {
        "depths": _depths(spec),
        "service_lat": sl,
        "max_outstanding": mo,
        "burst_beats": bb,
        "jitter": jitter_table(spec),
    }
    return static, dyn


def merge_spec(static: NocSpec, dyn: Mapping[str, np.ndarray]) -> NocSpec:
    """Reassemble the original spec from a :func:`partition_spec` pair
    (the depth vector is the only spec field the static half
    normalizes away; every other dynamic entry shadows a value the
    static spec still declares)."""
    depths = np.asarray(dyn["depths"], np.int64)
    if depths.shape != (len(static.channels),):
        raise ValueError(
            f"depths shape {depths.shape} != ({len(static.channels)},)")
    return static.with_(channels=tuple(
        replace(ch, depth=int(d))
        for ch, d in zip(static.channels, depths)))


# --------------------------------------------------------------------- #
# device mesh plumbing
# --------------------------------------------------------------------- #
def _device_mesh(n: int, axis: str) -> _DeviceMesh:
    avail = jax.devices()
    if n < 1:
        raise ValueError(f"need at least 1 device, got {n}")
    if n > len(avail):
        raise ValueError(
            f"requested {n} devices but only {len(avail)} are visible; "
            f"on a CPU-only host launch with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"(must be set before jax is imported)")
    return _DeviceMesh(np.array(avail[:n]), (axis,))


# --------------------------------------------------------------------- #
# tier (a): spec-grid sharding
# --------------------------------------------------------------------- #
def compiled_farm_sweep(spec: NocSpec, T: int, devices: int,
                        backend: str = "jnp", *,
                        max_depth: int | None = None):
    """The shard_mapped analogue of ``vmap(compiled_sim(...))``: same
    operand signature with a leading batch axis on schedules + depths
    (batch size divisible by ``devices``), batch split across the
    ``specs`` device axis.  Cached in partition ``"farm[N]:backend"``
    keyed by the depth-normalized spec — a repeat sweep at the same
    device count is a cache hit, never a recompile."""
    key_spec, _ = _depth_normalized(spec, max_depth)
    partition = f"farm[{devices}]:{backend}"
    key = (key_spec, T)
    fn = _cache_get(partition, key)
    if fn is not None:
        return fn
    inner = compiled_sim(spec, T, backend, max_depth=max_depth)
    n_fops = 5 if spec.faults is not None else 0
    mesh = _device_mesh(devices, SPEC_AXIS)
    vmapped = jax.vmap(inner, in_axes=(0, 0, 0, None, None, None, None, 0,
                                       *((None,) * n_fops)))
    in_specs = ((P(SPEC_AXIS),) * 3 + (P(),) * 4 + (P(SPEC_AXIS),)
                + (P(),) * n_fops)
    fn = jax.jit(jax.shard_map(vmapped, mesh=mesh, in_specs=in_specs,
                               out_specs=P(SPEC_AXIS), check_vma=False))
    return _cache_put(partition, key, fn)


def farm_batch(specs: Sequence[NocSpec], wls, devices: int,
               backend: str = "jnp") -> SimResult:
    """Run one sweep group (specs sharing a static half, possibly
    differing in FIFO depths) sharded across ``devices`` — the farm
    counterpart of :func:`repro.noc.api._batch_depth_sweep`.  Pads the
    group up to a device multiple by repeating the last point and
    slices the pad off the gathered raw, so results keep the exact
    batched shape of the single-device path."""
    from .api import run_program
    base = specs[0]
    with span("noc.schedules"):
        per_point = [wl.schedules(s) for s, wl in zip(specs, wls)]
        T = max(max(np.asarray(t).reshape(base.n_routers, -1).shape[1]
                    for t, *_ in sched.values()) for sched in per_point)
        stacked = [stack_schedules(s, sched, T=T)
                   for s, sched in zip(specs, per_point)]
        times = np.stack([t for t, _, _ in stacked])   # (n, n_lanes, R, T)
        dests = np.stack([d for _, d, _ in stacked])
        writes = np.stack([w for _, _, w in stacked])
        sl, mo, bb = _dyn_scalars(base, None, None, None)
        jt = jitter_table(base)
        fops = _fault_ops(base)
        for i in range(len(specs)):
            _check_dead_traffic(base, times[i], dests[i])
        depths = np.stack([_depths(s) for s in specs])  # (n, n_ch)

        n = len(specs)
        n_pad = -(-n // devices) * devices
        if n_pad != n:
            reps = n_pad - n
            pad = functools.partial(np.concatenate, axis=0)
            times = pad([times, np.repeat(times[-1:], reps, axis=0)])
            dests = pad([dests, np.repeat(dests[-1:], reps, axis=0)])
            writes = pad([writes, np.repeat(writes[-1:], reps, axis=0)])
            depths = pad([depths, np.repeat(depths[-1:], reps, axis=0)])
        args = (times, dests, writes, sl, mo, bb, jt, depths, *fops)

    d_max = int(depths.max())
    prog = compiled_program(
        ("farm", _depth_normalized(base, d_max)[0], T, devices, backend),
        lambda: compiled_farm_sweep(base, T, devices, backend,
                                    max_depth=d_max), args)
    return run_program(base, prog, args, fold=lambda raw: {
        k: np.asarray(v)[:n] for k, v in raw.items()})


# --------------------------------------------------------------------- #
# tier (b): spatial row-sharding with halo exchange
# --------------------------------------------------------------------- #
@dataclass(frozen=True)
class RowShard:
    """Split the fabric's router rows (``topology.ny`` mesh rows) into
    ``n`` contiguous strips, one device each.  Pass as
    ``simulate(spec, wl, shard=RowShard(n))``; requires a plain
    ``Mesh``/``Torus`` (no express links — their stride links would
    couple non-adjacent shards), ``ny % n == 0``, the ``jnp`` backend
    and a fault-free spec."""
    n: int

    def __post_init__(self):
        if not isinstance(self.n, int) or isinstance(self.n, bool) \
                or self.n < 1:
            raise ValueError(f"RowShard.n must be a positive int, "
                             f"got {self.n!r}")


def _check_rowshard(spec: NocSpec, shard: RowShard, backend: str) -> None:
    if backend != "jnp":
        raise ValueError(
            f"row-sharded simulation runs on the 'jnp' backend only "
            f"(got {backend!r}); the fused kernel path is single-device")
    topo = spec.topology
    if not isinstance(topo, Mesh) or getattr(topo, "express", ()):
        raise ValueError(
            "RowShard needs a plain Mesh/Torus topology (express links "
            "couple non-adjacent row strips)")
    if spec.faults is not None:
        raise NotImplementedError(
            "row-sharded simulation does not support FaultModel specs")
    if topo.ny % shard.n:
        raise ValueError(
            f"RowShard({shard.n}) needs ny divisible by the shard "
            f"count; got ny={topo.ny}")


def compiled_rowshard_sim(spec: NocSpec, T: int, shard: RowShard,
                          backend: str = "jnp"):
    """One jitted row-sharded simulator per (depth-normalized spec,
    horizon, shard count), cached in partition ``"rowshard[N]:jnp"``.
    Same operand signature and raw-result keys as
    :func:`~repro.noc.engine.compiled_sim` (fault-free form); the
    per-shard leaves it returns take :func:`fold_shards` on the host to
    the single-device raw shape."""
    _check_rowshard(spec, shard, backend)
    key_spec, d_max = _depth_normalized(spec, None)
    partition = f"rowshard[{shard.n}]:{backend}"
    key = (key_spec, T)
    fn = _cache_get(partition, key)
    if fn is not None:
        return fn
    return _cache_put(partition, key,
                      _build_rowshard_sim(key_spec, T, shard.n, d_max))


def _build_rowshard_sim(spec: NocSpec, T: int, n_shards: int, d_max: int):
    """Build the shard_mapped simulator: each shard advances ``R_l =
    R_g / n`` contiguous router rows with a locally-sliced copy of the
    global tables, exchanging boundary-row state via
    :func:`~repro.dist.backend.halo_permute` twice per cycle."""
    plan = build_flow_plan(spec)
    nbr, opp, route, n_vcs = _resolve_tables(spec.topology, spec.routing)
    src_r, src_o = feeder_tables(nbr, opp)
    R_g, Pn = nbr.shape
    nx = spec.topology.nx
    R_l = R_g // n_shards
    wrap = isinstance(spec.topology, Torus)
    n_ch = plan.n_ch
    n_vcs_pol = spec.routing.n_vcs
    sh = ShardInfo(ROW_AXIS, n_shards, R_l, R_g)
    mesh = _device_mesh(n_shards, ROW_AXIS)
    # extended row index space per shard: [north halo (nx rows) |
    # local (R_l rows) | south halo (nx rows)]

    # global tables as replicated jnp constants; each shard slices its
    # own R_l-row window at trace time (hoisted out of the cycle scan)
    nbr_g = jnp.asarray(nbr, jnp.int32)
    opp_g = jnp.asarray(opp, jnp.int32)
    route_g = jnp.asarray(route, jnp.int32)
    srcr_g = jnp.asarray(src_r, jnp.int32)
    srco_g = jnp.asarray(src_o, jnp.int32)

    def _local_tables():
        base = lax.axis_index(ROW_AXIS) * R_l

        def sl(a):
            return lax.dynamic_slice_in_dim(a, base, R_l, axis=0)

        nbr_l, opp_l, route_l = sl(nbr_g), sl(opp_g), sl(route_g)
        srcr_l, srco_l = sl(srcr_g), sl(srco_g)

        # every neighbor/feeder of a local row lies within one boundary
        # strip, so its extended index is one affine: north halo rows
        # land in [0, nx), local in [nx, nx + R_l), south in
        # [nx + R_l, R_l + 2 nx).  Torus wrap links need the mod (with n=1 a
        # wrapped neighbor then resolves into the identity self-halo);
        # a mesh has no wrap links, and must NOT mod — with n=1 the
        # affine of a local bottom-strip row exceeds R_g and the mod
        # would alias it into the zero-filled north halo
        def ext(g):
            off = g - base + nx
            return off % R_g if wrap else off

        has_feed = srcr_l >= 0
        return FabricTables(
            nbr=jnp.where(nbr_l >= 0, ext(nbr_l), -1), opp=opp_l,
            route=route_l, has_feed=has_feed,
            src_flat=jnp.where(has_feed, ext(srcr_l) * Pn + srco_l, 0))

    @jax.named_scope("farm.halo")
    def _with_halo(x):
        """(R_l, ...) local rows -> (R_ext, ...) with both boundary
        strips exchanged (mesh edges receive ppermute's zero fill,
        masked off by the nbr/feeder >= 0 guards)."""
        north = halo_permute(x[-nx:], ROW_AXIS, n_shards, shift=1,
                             wrap=wrap)
        south = halo_permute(x[:nx], ROW_AXIS, n_shards, shift=-1,
                             wrap=wrap)
        return jnp.concatenate([north, x, south], axis=0)

    def _make_net_step(tables: FabricTables):
        """The row-local analogue of
        :func:`~repro.core.noc_sim.router.make_fabric_step`: the same
        two phases, with the front's cross-row gathers (downstream
        occupancy, neighbor push) reading the halo-extended arrays."""
        def one(state: NetState, inject_valid, inject_flit, depth):
            front = fabric_front(state, inject_valid, inject_flit, depth,
                                 tables, n_vcs=n_vcs, ext=_with_halo)
            return (fabric_update(state, front, depth), front.inj_ok,
                    front.deliver_valid, front.deliver_flit,
                    front.link_moves)

        return jax.vmap(one, in_axes=(0, 0, 0, 0))

    # per-CLASS -> per-lane knob expansion, mirrored from _build_sim
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

    def sharded(times, dests, writes, service_lat, max_out, burst_beats,
                jitter, depths):
        # local shapes: times/dests/writes (n_lanes, R_l, T)
        net_step = _make_net_step(_local_tables())
        step = make_step(spec, plan, T, net_step, shard=sh)
        state = SimState(_stacked_init(R_l, Pn)(n_ch, d_max),
                         init_ni(R_l, plan, spec.resp_q_cap),
                         jnp.int32(0), jnp.zeros((n_ch,), jnp.int32),
                         jnp.int32(0), jnp.int32(0),
                         jnp.zeros((n_ch, n_vcs_pol), jnp.int32),
                         jnp.zeros((n_ch, n_vcs_pol), jnp.int32), ())
        service_lat, max_out, burst_beats, jitter = to_lanes(
            service_lat, max_out, burst_beats, jitter)
        times_l = jnp.moveaxis(times, 0, 1)            # (R_l, n_lanes, T)
        dyn = {"times": times_l,
               "dests": jnp.moveaxis(dests, 0, 1),
               "writes": jnp.moveaxis(writes, 0, 1),
               "service_lat": service_lat, "max_out": max_out,
               "burst_beats": burst_beats, "jitter": jitter,
               "depths": jnp.asarray(depths, jnp.int32)}
        final, _ = lax.scan(functools.partial(step, dyn), state, None,
                            length=spec.cycles)
        ni = final.ni
        n_sched = jnp.sum(times_l < BIG, axis=2)
        drained = (jnp.all(ni.ptr >= n_sched) & jnp.all(ni.out_r == 0)
                   & jnp.all(ni.out_w == 0))
        # every leaf leaves with a leading gather axis: per-row arrays
        # concatenate back into global row order (shards are contiguous
        # strips); per-shard leaves stack to (n_shards, ...) and are
        # reduced host-side by fold_shards()
        return {
            "done": ni.done, "lat_sum": ni.lat_sum,
            "lat_max": ni.lat_max, "beats_rx": ni.beats_rx,
            "first_t": ni.first_t, "last_t": ni.last_t,
            "w_done": ni.w_done, "w_lat_sum": ni.w_lat_sum,
            "w_lat_max": ni.w_lat_max, "w_beats_rx": ni.w_beats_rx,
            "w_first_t": ni.w_first_t, "w_last_t": ni.w_last_t,
            "link_moves": final.moves[None],            # local partials
            "max_stall_cycles": final.max_stall[None],  # psum-replicated
            "drained": drained[None],                   # local verdicts
            "vc_occ_sum": final.vc_occ_sum[None],       # psum-replicated
            "vc_occ_max": final.vc_occ_max[None],
        }

    in_specs = ((P(None, ROW_AXIS),) * 3 + (P(),) * 5)
    return jax.jit(jax.shard_map(sharded, mesh=mesh, in_specs=in_specs,
                                 out_specs=P(ROW_AXIS), check_vma=False))


def fold_shards(raw) -> dict:
    """A row-sharded simulator's raw result on the host, its per-shard
    leaves folded back to the single-device raw shape."""
    raw = {k: np.asarray(v) for k, v in raw.items()}
    raw["link_moves"] = raw["link_moves"].sum(axis=0, dtype=np.int32)
    raw["max_stall_cycles"] = raw["max_stall_cycles"][0]
    raw["drained"] = np.bool_(raw["drained"].all())
    raw["vc_occ_sum"] = raw["vc_occ_sum"][0]
    raw["vc_occ_max"] = raw["vc_occ_max"][0]
    return raw
