"""`simulate(spec, workload)` — the one entry point for NoC experiments.

The static half of an experiment (mesh dims, channel topology, FIFO
depths, cycle horizon, AXI flow map) lives in the frozen
:class:`NocSpec` and keys a cached jitted simulator; the dynamic half
(schedules + read/write mix, per-class service latency and jitter,
outstanding limits, burst lengths) are traced operands.  That split is
what makes sweeps cheap:

* :func:`simulate`        — one spec + one workload -> one SimResult,
* :func:`simulate_batch`  — one spec + N workloads (and optionally
  per-point scalar overrides) -> ONE vmapped jit call returning a
  batched SimResult, bit-identical to N individual runs,
* :func:`sweep`           — arbitrary (spec, workload) points; points
  sharing a static spec are grouped into vmapped batches, and (with
  ``pad_depths``, the default) points whose specs differ ONLY in
  channel FIFO depths are grouped too: depth is a traced operand
  masked against the group max, so a whole depth sweep shares one
  compilation (``sim_cache_stats()`` counts it).  Points that differ
  in any other static field (e.g. channel count) compile per group.

Per-class service-latency *distributions*: ``service_lat`` accepts one
int (every class) or a per-class vector of means; ``service_jitter``
adds a per-request uniform offset in ``[-j, +j]`` drawn from a seeded
static table (``jitter_seed``), so the target NIs answer after
``mean + offset`` cycles.  Both are traced operands — a latency-
distribution sweep vmaps like a rate sweep — and ``jitter=0``
reproduces the deterministic model bit-for-bit.
"""
from __future__ import annotations

from dataclasses import replace
from typing import Mapping, Sequence

import jax
import numpy as np

from .engine import (BIG, JITTER_TABLE_LEN, _depth_normalized, call_span,
                     compiled_program, compiled_sim, span)
from .result import SimResult
from .spec import NocSpec
from .workload import Workload

__all__ = ["simulate", "simulate_batch", "simulate_schedules", "sweep",
           "stack_schedules"]


def _verify(spec: NocSpec, verify: str) -> None:
    """Static-analysis gate (lazy import: repro.noc.analyze depends on
    this package's spec/engine modules)."""
    if verify == "off":
        return
    from .analyze import verify_spec
    with span("noc.verify"):
        verify_spec(spec, verify)


def run_program(spec: NocSpec, prog, args, fold=None) -> SimResult:
    """One call of a compiled program, each step under its span:
    ``noc.dispatch`` (copies to the device, enqueue), ``noc.wait``
    (until the device program ends) and ``noc.results`` (``fold`` of
    the raw leaves, if given, copies to the host and the
    ``SimResult``)."""
    with span("noc.dispatch"):
        raw = prog(*args)
    with span("noc.wait"):
        raw = jax.block_until_ready(raw)
    with span("noc.results"):
        return SimResult.from_raw(spec, raw if fold is None else fold(raw))


def _split_streams(cls_name, t, d, w, s, S):
    """Partition one class's per-NI schedule rows into ``S`` per-stream
    lanes, preserving each NI's entry order within a stream.  Rows are
    compacted with a stable argsort (stream-s entries first, original
    order kept) and re-padded with BIG sentinels."""
    valid = t < BIG
    if s is None:
        # 3-tuple schedule on a multi-stream class: deal entries
        # round-robin across the AXI ID streams per NI
        s = np.where(valid, (np.cumsum(valid, axis=1) - 1) % S, 0)
    else:
        bad = valid & ((s < 0) | (s >= S))
        if np.any(bad):
            raise ValueError(
                f"class {cls_name!r}: stream ids must be in [0, "
                f"n_streams={S}); got {np.unique(s[bad])}")
    lanes = []
    for si in range(S):
        mask = valid & (s == si)
        order = np.argsort(~mask, axis=1, kind="stable")
        mm = np.take_along_axis(mask, order, axis=1)
        width = max(1, int(mask.sum(axis=1).max()))
        tt = np.where(mm, np.take_along_axis(t, order, axis=1), BIG)
        dd = np.where(mm, np.take_along_axis(d, order, axis=1), 0)
        ww = np.where(mm, np.take_along_axis(w, order, axis=1), 0)
        lanes.append((tt[:, :width], dd[:, :width], ww[:, :width]))
    return lanes


def stack_schedules(spec: NocSpec,
                    schedules: Mapping[str, tuple],
                    T: int | None = None) -> tuple[np.ndarray, np.ndarray,
                                                   np.ndarray]:
    """Pad per-class ``(times, dests[, writes[, streams]])`` schedules
    to a common horizon and stack into the (n_lanes, R, T) operands the
    engine consumes — one lane per (class, AXI ID stream), class-major.
    A 2-tuple entry (a custom schedule source predating the write flag)
    is treated as all-reads; a 3-tuple on a class with ``n_streams >
    1`` is dealt round-robin across its streams; a 4-tuple's ``streams``
    array assigns each entry's AXI ID stream explicitly.  Classes at
    the default ``n_streams=1`` pass through without repacking, so
    single-stream operands are bit-identical to the pre-stream layout
    (n_lanes == n_cls)."""
    R = spec.n_routers
    per_lane = []
    for cls in spec.classes:
        entry = schedules[cls.name]
        t, d = entry[0], entry[1]
        t = np.asarray(t, np.int32).reshape(R, -1)
        d = np.asarray(d, np.int32).reshape(R, -1)
        w = (np.asarray(entry[2], np.int32).reshape(R, -1)
             if len(entry) > 2 and entry[2] is not None
             else np.zeros_like(t))
        s = (np.asarray(entry[3], np.int32).reshape(R, -1)
             if len(entry) > 3 and entry[3] is not None else None)
        for name, a in (("writes", w), ("streams", s)):
            if a is not None and a.shape != t.shape:
                raise ValueError(
                    f"class {cls.name!r}: {name} shape {a.shape} != "
                    f"times shape {t.shape}")
        if cls.n_streams == 1:
            # stream ids collapse onto the single AXI ID (so one
            # 4-tuple schedule compares n_streams settings directly)
            per_lane.append((t, d, w))
        else:
            per_lane.extend(_split_streams(cls.name, t, d, w, s,
                                           cls.n_streams))
    T_need = max(t.shape[1] for t, _, _ in per_lane)
    T = T_need if T is None else max(T, T_need)
    times = np.full((len(per_lane), R, T), BIG, np.int32)
    dests = np.zeros((len(per_lane), R, T), np.int32)
    writes = np.zeros((len(per_lane), R, T), np.int32)
    for i, (t, d, w) in enumerate(per_lane):
        times[i, :, :t.shape[1]] = t
        dests[i, :, :d.shape[1]] = d
        writes[i, :, :w.shape[1]] = w
    return times, dests, writes


def _per_class_vec(spec: NocSpec, v, default, name) -> np.ndarray:
    """Normalize a scalar-or-per-class knob to an (n_cls,) int32 vector."""
    n_cls = len(spec.classes)
    if v is None:
        v = np.asarray(default, np.int32)
    v = np.asarray(v, np.int32)
    if v.ndim == 0:
        return np.full((n_cls,), int(v), np.int32)
    if v.shape != (n_cls,):
        raise ValueError(
            f"{name} must be a scalar or length-{n_cls} per-class "
            f"vector; got shape {v.shape}")
    return v


def _dyn_scalars(spec: NocSpec, service_lat, max_outstanding, burst_beats):
    sl = _per_class_vec(
        spec, service_lat,
        [spec.service_lat if c.service_lat is None else c.service_lat
         for c in spec.classes], "service_lat")
    mo = _per_class_vec(spec, max_outstanding,
                        [c.max_outstanding for c in spec.classes],
                        "max_outstanding")
    bb = _per_class_vec(spec, burst_beats,
                        [c.burst_beats for c in spec.classes],
                        "burst_beats")
    return sl, mo, bb


def jitter_table(spec: NocSpec, service_jitter=None, *, seed: int = 0,
                 service_lat=None) -> np.ndarray:
    """Seeded static per-class jitter offsets, shape
    ``(n_cls, JITTER_TABLE_LEN)``: row ``i`` holds uniform draws from
    ``[-j_i, +j_i]`` (clipped so ``mean + offset >= 0``), indexed by
    (issuing NI, transaction id) inside the engine so the draws
    decorrelate across sources.  ``service_jitter=0`` rows are exactly
    zero — the deterministic model.  The table is a traced operand:
    sweeping jitter re-runs, never re-compiles."""
    jit = _per_class_vec(spec, service_jitter,
                         [c.service_jitter for c in spec.classes],
                         "service_jitter")
    if np.any(jit < 0):
        raise ValueError(f"service_jitter must be >= 0, got {jit}")
    sl, _, _ = _dyn_scalars(spec, service_lat, None, None)
    rng = np.random.default_rng(np.uint32(0xF100) + np.uint32(seed))
    tab = rng.integers(-jit[:, None], jit[:, None] + 1,
                       size=(len(spec.classes), JITTER_TABLE_LEN))
    return np.maximum(tab, -sl[:, None]).astype(np.int32)


def _depths(spec: NocSpec) -> np.ndarray:
    return np.asarray([ch.depth for ch in spec.channels], np.int32)


def _fault_ops(spec: NocSpec, timeout_cycles=None, max_retries=None,
               backoff_base=None) -> tuple:
    """The five extra traced operands of a faulted simulator (empty
    tuple when ``spec.faults is None`` — the healthy signature).  The
    keyword overrides shadow the FaultModel's declared robustness knobs
    without recompiling, exactly like ``service_lat`` etc."""
    if spec.faults is None:
        for name, v in (("timeout_cycles", timeout_cycles),
                        ("max_retries", max_retries),
                        ("backoff_base", backoff_base)):
            if v is not None:
                raise ValueError(
                    f"{name} override requires spec.faults (a FaultModel)")
        return ()
    from .faults import dynamic_events
    fm = spec.faults
    ev_fail, ev_heal, _ = dynamic_events(spec.topology, spec.routing, fm,
                                         spec.cycles)
    tmo = _per_class_vec(spec, timeout_cycles, fm.timeout_cycles,
                         "timeout_cycles")
    mr = np.int32(fm.max_retries if max_retries is None else max_retries)
    bo = np.int32(fm.backoff_base if backoff_base is None
                  else backoff_base)
    if bo < 1:
        raise ValueError(f"backoff_base must be >= 1, got {int(bo)}")
    return (ev_fail, ev_heal, tmo, mr, bo)


def _check_dead_traffic(spec: NocSpec, times: np.ndarray,
                        dests: np.ndarray) -> None:
    """Traffic sourced at or destined to a statically dead node is a
    workload/fault contradiction — reject it up front instead of
    reporting an undrained run."""
    fm = spec.faults
    if fm is None or not fm.dead_nodes:
        return
    dead = np.asarray(sorted(set(fm.dead_nodes)), np.int32)
    valid = times < BIG
    if valid[:, dead, :].any():
        bad = dead[valid[:, dead, :].any(axis=(0, 2))]
        raise ValueError(
            f"schedule sources traffic at dead node(s) {bad.tolist()}")
    to_dead = valid & np.isin(dests, dead)
    if to_dead.any():
        bad = sorted(set(dests[to_dead].tolist()))
        raise ValueError(
            f"schedule targets dead node(s) {bad}")


def simulate_schedules(spec: NocSpec,
                       schedules: Mapping[str, tuple],
                       *, service_lat=None,
                       max_outstanding: Sequence[int] | None = None,
                       burst_beats: Sequence[int] | None = None,
                       service_jitter=None, jitter_seed: int = 0,
                       timeout_cycles=None, max_retries=None,
                       backoff_base=None,
                       backend: str = "jnp",
                       verify: str = "fast",
                       shard=None) -> SimResult:
    """Run one experiment from raw per-class ``(times, dests[, writes])``
    schedules (the layer custom schedule sources go through).

    ``verify`` gates the static-analysis pass from
    :mod:`repro.noc.analyze` before any cycle is simulated: ``"fast"``
    (default) re-runs the cheap protocol/credit checks NocSpec
    construction already enforces, ``"full"`` adds the
    channel-dependency deadlock proof and route-table lint (lru-cached
    per (topology, routing) — e.g. a VC-less torus spec is rejected
    with the offending (link, VC) cycle instead of wedging), ``"off"``
    skips verification (how the wedge regressions simulate the
    documented-deadlocky configs on purpose).

    On a spec with a :class:`~repro.noc.faults.FaultModel`,
    ``timeout_cycles``/``max_retries``/``backoff_base`` shadow the
    model's declared NI robustness knobs (traced — no recompile) and
    the result carries :class:`~repro.noc.result.FaultStats`.

    ``shard=RowShard(n)`` (:mod:`repro.noc.farm`) spatially shards the
    fabric's router rows across ``n`` local devices with a per-cycle
    halo exchange of boundary-link state — flit-for-flit identical to
    the single-device engine; requires a plain Mesh/Torus, the
    ``jnp`` backend and a fault-free spec."""
    return _simulate(spec, lambda: schedules, service_lat=service_lat,
                     max_outstanding=max_outstanding,
                     burst_beats=burst_beats, service_jitter=service_jitter,
                     jitter_seed=jitter_seed, timeout_cycles=timeout_cycles,
                     max_retries=max_retries, backoff_base=backoff_base,
                     backend=backend, verify=verify, shard=shard)


def _simulate(spec: NocSpec, make_schedules, *, service_lat,
              max_outstanding, burst_beats, service_jitter, jitter_seed,
              timeout_cycles, max_retries, backoff_base, backend, verify,
              shard) -> SimResult:
    """One experiment as one ``noc.simulate`` call span; the schedules
    are made (``make_schedules()``) inside its ``noc.schedules``."""
    with call_span("noc.simulate"):
        _verify(spec, verify)
        with span("noc.schedules"):
            times, dests, writes = stack_schedules(spec, make_schedules())
            _check_dead_traffic(spec, times, dests)
            sl, mo, bb = _dyn_scalars(spec, service_lat, max_outstanding,
                                      burst_beats)
            jt = jitter_table(spec, service_jitter, seed=jitter_seed,
                              service_lat=service_lat)
            fops = _fault_ops(spec, timeout_cycles, max_retries,
                              backoff_base)
            args = (times, dests, writes, sl, mo, bb, jt, _depths(spec),
                    *fops)
        T = times.shape[-1]
        key_spec, _ = _depth_normalized(spec, None)
        if shard is None:
            prog = compiled_program(
                ("sim", key_spec, T, backend),
                lambda: compiled_sim(spec, T, backend), args)
            return run_program(spec, prog, args)
        from .farm import compiled_rowshard_sim, fold_shards
        prog = compiled_program(
            ("rowshard", key_spec, T, shard, backend),
            lambda: compiled_rowshard_sim(spec, T, shard, backend=backend),
            args)
        return run_program(spec, prog, args, fold=fold_shards)


def simulate(spec: NocSpec, workload: Workload, *,
             service_lat=None,
             max_outstanding: Sequence[int] | None = None,
             burst_beats: Sequence[int] | None = None,
             service_jitter=None, jitter_seed: int = 0,
             timeout_cycles=None, max_retries=None, backoff_base=None,
             backend: str = "jnp", verify: str = "fast",
             shard=None) -> SimResult:
    """Run one experiment; scalar keyword overrides shadow the spec's
    declared values without recompiling (they are traced operands).
    ``service_lat``/``service_jitter`` take one int or a per-class
    vector — the per-class service-latency distribution.  ``backend``
    picks the router hot-loop implementation ("jnp" reference, the
    "pallas" arbiter kernel, or the fused "pallas_fused" full-cycle
    kernel — see :mod:`repro.noc.backends`); results are
    backend-invariant.  ``verify="full"`` statically rejects
    deadlock-prone specs before stepping (see
    :func:`simulate_schedules` / :mod:`repro.noc.analyze`).  The NI
    robustness knobs (``timeout_cycles``/``max_retries``/
    ``backoff_base``) require a spec with a FaultModel.
    ``shard=RowShard(n)`` row-shards one big fabric across ``n`` local
    devices (:mod:`repro.noc.farm` tier b), flit-for-flit identical."""
    return _simulate(spec, lambda: workload.schedules(spec),
                     service_lat=service_lat,
                     max_outstanding=max_outstanding,
                     burst_beats=burst_beats, service_jitter=service_jitter,
                     jitter_seed=jitter_seed, timeout_cycles=timeout_cycles,
                     max_retries=max_retries, backoff_base=backoff_base,
                     backend=backend, verify=verify, shard=shard)


def simulate_batch(spec: NocSpec, workloads: Sequence[Workload], *,
                   service_lat=None, max_outstanding=None,
                   burst_beats=None, service_jitter=None,
                   jitter_seed: int = 0,
                   backend: str = "jnp",
                   verify: str = "fast") -> SimResult:
    """Run N operating points in ONE vmapped jit call.

    ``workloads`` supplies per-point schedules (rate/seed/pattern/mix
    sweeps). The knobs (``service_lat``, ``max_outstanding``,
    ``burst_beats``, ``service_jitter``) each take one int (all
    classes, all points), a length-N sequence (swept per point), a
    length-n_cls vector (per-class, broadcast across points), or an
    (N, n_cls) array (fully swept).  When N == n_cls a 1-D vector is
    ambiguous and resolves to each knob's historical meaning —
    per-point for ``service_lat``, per-class for the rest; pass the
    explicit (N, n_cls) form to be unambiguous.  Returns a SimResult
    whose arrays carry a leading sweep axis.
    """
    if not workloads:
        raise ValueError("empty sweep")
    with call_span("noc.sweep"):
        _verify(spec, verify)
        with span("noc.schedules"):
            args, in_axes = _batch_operands(
                spec, workloads, service_lat, max_outstanding, burst_beats,
                service_jitter, jitter_seed)
        T = args[0].shape[-1]
        prog = compiled_program(
            ("sim", _depth_normalized(spec, None)[0], T, backend),
            lambda: compiled_sim(spec, T, backend), args, in_axes)
        return run_program(spec, prog, args)


def _batch_operands(spec: NocSpec, workloads, service_lat,
                    max_outstanding, burst_beats, service_jitter,
                    jitter_seed) -> tuple[tuple, tuple]:
    """The operands of :func:`simulate_batch`'s vmapped call and their
    ``in_axes``."""
    n = len(workloads)
    fops = _fault_ops(spec)    # fault knobs stay spec-declared per batch
    per_point = [wl.schedules(spec) for wl in workloads]
    T = max(max(np.asarray(t).reshape(spec.n_routers, -1).shape[1]
                for t, *_ in sched.values()) for sched in per_point)
    stacked = [stack_schedules(spec, sched, T=T) for sched in per_point]
    times = np.stack([t for t, _, _ in stacked])       # (n, n_cls, R, T)
    dests = np.stack([d for _, d, _ in stacked])
    writes = np.stack([w for _, _, w in stacked])
    n_cls = len(spec.classes)

    def per_class_axis(v, default, name, prefer):
        """scalar -> all classes; (n,) -> per-point; (n_cls,) ->
        broadcast; (n, n_cls) -> swept.  When N == n_cls a 1-D vector
        is ambiguous: ``prefer`` resolves it to the knob's historical
        meaning (per-point for ``service_lat``, per-class for the
        per-class knobs) — pass an explicit 2-D array to override."""
        if v is None:
            return _per_class_vec(spec, None, default, name), None
        v = np.asarray(v, np.int32)
        if v.ndim == 0:
            return np.full((n_cls,), int(v), np.int32), None
        interps = [("point", (n,)), ("class", (n_cls,))]
        interps.sort(key=lambda it: it[0] != prefer)
        for how, shape in interps:
            if v.shape != shape:
                continue
            if how == "point":     # per-point scalar, swept
                return np.broadcast_to(v[:, None], (n, n_cls)).copy(), 0
            return v, None
        if v.shape == (n, n_cls):
            return v, 0
        raise ValueError(
            f"{name} must be a scalar, length-{n} sweep, ({n_cls},) "
            f"per-class vector, or ({n}, {n_cls}) array; got shape "
            f"{v.shape}")

    sl, sl_ax = per_class_axis(
        service_lat,
        [spec.service_lat if c.service_lat is None else c.service_lat
         for c in spec.classes], "service_lat", prefer="point")
    mo, mo_ax = per_class_axis(
        max_outstanding, [c.max_outstanding for c in spec.classes],
        "max_outstanding", prefer="class")
    bb, bb_ax = per_class_axis(
        burst_beats, [c.burst_beats for c in spec.classes], "burst_beats",
        prefer="class")
    jit, jit_ax = per_class_axis(
        service_jitter, [c.service_jitter for c in spec.classes],
        "service_jitter", prefer="class")
    if sl_ax is None and jit_ax is None:
        jt = jitter_table(spec, jit, seed=jitter_seed, service_lat=sl)
        jt_ax = None
    else:                              # per-point means/jitter widths
        jt = np.stack([jitter_table(
            spec, jit[i] if jit_ax == 0 else jit, seed=jitter_seed,
            service_lat=sl[i] if sl_ax == 0 else sl) for i in range(n)])
        jt_ax = 0

    for t, d in ((times[i], dests[i]) for i in range(n)):
        _check_dead_traffic(spec, t, d)
    return ((times, dests, writes, sl, mo, bb, jt, _depths(spec), *fops),
            (0, 0, 0, sl_ax, mo_ax, bb_ax, jt_ax, None,
             *((None,) * len(fops))))


def _strip_depths(spec: NocSpec) -> NocSpec:
    """Grouping key for :func:`sweep`: depth is a traced operand, so
    specs differing only in channel FIFO depths share a compilation."""
    return spec.with_(channels=tuple(
        replace(ch, depth=1) for ch in spec.channels))


def _batch_depth_sweep(specs: Sequence[NocSpec], wls: Sequence[Workload],
                       backend: str) -> SimResult:
    """Vmap points that differ only in FIFO depths through ONE
    padded-depth compilation (depth masked against the group max)."""
    base = specs[0]
    with span("noc.schedules"):
        per_point = [wl.schedules(s) for s, wl in zip(specs, wls)]
        T = max(max(np.asarray(t).reshape(base.n_routers, -1).shape[1]
                    for t, *_ in sched.values()) for sched in per_point)
        stacked = [stack_schedules(s, sched, T=T)
                   for s, sched in zip(specs, per_point)]
        times = np.stack([t for t, _, _ in stacked])
        dests = np.stack([d for _, d, _ in stacked])
        writes = np.stack([w for _, _, w in stacked])
        sl, mo, bb = _dyn_scalars(base, None, None, None)
        jt = jitter_table(base)
        fops = _fault_ops(base)
        for t, d in ((times[i], dests[i]) for i in range(len(specs))):
            _check_dead_traffic(base, t, d)
        depths = np.stack([_depths(s) for s in specs])     # (n, n_ch)
        args = (times, dests, writes, sl, mo, bb, jt, depths, *fops)
    d_max = int(depths.max())
    prog = compiled_program(
        ("sim", _depth_normalized(base, d_max)[0], T, backend),
        lambda: compiled_sim(base, T, backend, max_depth=d_max), args,
        (0, 0, 0, None, None, None, None, 0, *((None,) * len(fops))))
    return run_program(base, prog, args)


def sweep(points: Sequence[tuple[NocSpec, Workload]], *,
          backend: str = "jnp", pad_depths: bool = True,
          verify: str = "fast",
          devices: int | None = None) -> list[SimResult]:
    """Simulate arbitrary (spec, workload) points, vmapping every group
    of points that shares a static spec. Results come back in input
    order, one unbatched SimResult per point.

    With ``pad_depths`` (default) points whose specs differ ONLY in
    channel FIFO depths also share one group: the group compiles once
    at the max depth with per-point depths a vmapped traced operand —
    a whole depth sweep costs a single ``compiled_sim`` compilation
    (count it with :func:`repro.noc.sim_cache_stats`).

    ``devices=N`` (:mod:`repro.noc.farm` tier a) shards each vmapped
    group across N local devices: the group batch splits on a
    ``specs`` shard_map axis (uneven groups padded with the last point
    and sliced back), per-point results bit-identical to the
    single-device path.  ``devices=None`` keeps the classic one-device
    vmap; size-1 groups always run unsharded.

    ``verify`` runs the :mod:`repro.noc.analyze` gate once per distinct
    spec before any simulation (the deadlock proof is lru-cached per
    (topology, routing), so a 70-point sweep pays it once)."""
    with call_span("noc.sweep"):
        return _sweep(points, backend=backend, pad_depths=pad_depths,
                      verify=verify, devices=devices)


def _sweep(points, *, backend, pad_depths, verify, devices):
    for s in {spec for spec, _ in points}:
        _verify(s, verify)
    groups: dict[NocSpec, list[int]] = {}
    for i, (spec, _) in enumerate(points):
        key = _strip_depths(spec) if pad_depths else spec
        groups.setdefault(key, []).append(i)
    out: list[SimResult | None] = [None] * len(points)
    for idxs in groups.values():
        specs = [points[i][0] for i in idxs]
        wls = [points[i][1] for i in idxs]
        # every spec is verified above: the groups' calls skip the gate
        if len(idxs) == 1:
            out[idxs[0]] = simulate(specs[0], wls[0], backend=backend,
                                    verify="off")
        elif devices is not None:
            from .farm import farm_batch
            batched = farm_batch(specs, wls, devices, backend)
            for j, i in enumerate(idxs):
                # re-attach each point's own spec (the farm compiles
                # under the group's depth-padded base spec)
                out[i] = replace(batched.point(j), spec=specs[j])
        elif all(s == specs[0] for s in specs):
            batched = simulate_batch(specs[0], wls, backend=backend,
                                     verify="off")
            for j, i in enumerate(idxs):
                out[i] = batched.point(j)
        else:
            batched = _batch_depth_sweep(specs, wls, backend)
            for j, i in enumerate(idxs):
                # re-attach each point's own spec (the batch compiled
                # under the group's depth-padded base spec)
                out[i] = replace(batched.point(j), spec=specs[j])
    return out  # type: ignore[return-value]
