"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived = the quantity the
paper reports, e.g. latency cycles, bandwidth utilization, pJ/B/hop).

All cycle-level benches run through the declarative ``repro.noc`` API
(NocSpec presets + Workload patterns + vmapped ``simulate_batch``).

    PYTHONPATH=src python benchmarks/run.py [--smoke] [--json PATH]

``--smoke`` shrinks horizons for CI and ``--json`` (default
``BENCH_noc.json`` under --smoke) records every derived metric plus
wall time so the performance trajectory accumulates across PRs.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

RESULTS: dict[str, dict] = {}


def _record(name: str, us: float, compile_us: float | None = None,
            **derived):
    def _jsonable(v):
        if isinstance(v, (bool, np.bool_)):
            return bool(v)
        if isinstance(v, (int, float, np.integer, np.floating)):
            return float(v)
        return v
    row = {"us_per_call": round(us, 1)}
    if compile_us is not None:
        row["compile_us"] = round(compile_us, 1)
    RESULTS[name] = {**row,
                     **{k: _jsonable(v) for k, v in derived.items()}}


def _timed(fn, *args, repeat=1, **kw):
    """(out, run_us, compile_us): the first call carries tracing + XLA
    compilation, steady-state calls don't — report them separately
    instead of conflating them in one number."""
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    first_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args, **kw)
    run_us = (time.perf_counter() - t0) / repeat * 1e6
    return out, run_us, max(first_us - run_us, 0.0)


def bench_zero_load_latency(smoke: bool = False):
    """Paper section VI-A: 18-cycle tile-to-tile round trip."""
    from repro.noc import NocSpec, Workload, simulate
    spec = NocSpec.narrow_wide(2, 1, cycles=200)
    wl = Workload.make("fig5", rates={"narrow": 0.01},
                       counts={"narrow": 1}, src=0, dst=1)
    m, us, cus = _timed(simulate, spec, wl)
    lat = float(m.classes["narrow"].avg_lat[0])
    print(f"zero_load_latency,{us:.0f},round_trip_cycles={lat:.0f} (paper=18)")
    _record("zero_load_latency", us, cus, round_trip_cycles=lat,
            paper=18)
    return lat


def bench_fig5a_latency(smoke: bool = False):
    """Fig. 5a: narrow latency under wide burst interference.

    One vmapped ``simulate_batch`` per topology covers the interference
    and no-interference points together."""
    from repro.noc import NocSpec, Workload, simulate_batch
    cycles = 3000 if smoke else 8000
    n_wide = 64 if smoke else 200
    rows = []
    for preset, tag in ((NocSpec.narrow_wide, "nw"),
                        (NocSpec.wide_only, "wideonly")):
        spec = preset(4, 4, cycles=cycles)
        for bidir in (False, True):
            # point 0: interference at `bidir`; point 1: the seed bench's
            # baseline — no wide traffic, always unidirectional
            wls = [Workload.make("fig5",
                                 rates={"narrow": 0.05, "wide": 1.0},
                                 counts={"narrow": 100, "wide": n_wide},
                                 src=0, dst=15, bidir=bidir),
                   Workload.make("fig5", rates={"narrow": 0.05},
                                 counts={"narrow": 100}, src=0, dst=15)]
            m, us, cus = _timed(simulate_batch, spec, wls)
            lat = float(m.classes["narrow"].avg_lat[0, 0])
            lat0 = float(m.classes["narrow"].avg_lat[1, 0])
            mx = float(m.classes["narrow"].max_lat[0, 0])
            name = f"fig5a_{tag}_{'bidir' if bidir else 'unidir'}"
            print(f"{name},{us:.0f},avg={lat:.0f}cyc({lat/lat0:.2f}x)"
                  f" max={mx:.0f}cyc({mx/lat0:.2f}x)")
            _record(name, us, cus, avg_cycles=lat, avg_x=lat / lat0,
                    max_x=mx / lat0)
            rows.append((tag, bidir, lat / lat0, mx / lat0))
    return rows


def bench_fig5b_bandwidth(smoke: bool = False):
    """Fig. 5b: wide effective bandwidth under narrow interference."""
    from repro.noc import NocSpec, Workload, simulate_batch
    cycles = 3000 if smoke else 6000
    n_wide = 128 if smoke else 256
    rows = []
    for preset, tag in ((NocSpec.narrow_wide, "nw"),
                        (NocSpec.wide_only, "wideonly")):
        spec = preset(4, 4, cycles=cycles)
        wls = [Workload.make("fig5",
                             rates={"narrow": nrate, "wide": 1.0},
                             counts={"narrow": 3000 if nrate else 0,
                                     "wide": n_wide},
                             src=0, dst=5)
               for nrate in (0.0, 1.0)]
        m, us, cus = _timed(simulate_batch, spec, wls)
        utils = [float(m.classes["wide"].eff_bw[i, 0]) for i in (0, 1)]
        rel = utils[1] / max(utils[0], 1e-9)
        name = f"fig5b_{tag}"
        print(f"{name},{us:.0f},util={utils[1]:.2f} rel={rel:.2f}"
              f" (paper nw>=0.85)")
        _record(name, us, cus, util=utils[1], rel=rel)
        rows.append((tag, utils))
    return rows


def bench_rate_sweep(smoke: bool = False):
    """API showcase: a vmapped injection-rate sweep in ONE jit call."""
    from repro.noc import NocSpec, Workload, simulate_batch
    spec = NocSpec.narrow_wide(4, 4, cycles=2000 if smoke else 4000)
    rates = [0.25, 0.5, 0.75, 1.0]
    wls = [Workload.make("fig5", rates={"narrow": 0.05, "wide": r},
                         counts={"narrow": 50, "wide": 32},
                         src=0, dst=15) for r in rates]
    m, us, cus = _timed(simulate_batch, spec, wls)
    bw = [float(m.classes["wide"].eff_bw[i, 0]) for i in range(len(rates))]
    print(f"rate_sweep_vmap,{us:.0f},"
          + " ".join(f"r{r}={b:.2f}" for r, b in zip(rates, bw)))
    _record("rate_sweep_vmap", us, cus,
            **{f"bw_at_{r}": b for r, b in zip(rates, bw)})
    return bw


def bench_backend_channels(smoke: bool = False):
    """Backend x channel-count comparison behind one simulate() surface.

    Times the jnp reference against the Pallas arbiter kernel and the
    fused router-update kernel on 1-channel (wide-only), 3-channel (paper
    narrow-wide) and 4-channel (2-stream) specs, checks them
    flit-for-flit equivalent, and records everything into
    BENCH_noc.json.  On the CPU the Pallas backends run interpreted, so
    their timings measure correctness cost, not kernel speed."""
    from repro.noc import NocSpec, Workload, simulate
    cycles = 1000 if smoke else 3000
    n_wide = 12 if smoke else 48
    specs = [
        ("1ch", NocSpec.wide_only(4, 4, cycles=cycles),
         {"narrow": 0.05, "wide": 1.0}, {"narrow": 30, "wide": n_wide}),
        ("3ch", NocSpec.narrow_wide(4, 4, cycles=cycles),
         {"narrow": 0.05, "wide": 1.0}, {"narrow": 30, "wide": n_wide}),
        ("4ch", NocSpec.multi_stream(4, 4, n_wide=2, cycles=cycles),
         {"narrow": 0.05, "wide0": 1.0, "wide1": 1.0},
         {"narrow": 30, "wide0": n_wide // 2, "wide1": n_wide // 2}),
    ]
    backends = ("jnp", "pallas", "pallas_fused")
    rows = []
    for tag, spec, rates, counts in specs:
        wl = Workload.make("fig5", rates=rates, counts=counts,
                           src=0, dst=15)
        results = {}
        for backend in backends:
            m, us, cus = _timed(simulate, spec, wl, backend=backend)
            results[backend] = (m, us, cus)
        mj, usj, cusj = results["jnp"]
        equal = all(
            np.array_equal(getattr(mj.classes[c], f),
                           getattr(results[b][0].classes[c], f))
            for b in backends[1:]
            for c in mj.classes
            for f in ("done", "avg_lat", "max_lat", "beats_rx", "eff_bw")
        ) and all(
            np.array_equal(mj.channels[ch].link_moves,
                           results[b][0].channels[ch].link_moves)
            for b in backends[1:] for ch in mj.channels)
        lat = float(mj.classes["narrow"].avg_lat[0])
        name = f"backend_{tag}"
        print(f"{name},{usj:.0f},jnp={usj:.0f}us "
              f"pallas={results['pallas'][1]:.0f}us "
              f"fused={results['pallas_fused'][1]:.0f}us "
              f"equal={equal} narrow_avg={lat:.0f}cyc")
        _record(name, usj, cusj, pallas_us=results["pallas"][1],
                pallas_fused_us=results["pallas_fused"][1],
                backends_equal=equal,
                narrow_avg_cycles=lat, n_channels=len(spec.channels))
        rows.append((tag, usj, equal))
    assert all(eq for *_, eq in rows), "backend mismatch!"
    return rows


def bench_write_mix(smoke: bool = False):
    """AXI4 write-path bench: read-only vs 50/50 vs write-heavy traffic
    through the full AW/W/B flow model, across ALL THREE backends.

    For each mix, every backend must agree flit-for-flit (asserted);
    the derived metrics record per-direction completions/latency and
    the per-channel link-move shift as W bursts move to the wide
    channel and B acks load the rsp channel.  On the CPU the Pallas
    backends run interpreted (correctness cost, not kernel speed)."""
    from repro.noc import NocSpec, Workload, simulate
    cycles = 1500 if smoke else 4000
    n_wide = 12 if smoke else 48
    spec = NocSpec.narrow_wide(4, 4, cycles=cycles)
    backends = ("jnp", "pallas", "pallas_fused")
    fields = ("done", "avg_lat", "max_lat", "beats_rx", "eff_bw",
              "w_done", "w_avg_lat", "w_max_lat", "w_beats_rx", "w_eff_bw")
    rows = []
    for tag, wf in (("read_only", 0.0), ("mix50", 0.5),
                    ("write_heavy", 0.9)):
        wl = Workload.make("fig5", rates={"narrow": 0.05, "wide": 1.0},
                           counts={"narrow": 30, "wide": n_wide},
                           src=0, dst=15, bidir=True, write_frac=wf)
        results = {}
        for backend in backends:
            m, us, cus = _timed(simulate, spec, wl, backend=backend)
            results[backend] = (m, us, cus)
        mj, usj, cusj = results["jnp"]
        equal = all(
            np.array_equal(getattr(mj.classes[c], f),
                           getattr(results[b][0].classes[c], f))
            for b in backends[1:] for c in mj.classes for f in fields
        ) and all(
            np.array_equal(mj.channels[ch].link_moves,
                           results[b][0].channels[ch].link_moves)
            for b in backends[1:] for ch in mj.channels)
        assert equal, f"backend mismatch on write mix {tag}!"
        r_done = sum(int(c.done.sum()) for c in mj.classes.values())
        w_done = sum(int(c.w_done.sum()) for c in mj.classes.values())
        w_lat = float(np.max(mj.classes["wide"].w_avg_lat)) if w_done \
            else 0.0
        name = f"write_mix_{tag}"
        print(f"{name},{usj:.0f},reads={r_done} writes={w_done} "
              f"wide_w_avg_lat={w_lat:.0f}cyc "
              f"rsp_moves={int(mj.channels['rsp'].link_moves)} "
              f"drained={bool(mj.drained)} equal={equal}")
        _record(name, usj, cusj, reads_done=r_done, writes_done=w_done,
                wide_write_avg_lat=w_lat,
                rsp_link_moves=int(mj.channels["rsp"].link_moves),
                wide_link_moves=int(mj.channels["wide"].link_moves),
                drained=bool(mj.drained), backends_equal=equal,
                pallas_us=results["pallas"][1],
                pallas_fused_us=results["pallas_fused"][1])
        rows.append((tag, r_done, w_done))
    # the mix conserves transactions while shifting direction
    totals = {tag: r + w for tag, r, w in rows}
    assert len(set(totals.values())) == 1, totals
    return rows


def bench_routing(smoke: bool = False):
    """Routing-policy x VC-count study: torus vs mesh throughput at
    EQUAL saturating all-to-all load (paper-adjacent: the journal
    FlooNoC routing evaluation + escape-VC deadlock freedom).

    The VC-less minimal-wrap torus wedges under this load (drained
    False, stall ~ horizon) — recorded as the contrast point.  With the
    2-VC escape/dateline policy the torus drains and completes at least
    as many transactions as the mesh in the same horizon (asserted:
    that is the PR acceptance).  The escape-VC jnp/pallas_fused results
    are also equivalence-asserted so the folded-table VC fabric stays
    backend-exact inside the bench, not just the test suite."""
    from repro.noc import Mesh, NocSpec, RoutingPolicy, Torus, Workload, \
        simulate
    cycles = 2000 if smoke else 3500
    wl = Workload.make("all_to_all", rates={"wide": 1.0},
                       rounds={"wide": 4}, write_frac=0.5)

    def mk(topo, pol):
        return NocSpec.wide_only(4, 4, topology=topo, burstlen=32,
                                 cycles=cycles, max_wide_outstanding=16,
                                 routing=pol)

    configs = [
        ("mesh_xy_1vc", Mesh(4, 4), RoutingPolicy.xy(1)),
        ("torus_xy_1vc", Torus(4, 4), RoutingPolicy.xy(1)),
        ("torus_xy_2vc", Torus(4, 4), RoutingPolicy.xy(2)),
        ("mesh_o1turn_2vc", Mesh(4, 4), RoutingPolicy.o1turn(2)),
        ("torus_o1turn_4vc", Torus(4, 4), RoutingPolicy.o1turn(4)),
        ("mesh_valiant_4vc", Mesh(4, 4), RoutingPolicy.valiant(4)),
    ]
    done = {}
    for tag, topo, pol in configs:
        spec = mk(topo, pol)
        m, us, cus = _timed(simulate, spec, wl)
        st = m.classes["wide"]
        n_done = int(st.done.sum()) + int(st.w_done.sum())
        done[tag] = n_done
        thpt = n_done / cycles
        occ = m.channels["wide"].vc_occupancy
        name = f"routing_{tag}"
        print(f"{name},{us:.0f},done={n_done} thpt={thpt:.3f}/cyc "
              f"drained={bool(m.drained)} "
              f"max_stall={int(m.max_stall_cycles)} "
              f"vc_occ={np.round(occ, 1).tolist()}")
        _record(name, us, cus, txns_done=n_done, txns_per_cycle=thpt,
                drained=bool(m.drained),
                max_stall_cycles=int(m.max_stall_cycles),
                n_vcs=pol.n_vcs, algorithm=pol.algorithm,
                vc_peak_occupancy=[
                    int(v) for v in m.channels["wide"].vc_peak_occupancy])

    # escape-VC torus: backend-exact (jnp vs fused kernel, VC tables)
    spec = mk(Torus(4, 4), RoutingPolicy.xy(2))
    mj = simulate(spec, wl, backend="jnp")
    mf = simulate(spec, wl, backend="pallas_fused")
    equal = all(
        np.array_equal(getattr(mj.classes[c], f),
                       getattr(mf.classes[c], f))
        for c in mj.classes
        for f in ("done", "avg_lat", "beats_rx", "w_done", "w_beats_rx")
    ) and np.array_equal(mj.channels["wide"].link_moves,
                         mf.channels["wide"].link_moves)
    assert equal, "VC fabric backend mismatch in bench_routing!"

    torus_ge_mesh = done["torus_xy_2vc"] >= done["mesh_xy_1vc"]
    print(f"routing_summary,0,torus2vc={done['torus_xy_2vc']} "
          f"mesh={done['mesh_xy_1vc']} torus_ge_mesh={torus_ge_mesh} "
          f"backends_equal={equal}")
    _record("routing_summary", 0.0, torus_done=done["torus_xy_2vc"],
            mesh_done=done["mesh_xy_1vc"], torus_ge_mesh=torus_ge_mesh,
            vcless_torus_done=done["torus_xy_1vc"], backends_equal=equal)
    assert torus_ge_mesh, (
        f"escape-VC torus completed {done['torus_xy_2vc']} < mesh "
        f"{done['mesh_xy_1vc']} at equal load")
    return done


def _count_eqns(jaxpr) -> int:
    """Total jaxpr equations, recursing into scan/jit sub-jaxprs — the
    trace-size metric the fusion work optimizes."""
    n = 0
    for eq in jaxpr.eqns:
        n += 1
        for v in eq.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for x in vs:
                inner = getattr(x, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    n += _count_eqns(inner)
                elif hasattr(x, "eqns"):
                    n += _count_eqns(x)
    return n


def _scan_body_eqns(jaxpr) -> int:
    """Equation count of the innermost scan body — per-cycle HLO ops."""
    for eq in jaxpr.eqns:
        for v in eq.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for x in vs:
                inner = getattr(x, "jaxpr", None)
                inner = inner if inner is not None and hasattr(
                    inner, "eqns") else (x if hasattr(x, "eqns") else None)
                if inner is None:
                    continue
                if eq.primitive.name == "scan":
                    return len(inner.eqns)
                found = _scan_body_eqns(inner)
                if found:
                    return found
    return 0


def bench_ledger_replay(smoke: bool = False):
    """Replay a REAL decode step's collective ledger through all three
    backends.  The trace (``benchmarks/decode_ledger.json``) was
    captured once from ``build_decode_step`` on a 2x2 device mesh
    (llama3.2-1b smoke) and committed via ``Ledger.to_json`` — the
    bench replays it with ``Workload.from_ledger`` on the job's own
    rank mapping, times each backend, and asserts the replayed traffic
    is flit-for-flit identical across them (including the per-stream
    completion stats)."""
    from pathlib import Path

    from repro.core.channels import Ledger
    from repro.noc import NocSpec, Workload, simulate

    led = Ledger.from_json(
        (Path(__file__).parent / "decode_ledger.json").read_text())
    spec = NocSpec.narrow_wide(4, 4, cycles=2500 if smoke else 4000)
    wl = Workload.from_ledger(led, spec, mapping={"data": 2, "model": 2},
                              scale=0.25)
    results = {}
    for backend in ("jnp", "pallas", "pallas_fused"):
        r, us, compile_us = _timed(simulate, spec, wl, backend=backend,
                                   repeat=1 if smoke else 3)
        results[backend] = (r, us, compile_us)
    ref = results["jnp"][0]
    for backend in ("pallas", "pallas_fused"):
        r = results[backend][0]
        for cname, c in ref.classes.items():
            other = r.classes[cname]
            for f in ("done", "avg_lat", "w_done", "w_avg_lat",
                      "stream_done", "stream_last_t", "stream_w_done",
                      "stream_w_last_t"):
                np.testing.assert_array_equal(
                    getattr(c, f), getattr(other, f),
                    err_msg=f"{backend}:{cname}.{f}")
        for ch in ref.channels:
            np.testing.assert_array_equal(
                ref.channels[ch].link_moves, r.channels[ch].link_moves,
                err_msg=f"{backend}:{ch}.link_moves")
    txns = sum(int(c.done.sum() + c.w_done.sum())
               for c in ref.classes.values())
    makespan = max(int(c.stream_w_last_t.max())
                   for c in ref.classes.values())
    for backend in ("jnp", "pallas", "pallas_fused"):
        _, us, compile_us = results[backend]
        print(f"ledger_replay_{backend},{us:.0f},txns={txns} "
              f"makespan={makespan} drained={bool(ref.drained)} "
              f"equal=True")
        _record(f"ledger_replay_{backend}", us, compile_us,
                txns=txns, makespan=makespan,
                drained=bool(ref.drained), backends_equal=True,
                entries=len(led.entries))


def bench_engine_throughput(smoke: bool = False):
    """Perf tentpole bench: the fused hot loop vs the PINNED pre-PR
    engine (``_baseline_engine.py``), measured in the same process on
    bit-identical workloads.

    Records router steps/sec, run vs compile wall time, per-cycle HLO
    op count (scan-body jaxpr equations), the >=3x speedup target on
    the fig5 preset, a backend x mesh x channel-count steps/sec grid,
    and the one-compilation depth-sweep cost per point."""
    import jax
    from repro.noc import NocSpec, Workload, sim_cache_clear, \
        sim_cache_stats, simulate, sweep
    from repro.noc.api import _depths, _dyn_scalars, jitter_table, \
        stack_schedules
    from repro.noc.engine import compiled_sim
    import _baseline_engine as baseline

    cycles = 1500 if smoke else 4000
    spec = NocSpec.narrow_wide(4, 4, cycles=cycles)
    wl = Workload.make("fig5", rates={"narrow": 0.05, "wide": 1.0},
                       counts={"narrow": 100, "wide": 64},
                       src=0, dst=15, bidir=True)
    times, dests, writes = stack_schedules(spec, wl.schedules(spec))
    sl, mo, bb = _dyn_scalars(spec, None, None, None)
    T = times.shape[-1]

    new_fn = compiled_sim(spec, T)
    old_fn = baseline.compiled_sim_baseline(spec, T)
    new_args = (times, dests, writes, sl, mo, bb, jitter_table(spec),
                _depths(spec))
    # the pinned baseline predates the AXI4 flow model: scalar service
    # latency, no write mask/jitter operands
    old_args = (times, dests, np.int32(spec.service_lat), mo, bb)
    block = jax.block_until_ready
    out_new, run_new, comp_new = _timed(
        lambda: block(new_fn(*new_args)), repeat=3)
    out_old, run_old, comp_old = _timed(
        lambda: block(old_fn(*old_args)), repeat=3)
    # compare the read metrics the baseline knows about (the live
    # engine additionally reports write metrics + liveness)
    equal = all(np.array_equal(np.asarray(out_new[k]),
                               np.asarray(out_old[k])) for k in out_old)
    assert equal, "AXI4 engine diverged from the pinned baseline!"

    sps_new = cycles / (run_new / 1e6)
    sps_old = cycles / (run_old / 1e6)
    speedup = run_old / run_new
    jp_new = jax.make_jaxpr(new_fn)(*new_args).jaxpr
    jp_old = jax.make_jaxpr(old_fn)(*old_args).jaxpr
    eq_new, cyc_new = _count_eqns(jp_new), _scan_body_eqns(jp_new)
    eq_old, cyc_old = _count_eqns(jp_old), _scan_body_eqns(jp_old)
    print(f"engine_throughput,{run_new:.0f},steps/s={sps_new:,.0f} "
          f"(baseline {sps_old:,.0f}) speedup={speedup:.2f}x "
          f"scan_body_eqns={cyc_new} (baseline {cyc_old}) "
          f"compile={comp_new/1e3:.0f}ms (baseline {comp_old/1e3:.0f}ms)")
    # the live engine now also models the AXI4 write path (five flow
    # gathers, W rings, per-direction metrics) the read-only baseline
    # doesn't, so the historical 3x-over-baseline target became ~2x;
    # warn only on a real regression below that level
    if speedup < 1.5:
        print(f"# WARNING: fig5 speedup {speedup:.2f}x below the 1.5x "
              f"floor — engine regression?")
    _record("bench_engine_throughput", run_new, comp_new,
            steps_per_sec=sps_new, baseline_steps_per_sec=sps_old,
            speedup_x=speedup, baseline_us_per_call=run_old,
            baseline_compile_us=comp_old, results_equal=equal,
            scan_body_eqns=cyc_new, baseline_scan_body_eqns=cyc_old,
            total_trace_eqns=eq_new, baseline_total_trace_eqns=eq_old,
            cycles=cycles)

    # backend x mesh x channel-count steps/sec grid (interpret-mode
    # Pallas on the CPU: correctness cost, not kernel speed)
    grid_cycles = 300 if smoke else 1000
    grid = [("jnp", 4, NocSpec.narrow_wide, "3ch"),
            ("jnp", 8, NocSpec.narrow_wide, "3ch"),
            ("jnp", 4, NocSpec.wide_only, "1ch"),
            ("pallas", 4, NocSpec.narrow_wide, "3ch"),
            ("pallas_fused", 4, NocSpec.narrow_wide, "3ch"),
            ("pallas_fused", 4, NocSpec.wide_only, "1ch")]
    for backend, n, preset, tag in grid:
        gspec = preset(n, n, cycles=grid_cycles)
        gwl = Workload.make("fig5", rates={"narrow": 0.05, "wide": 1.0},
                            counts={"narrow": 30, "wide": 12},
                            src=0, dst=n * n - 1)
        _, us, cus = _timed(simulate, gspec, gwl, backend=backend)
        sps = grid_cycles / (us / 1e6)
        name = f"engine_grid_{backend}_{n}x{n}_{tag}"
        print(f"{name},{us:.0f},steps/s={sps:,.0f}")
        _record(name, us, cus, steps_per_sec=sps, mesh=n,
                n_channels=len(gspec.channels))

    # one-compilation FIFO-depth sweep: wall per point, compiles counted
    depths = (2, 3, 4, 6)
    dwl = Workload.make("fig5", rates={"narrow": 0.2, "wide": 1.0},
                        counts={"narrow": 20, "wide": 8}, src=0, dst=15)
    pts = [(NocSpec.narrow_wide(4, 4, depth=d, cycles=grid_cycles), dwl)
           for d in depths]
    sim_cache_clear()
    _, us, cus = _timed(sweep, pts)
    compiles = sim_cache_stats()["misses"]
    print(f"depth_sweep,{us / len(pts):.0f},points={len(pts)} "
          f"compiles={compiles} wall_per_point_us={us / len(pts):.0f}")
    _record("depth_sweep", us / len(pts), cus,
            points=len(pts), compiles=compiles)
    assert compiles == 1, f"depth sweep compiled {compiles}x, expected 1"
    return speedup


def _sweep_scaling_points(smoke: bool):
    """The shared sweep campaign: >=64 spec points (16 under smoke
    workers would undershoot the acceptance floor, so both modes keep
    64 and shrink the horizon instead), one depth-compatible group so
    the whole campaign rides a single farm-compiled executable."""
    from repro.noc import NocSpec, Workload
    n_specs = 64
    cycles = 400 if smoke else 1200
    depths = (2, 3, 4, 6)
    pts = []
    for i in range(n_specs):
        spec = NocSpec.narrow_wide(4, 4, depth=depths[i % len(depths)],
                                   cycles=cycles)
        wl = Workload.make("uniform_random",
                           rates={"narrow": 0.1, "wide": 0.6},
                           counts={"narrow": 4, "wide": 3}, seed=i)
        pts.append((spec, wl))
    return pts


def _sweep_scaling_run(devices: int, smoke: bool) -> dict:
    """Run the campaign twice on ``jax.devices()[:devices]`` (the first
    call compiles) and return its stats and result digest."""
    import jax
    from repro.noc import sim_cache_clear, sim_cache_stats, sweep

    if jax.device_count() < devices:
        raise SystemExit(
            f"wanted {devices} devices, jax sees {jax.device_count()} — "
            f"XLA_FLAGS not applied before import?")
    pts = _sweep_scaling_points(smoke)
    sim_cache_clear()
    t0 = time.perf_counter()
    out = sweep(pts, devices=devices)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = sweep(pts, devices=devices)
    run_s = time.perf_counter() - t0
    misses = sim_cache_stats()["misses"]
    # one inner engine build (shared "jnp" partition) + one farm
    # shard_map wrapper serve the whole campaign, and the second call
    # reuses both — the farm partition must not recompile per call
    assert misses == 2, f"farm sweep built {misses} fns, expected 2"
    return {"devices": devices, "n_specs": len(pts),
            "specs_per_sec": len(pts) / run_s,
            "run_s": round(run_s, 4), "compile_s": round(compile_s, 2),
            "compiles": misses, "digest": sweep_digest(out)}


def sweep_digest(results) -> str:
    """sha256 over every point's per-class stats and link moves — equal
    digests mean bit-identical sweeps."""
    import hashlib

    h = hashlib.sha256()
    for m in results:
        for cname in sorted(m.classes):
            c = m.classes[cname]
            for f in ("done", "avg_lat", "max_lat", "beats_rx", "w_done",
                      "w_avg_lat", "w_beats_rx"):
                h.update(np.ascontiguousarray(getattr(c, f)).tobytes())
        for ch in sorted(m.channels):
            h.update(np.ascontiguousarray(
                m.channels[ch].link_moves).tobytes())
    return h.hexdigest()


def bench_sweep_scaling(smoke: bool = False):
    """Tentpole bench: the device-parallel sweep farm at 1/2/4/8
    devices over the same >=64-spec campaign.

    On an accelerator every count runs in this process over
    ``jax.devices()[:n]``, for each count up to the visible devices (a
    chip belongs to one process, so a child could not reach it).  On
    the CPU each count runs in its own subprocess so
    ``XLA_FLAGS=--xla_force_host_platform_device_count`` lands before
    jax import.

    Records specs/sec and parallel efficiency per device count plus the
    result digest — asserted identical across counts (sharding must be
    bit-invisible).  Host 'devices' share this machine's physical
    cores, so real speedup needs real cores: the >=5x floor at 8 host
    devices is asserted only when the host has >= 8 cores, and the
    honest per-count numbers + core count are recorded either way."""
    import jax

    on_cpu = jax.default_backend() == "cpu"
    devices_list = (1, 2, 4, 8) if on_cpu else tuple(
        n for n in (1, 2, 4, 8) if n <= jax.device_count())
    cores = os.cpu_count() or 1
    stats = {}
    for n in devices_list:
        if not on_cpu:
            stats[n] = _sweep_scaling_run(n, smoke)
            continue
        env = dict(os.environ)
        flags = env.get("XLA_FLAGS", "")
        flags = " ".join(f for f in flags.split()
                         if not f.startswith(
                             "--xla_force_host_platform_device_count"))
        env["XLA_FLAGS"] = (flags + " "
                            f"--xla_force_host_platform_device_count={n}"
                            ).strip()
        cmd = [sys.executable, os.path.abspath(__file__),
               "--sweep-worker", str(n)]
        if smoke:
            cmd.append("--smoke")
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"sweep worker (devices={n}) failed:\n{proc.stdout}\n"
                f"{proc.stderr}")
        stats[n] = json.loads(proc.stdout.strip().splitlines()[-1])

    digests = {s["digest"] for s in stats.values()}
    assert len(digests) == 1, \
        f"sweep results differ across device counts: {stats}"
    sps1 = stats[1]["specs_per_sec"]
    for n in devices_list:
        s = stats[n]
        eff = s["specs_per_sec"] / (n * sps1)
        speedup = s["specs_per_sec"] / sps1
        name = f"sweep_scaling_d{n}"
        print(f"{name},{1e6 / s['specs_per_sec']:.0f},"
              f"specs/s={s['specs_per_sec']:.1f} speedup={speedup:.2f}x "
              f"efficiency={eff:.2f} n_specs={s['n_specs']} "
              f"compiles={s['compiles']} cores={cores}")
        _record(name, 1e6 / s["specs_per_sec"],
                s["compile_s"] * 1e6,
                specs_per_sec=s["specs_per_sec"], speedup_x=speedup,
                efficiency=eff, n_specs=s["n_specs"],
                compiles=s["compiles"], cores=cores,
                bit_identical=True)
    if on_cpu and cores >= 8:
        assert stats[8]["specs_per_sec"] >= 5 * sps1, (
            f"sweep(devices=8) reached only "
            f"{stats[8]['specs_per_sec'] / sps1:.2f}x over devices=1 "
            f"on a {cores}-core host (need >= 5x)")
    elif on_cpu:
        print(f"# sweep_scaling: {cores} core(s) < 8 — host devices "
              f"share cores, >=5x floor not asserted (numbers above "
              f"are the honest single-core serialization)")
    return stats


def bench_table1_links(smoke: bool = False):
    """Table I / section VI-B: link sizing and peak bandwidth."""
    from repro.core.noc_sim import PAPER
    _, us, _ = _timed(lambda: None)
    gbps = PAPER.wide_link_gbps()
    tbps = PAPER.wide_link_duplex_tbps()
    agg = PAPER.mesh_boundary_bandwidth_tbs(7, 7)
    wires = PAPER.duplex_channel_wires()
    um = PAPER.routing_channel_um()
    print(f"table1_wide_link,{us:.0f},{gbps:.0f}Gbps (paper 629)")
    print(f"table1_duplex,{us:.0f},{tbps:.2f}Tbps (paper 1.26)")
    print(f"table1_mesh7x7_boundary,{us:.0f},{agg:.1f}TB/s (paper 4.4)")
    print(f"table1_channel_wires,{us:.0f},{wires} wires (~1600)")
    print(f"table1_channel_width,{us:.0f},{um:.0f}um (paper ~120)")
    _record("table1", us, wide_link_gbps=gbps, duplex_tbps=tbps,
            mesh7x7_boundary_tbs=agg, channel_wires=wires,
            channel_width_um=um)
    return gbps, tbps, agg


def bench_fig6_area_energy(smoke: bool = False):
    """Fig. 6: area/power breakdown + 0.19 pJ/B/hop."""
    from repro.core.noc_sim import PAPER
    _, us, _ = _timed(lambda: None)
    frac = PAPER.noc_area_fraction()
    e = PAPER.energy_pj(1024, 1)
    print(f"fig6_noc_area_fraction,{us:.0f},{frac:.2f} (paper 0.10)")
    print(f"fig6_energy_1kB_hop,{us:.0f},{e:.0f}pJ (paper 198)")
    print(f"fig6_pJ_per_B_hop,{us:.0f},{PAPER.pj_per_byte_hop} (paper 0.19)")
    _record("fig6", us, noc_area_fraction=frac, energy_1kB_hop_pj=e,
            pj_per_byte_hop=PAPER.pj_per_byte_hop)
    return frac, e


def bench_straggler_sim(smoke: bool = False):
    """Straggler mitigation at 1024 hosts (DESIGN section 7)."""
    from repro.train.straggler import SimulatedCluster
    sim = SimulatedCluster(n_hosts=128 if smoke else 1024)
    rep, us, cus = _timed(sim.report)
    for pol, r in rep.items():
        print(f"straggler_{pol},{us:.0f},p50={r['p50']:.3f} p99={r['p99']:.3f}")
        _record(f"straggler_{pol}", us, cus, p50=r["p50"],
                p99=r["p99"])
    return rep


def bench_train_step(smoke: bool = False):
    """End-to-end smoke train step through repro.dist (wide grad bulk +
    narrow flit-packed metrics riding the dual-channel policy)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch, ShapeConfig
    from repro.configs.base import MeshConfig, RunConfig
    from repro.dist import params as params_lib, step as step_lib
    from repro.models import build_model

    mcfg = get_arch("llama3.2-1b").smoke(num_layers=2, d_model=64, d_ff=128,
                                         vocab_size=256)
    shape = ShapeConfig("bench", 64, 4, "train")
    cfg = RunConfig(model=mcfg, shape=shape, mesh=MeshConfig(1, 1, 1))
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    model = build_model(mcfg, cfg)
    art = step_lib.build_train_step(model, shape, mesh)
    key = jax.random.key(0)
    params = params_lib.materialize_sharded(art.param_specs, key, mesh)
    opt = params_lib.materialize_sharded(art.opt_specs, key, mesh)
    toks = jax.random.randint(key, (4, 64), 0, mcfg.vocab_size, jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    t0 = time.perf_counter()
    params, opt, m = art.fn(params, opt, jnp.int32(0), batch)   # compile
    first_us = (time.perf_counter() - t0) * 1e6
    (_, _, m), us, _ = _timed(art.fn, params, opt, jnp.int32(1), batch,
                              repeat=2 if smoke else 5)
    loss = float(m["loss"])
    gnorm = float(m["grad_norm"])
    print(f"train_step,{us:.0f},loss={loss:.3f} grad_norm={gnorm:.3f}")
    _record("train_step", us, max(first_us - us, 0.0), loss=loss,
            grad_norm=gnorm)
    return loss


def bench_faults(smoke: bool = False):
    """Graceful-degradation study: the same workload on a healthy
    torus, with one statically dead X-link (rerouted around via the
    dedicated detour VC), and under flapping links with NI
    timeout/retry.  Reports completed transactions, worst-case latency
    inflation over healthy, and goodput while links are down.  The
    dead-link case is equivalence-asserted across all three backends —
    the fault machinery must stay backend-exact, not just the healthy
    path."""
    from repro.noc import (FaultModel, NocSpec, RoutingPolicy, Torus,
                           Workload, simulate)
    cycles = 4000 if smoke else 8000
    wl = Workload.make("uniform_random",
                       rates={"narrow": 0.3, "wide": 0.8},
                       counts={"narrow": 12, "wide": 5}, seed=7)

    def mk(faults=None):
        return NocSpec.narrow_wide(4, 4, topology=Torus(4, 4),
                                   cycles=cycles,
                                   routing=RoutingPolicy.xy(3),
                                   faults=faults)

    flap = FaultModel(link_events=((1, 2, 100, 260), (5, 6, 300, 420)),
                      timeout_cycles=2000, max_retries=2)
    configs = [
        ("healthy", None),
        ("dead_link", FaultModel(dead_links=((1, 2),))),
        ("flapping", flap),
    ]
    base_lat = None
    stats = {}
    for tag, fm in configs:
        spec = mk(fm)
        m, us, cus = _timed(simulate, spec, wl)
        n_done = sum(int(s.done.sum()) + int(s.w_done.sum())
                     for s in m.classes.values())
        worst = max(int(s.max_lat.max()) for s in m.classes.values())
        if base_lat is None:
            base_lat = worst
        row = {"txns_done": n_done, "max_lat": worst,
               "lat_x_healthy": worst / max(base_lat, 1),
               "drained": bool(m.drained)}
        if m.faults is not None:
            row["fault_cycles"] = int(m.faults.fault_cycles)
            row["retries"] = sum(int(np.sum(v))
                                 for v in m.faults.retries.values())
            row["goodput_under_fault"] = sum(
                float(v) for v in m.faults.goodput_under_fault.values())
        name = f"faults_{tag}"
        print(f"{name},{us:.0f}," + " ".join(
            f"{k}={v if not isinstance(v, float) else round(v, 3)}"
            for k, v in row.items()))
        _record(name, us, cus, **row)
        stats[tag] = (n_done, worst, bool(m.drained))

    # every case must drain, and the cut's latency hit stays under 2x
    assert all(d for _, _, d in stats.values()), stats
    assert stats["dead_link"][1] < 2 * stats["healthy"][1], stats

    # dead-link cut: backend-exact fault path
    spec = mk(FaultModel(dead_links=((1, 2),)))
    runs = {b: simulate(spec, wl, backend=b)
            for b in ("jnp", "pallas", "pallas_fused")}
    ref = runs["jnp"]
    for b, m in runs.items():
        for cname, s in ref.classes.items():
            got = m.classes[cname]
            assert int(got.done.sum()) == int(s.done.sum()), (b, cname)
            assert int(got.max_lat.max()) == int(s.max_lat.max()), b
        assert int(m.faults.fault_cycles) == int(ref.faults.fault_cycles)
    print("faults_backend_equiv,0,jnp==pallas==pallas_fused on the cut")
    _record("faults_backend_equiv", 0.0, equivalent=True)


def bench_channels_ablation(smoke: bool = False):
    """Software Fig. 5 analogue: the collectives schedule under the
    dual- vs single-channel policies derived from the same NocSpecs that
    drive the cycle simulator (one shared vocabulary)."""
    from repro.core import channels
    from repro.noc import NocSpec

    class Fake:
        def __init__(self, shape):
            self.shape = shape
            self.dtype = np.dtype(np.float32)

    leaves = [Fake((1024, 1024)), Fake((4096, 512))] + \
             [Fake((256,)) for _ in range(20)]
    t0 = time.perf_counter()
    dual = channels.ChannelPolicy.from_spec(NocSpec.narrow_wide())
    single = channels.ChannelPolicy.from_spec(NocSpec.wide_only())
    cls = [dual.classify(int(np.prod(l.shape)) * 4) for l in leaves]
    n_narrow = sum(c.transport == "psum" for c in cls)
    wide = [l for l, c in zip(leaves, cls) if c.transport == "ring"]
    buckets = channels.bucketize(wide, dual.bucket_bytes)
    us = (time.perf_counter() - t0) * 1e6
    narrow_bytes = sum(int(np.prod(l.shape)) * 4 for l, c in
                       zip(leaves, cls) if c.transport == "psum")
    single_shared = len({c.channel for c in single.classes}) == 1
    print(f"channels_dual,{us:.0f},smalls={n_narrow}->1 flit-packed psum"
          f" ({narrow_bytes}B) + {len(buckets)} wide ring bucket(s)"
          f" | single-channel policy shares 1 link: {single_shared}"
          f" ({len(leaves)} tensors serialized on one ring)")
    _record("channels_dual", us, n_narrow=n_narrow,
            narrow_bytes=narrow_bytes, wide_buckets=len(buckets),
            single_policy_shared=single_shared)
    return cls, buckets


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced horizons for CI")
    ap.add_argument("--json", default=None,
                    help="write derived metrics to this JSON file "
                         "(default BENCH_noc.json under --smoke)")
    ap.add_argument("--tpu", action="store_true",
                    help="require a real TPU backend: the Pallas benches "
                         "then compile through Mosaic (and hit the VMEM "
                         "budget check) instead of interpreting")
    ap.add_argument("--sweep-worker", type=int, default=None,
                    metavar="N", help=argparse.SUPPRESS)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.sweep_worker is not None:
        # child of bench_sweep_scaling's CPU path: one JSON line
        print(json.dumps(_sweep_scaling_run(args.sweep_worker,
                                            args.smoke)))
        return
    if args.tpu:
        import jax
        if jax.default_backend() != "tpu":
            raise SystemExit(
                f"--tpu passed but jax.default_backend() is "
                f"{jax.default_backend()!r}; the Pallas kernels would "
                f"silently fall back to interpret mode, which is not "
                f"the measurement you asked for")
    json_path = args.json or ("BENCH_noc.json" if args.smoke else None)

    t0 = time.perf_counter()
    print("name,us_per_call,derived")
    bench_table1_links(args.smoke)
    bench_fig6_area_energy(args.smoke)
    bench_zero_load_latency(args.smoke)
    bench_fig5a_latency(args.smoke)
    bench_fig5b_bandwidth(args.smoke)
    bench_rate_sweep(args.smoke)
    bench_backend_channels(args.smoke)
    bench_write_mix(args.smoke)
    bench_routing(args.smoke)
    bench_engine_throughput(args.smoke)
    bench_sweep_scaling(args.smoke)
    bench_ledger_replay(args.smoke)
    bench_straggler_sim(args.smoke)
    bench_train_step(args.smoke)
    bench_channels_ablation(args.smoke)
    bench_faults(args.smoke)
    wall_s = time.perf_counter() - t0

    if json_path:
        import jax
        payload = {"smoke": args.smoke, "wall_s": round(wall_s, 2),
                   "accelerator": jax.default_backend(),
                   "benches": RESULTS}
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"# wrote {json_path} ({len(RESULTS)} benches, "
              f"{wall_s:.1f}s wall)")


if __name__ == "__main__":
    main()
