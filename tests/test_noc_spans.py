"""Host spans and device phase scopes.

Every ``simulate``/``sweep`` call records its host phases in
``span_stats()`` (and as profiler annotations), and every operation of
the scan step runs under one of the four phase scopes, so a profiler
trace splits the device loop by phase.
"""
import json
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.noc import (FaultModel, NocSpec, Workload, simulate,
                       simulate_batch, span_stats, span_stats_clear, sweep)
from repro.noc.engine import PHASES, compiled_sim, sim_cache_clear

CALL_SPANS = ("noc.verify", "noc.schedules", "noc.dispatch", "noc.wait",
              "noc.results")


def _wl(seed=1):
    return Workload.make("uniform_random",
                         rates={"narrow": 0.05, "wide": 0.3},
                         counts={"narrow": 4, "wide": 4}, seed=seed,
                         write_frac=0.5)


def _counts():
    return {k: v["count"] for k, v in span_stats().items()}


def _added(before, after):
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in set(before) | set(after)
            if after.get(k, 0) != before.get(k, 0)}


@pytest.fixture
def spec():
    return NocSpec.narrow_wide(4, 4, cycles=300)


def test_simulate_adds_one_of_each_span(spec):
    sim_cache_clear()
    span_stats_clear()
    first = simulate(spec, _wl())
    assert _counts() == {"noc.simulate": 1, "noc.build": 1,
                         **{k: 1 for k in CALL_SPANS}}
    before = _counts()
    again = simulate(spec, _wl())
    assert _added(before, _counts()) == {"noc.simulate": 1,
                                         **{k: 1 for k in CALL_SPANS}}
    assert int(again.classes["wide"].done.sum()) == int(
        first.classes["wide"].done.sum()) > 0
    stats = span_stats()
    assert all(v["seconds"] >= 0 for v in stats.values())
    assert stats["noc.simulate"]["seconds"] >= stats["noc.wait"]["seconds"]
    span_stats_clear()
    assert span_stats() == {}


def test_sweep_adds_one_of_each_span_and_builds_only_on_a_miss(spec):
    """One sweep of one spec group: one call span, one of each phase;
    ``noc.build`` only where the simulator was not cached yet."""
    sim_cache_clear()
    pts = [(spec, _wl(s)) for s in (1, 2)]
    before = _counts()
    sweep(pts)
    assert _added(before, _counts()) == {
        "noc.sweep": 1, "noc.build": 1, **{k: 1 for k in CALL_SPANS}}
    before = _counts()
    sweep(pts)
    assert _added(before, _counts()) == {"noc.sweep": 1,
                                         **{k: 1 for k in CALL_SPANS}}
    before = _counts()
    simulate_batch(spec, [_wl(3), _wl(4)])
    assert _added(before, _counts()) == {"noc.sweep": 1,
                                         **{k: 1 for k in CALL_SPANS}}


def test_a_sweep_of_one_point_stays_one_call(spec):
    """A one-point group runs through ``simulate`` inside the sweep's
    call: no second parent span."""
    before = _counts()
    sweep([(spec, _wl())])
    added = _added(before, _counts())
    assert added["noc.sweep"] == 1 and "noc.simulate" not in added
    assert added["noc.dispatch"] == 1


def test_spans_carry_the_call_id(spec, tmp_path):
    """The profiler records each span with ``call=<id>``: one id per
    API call, shared by every span inside it."""
    simulate(spec, _wl())                    # built and warm
    jax.profiler.start_trace(str(tmp_path))
    try:
        simulate(spec, _wl())
        simulate(spec, _wl(2))
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(path))
    calls = {}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("noc."):
                    calls.setdefault(dict(e.stats).get("call"),
                                     []).append(e.name)
    assert None not in calls and len(calls) == 2, calls
    for names in calls.values():
        assert sorted(names) == sorted(("noc.simulate",) + CALL_SPANS)


def _sub_jaxprs(params):
    """The jaxprs held in an equation's parameters (closed or open,
    alone or in tuples, as ``cond`` branches are)."""
    for p in params.values():
        for q in (p if isinstance(p, (tuple, list)) else (p,)):
            q = getattr(q, "jaxpr", q)
            if hasattr(q, "eqns"):
                yield q


def scan_bodies(jaxpr):
    """The body jaxpr of every ``scan`` in ``jaxpr``, at any depth (not
    looking inside a scan's body)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn.params["jaxpr"].jaxpr
        else:
            for sub in _sub_jaxprs(eqn.params):
                yield from scan_bodies(sub)


def outer_scopes(body) -> list[str]:
    """The outermost name-stack scope of each equation of ``body``."""
    return [str(e.source_info.name_stack).split("/")[0] for e in body.eqns]


def operands(spec, wl):
    from repro.noc import stack_schedules
    from repro.noc.api import _depths, _dyn_scalars, _fault_ops, jitter_table
    t, d, w = stack_schedules(spec, wl.schedules(spec))
    sl, mo, bb = _dyn_scalars(spec, None, None, None)
    return (t, d, w, sl, mo, bb, jitter_table(spec), _depths(spec),
            *_fault_ops(spec))


@pytest.mark.parametrize(
    "faults", [None, FaultModel(link_events=((1, 2, 10, 30),))])
def test_every_step_equation_is_in_a_phase(faults):
    """Each equation of the scan body sits under exactly one of the
    four phases, its outermost scope; the fault watchdog too."""
    spec = NocSpec.narrow_wide(4, 4, cycles=50, faults=faults)
    args = operands(spec, _wl())
    fn = compiled_sim(spec, args[0].shape[-1])
    (body,) = scan_bodies(jax.make_jaxpr(fn)(*args).jaxpr)
    scopes = outer_scopes(body)
    assert set(scopes) == set(PHASES), sorted(set(scopes) - set(PHASES))
    for e in body.eqns:
        stack = str(e.source_info.name_stack).split("/")
        assert sum(s in PHASES for s in stack) == 1, stack
    fabric = {str(e.source_info.name_stack) for e in body.eqns
              if str(e.source_info.name_stack).startswith("noc.fabric/")}
    assert any("fabric_front" in s for s in fabric)
    assert any("fabric_update" in s for s in fabric)


_HERE = str(Path(__file__).resolve().parent)


def test_every_rowshard_step_equation_is_in_a_phase(subproc):
    """RowShard(4) on four CPU devices: the halo exchange sits under
    ``noc.fabric`` (as ``farm.halo``), the per-cycle ``psum``s under
    ``noc.stats``."""
    out = subproc(f"""
        import json, sys
        sys.path.insert(0, {_HERE!r})
        import jax
        from repro.noc import NocSpec, RowShard
        from repro.noc.farm import compiled_rowshard_sim
        import test_noc_spans as t
        spec = NocSpec.narrow_wide(4, 8, cycles=50)
        args = t.operands(spec, t._wl())
        fn = compiled_rowshard_sim(spec, args[0].shape[-1], RowShard(4))
        (body,) = t.scan_bodies(jax.make_jaxpr(fn)(*args).jaxpr)
        stacks = [str(e.source_info.name_stack) for e in body.eqns]
        print(json.dumps({{
            "scopes": sorted(set(t.outer_scopes(body))),
            "halo": sorted({{s.split("/")[0] for s in stacks
                            if "farm.halo" in s}}),
            "psum": sorted({{s.split("/")[0] for s, e in zip(stacks, body.eqns)
                            if e.primitive.name == "psum"}})}}))
    """, n_devices=4)
    got = json.loads(out.strip().splitlines()[-1])
    assert got == {"scopes": sorted(PHASES), "halo": ["noc.fabric"],
                   "psum": ["noc.stats"]}


def test_rowshard_results_fold_on_the_host(subproc):
    """The shard fold now runs after the device program, under
    ``noc.results``: a RowShard(2) run still equals the single-device
    engine and adds one of each span."""
    out = subproc(f"""
        import sys
        sys.path.insert(0, {_HERE!r})
        import numpy as np
        from repro.noc import (NocSpec, RowShard, simulate, span_stats,
                               span_stats_clear)
        import test_noc_spans as t
        spec = NocSpec.narrow_wide(4, 4, cycles=300)
        ref = simulate(spec, t._wl())
        span_stats_clear()
        sh = simulate(spec, t._wl(), shard=RowShard(2))
        for c in ref.classes:
            np.testing.assert_array_equal(ref.classes[c].done,
                                          sh.classes[c].done)
            np.testing.assert_array_equal(ref.classes[c].w_avg_lat,
                                          sh.classes[c].w_avg_lat)
        for ch in ref.channels:
            np.testing.assert_array_equal(ref.channels[ch].link_moves,
                                          sh.channels[ch].link_moves)
            np.testing.assert_array_equal(ref.channels[ch].vc_occupancy,
                                          sh.channels[ch].vc_occupancy)
        assert bool(ref.drained) == bool(sh.drained)
        assert int(ref.max_stall_cycles) == int(sh.max_stall_cycles)
        print(sorted((k, v["count"]) for k, v in span_stats().items()))
    """, n_devices=2)
    assert out.strip().splitlines()[-1] == str(sorted(
        [("noc.simulate", 1), ("noc.build", 1)]
        + [(k, 1) for k in CALL_SPANS]))
