"""The phase and host-span split of a traced window, on a small
synthetic trace whose numbers are known."""
import pytest

import bench_helpers  # noqa: F401  (puts the repository on sys.path)
from bench import phases, tracing

BODY = [("fusion.a", 3_000, "noc.ni_issue"), ("fusion.b", 2_000,
                                              "noc.fabric"),
        ("copy.c", 1_000, None)]
MS = 1_000_000


def loop(t, iters, body=BODY):
    ev = []
    for _ in range(iters):
        for name, dur, phase in body:
            ev.append((name, t, t + dur, phase))
            t += dur + 1_000
    return ev, t


def span(name, start, end, call):
    return (name, start, end, {"call": call})


def synthetic(a=30, b=20, devices=1, body=BODY, spans=True):
    """``a`` loop iterations of one call, a 2 ms boundary, ``b`` of the
    next, per device.  Each iteration: a 3 us ``noc.ni_issue`` op, a
    2 us ``noc.fabric`` op and a 1 us op with no scope, each followed
    by 1 us idle.  On the host, in the boundary (from its start): 0.1
    ms in call 1's wait (it and call 1's parent began before the window),
    call 1's ``noc.results`` to 0.5 ms, the bench's markers, then call
    2: its parent from 0.6 ms, ``noc.verify`` 0.65-0.7,
    ``noc.schedules`` 0.7-1.2, ``noc.dispatch`` 1.2-1.9 and
    ``noc.wait`` from 1.9 ms into the next loop."""
    planes, t0 = [], 1 * MS
    for d in range(devices):
        ev, t = loop(t0, a, body)
        end_a = t - 1_000
        ev.append(("init.1", end_a + 1_950_000, end_a + 1_960_000, None))
        more, t_end = loop(end_a + 2 * MS, b, body)
        planes.append({"name": f"/device:TPU:{d}",
                       "lines": [{"name": "XLA Ops", "events": ev + more}]})
    host = [(tracing.END, end_a + 500_000, end_a + 500_000, {}),
            (tracing.START, end_a + 600_000, end_a + 600_000, {})]
    if spans:
        host += [
            span("noc.simulate", t0 - 5 * MS, end_a + 500_000, 1),
            span("noc.wait", t0 - 4 * MS, end_a + 100_000, 1),
            span("noc.results", end_a + 100_000, end_a + 500_000, 1),
            span("noc.simulate", end_a + 600_000, t_end, 2),
            span("noc.verify", end_a + 650_000, end_a + 700_000, 2),
            span("noc.schedules", end_a + 700_000, end_a + 1_200_000, 2),
            span("noc.dispatch", end_a + 1_200_000, end_a + 1_900_000, 2),
            span("noc.wait", end_a + 1_900_000, t_end, 2)]
    planes.append({"name": "/host:CPU",
                   "lines": [{"name": "python", "events": host}]})
    return planes


# the body with a scope nested in a phase: a 0.5 us op under
# noc.fabric/noc.vc_arb
NESTED = BODY + [("fusion.v", 500, "noc.fabric/noc.vc_arb")]


def nested(flat=False, plan=True):
    """The trace of :func:`synthetic` on ``NESTED`` (each op only its
    outermost phase if ``flat``), with a new host span ``noc.plan`` of
    call 2 from 0.6 to 0.65 ms into the boundary if ``plan``."""
    planes = synthetic(body=NESTED)
    if flat:
        for p in planes[:-1]:
            p["lines"][0]["events"] = [
                (n, s, e, phases.phase_of(sc))
                for n, s, e, sc in p["lines"][0]["events"]]
    host = planes[-1]["lines"][0]["events"]
    end_a = next(s for n, s, _, _ in host if n == tracing.END) - 500_000
    if plan:
        host.append(span("noc.plan", end_a + 600_000, end_a + 650_000, 2))
    return planes


def test_phases_and_rest_add_up_to_busy():
    out = phases.reduce_phases(synthetic(), points=1)
    ph = out["phase_s_per_iteration"]
    assert ph["noc.ni_issue"] == pytest.approx(3e-6)
    assert ph["noc.fabric"] == pytest.approx(2e-6)
    assert ph["noc.ni_rings"] == ph["noc.stats"] == 0
    assert out["unscoped_s_per_iteration"] == pytest.approx(1e-6)
    assert sum(ph.values()) + out["unscoped_s_per_iteration"] == \
        pytest.approx(out["busy_s_per_iteration"])
    # the same busy time as the existing reduction, on the same trace
    old = tracing.reduce(phases.plain(synthetic()), points=1)
    assert out["busy_s_per_iteration"] == pytest.approx(
        old["busy_s_per_iteration"])


def test_unscoped_ops_are_the_rest_and_the_breakdown_names_phases():
    out = phases.reduce_phases(synthetic(), points=1)
    assert [n for n, _ in out["unscoped_ops"]] == ["copy.c"]
    assert out["unscoped_ops"][0][1] == pytest.approx(1e-6)
    names = [n for n, _ in out["device_ops"]]
    assert names == ["noc.ni_issue fusion.a", "noc.fabric fusion.b",
                     "unscoped copy.c"]
    assert [op[:2] for op in out["loop_ops"]] == [
        ["fusion.a", "noc.ni_issue"], ["fusion.b", "noc.fabric"],
        ["copy.c", "unscoped"]]


def test_per_point_and_over_devices():
    out = phases.reduce_phases(synthetic(a=25, b=15, devices=4), points=4)
    assert out["devices"] == 4
    res = {"phases": out, "tracing": tracing.reduce(
        phases.plain(synthetic(a=25, b=15, devices=4)), points=4),
        "setup_spans": {}, "cell": "x", "host": None}
    got = phases.summary(res)
    assert got["ni_issue_us_per_cycle"] == pytest.approx(3.0 / 4)
    assert got["fabric_us_per_cycle"] == pytest.approx(2.0 / 4)
    assert got["unscoped_us_per_cycle"] == pytest.approx(1.0 / 4)
    assert got["device_us_per_cycle"] == pytest.approx(6.0 / 4)


@pytest.mark.parametrize("op_name, phase", [
    ("jit(run)/while/body/closed_call/noc.ni_rings/scatter",
     "noc.ni_rings"),
    ("jit(run)/shard_map/while/body/closed_call/noc.fabric/fabric_front/"
     "farm.halo/ppermute", "noc.fabric"),
    ("jit(run)/while/body/noc.stats/farm.halo/noc.fabric/x", "noc.stats"),
    ("jit(run)/while/body/closed_call/add", None),
    ("", None),
])
def test_outermost_phase_wins(op_name, phase):
    assert phases.phase_of(op_name) == phase
    assert phases.phase_of(phases.scopes_of_stats({"tf_op": op_name})) \
        == phase


def test_a_scope_under_the_halo_counts_toward_the_fabric():
    halo = [("collective-permute-done.1", 1_000, "noc.fabric"),
            ("fusion.a", 3_000, "noc.ni_issue")]
    planes = synthetic(body=halo)
    ops = planes[0]["lines"][0]["events"]
    name = "jit(run)/shard_map/noc.fabric/fabric_front/farm.halo/ppermute"
    planes[0]["lines"][0]["events"] = [
        (n, s, e, phases.phase_of(name) if n.startswith("coll") else p)
        for n, s, e, p in ops]
    out = phases.reduce_phases(planes, points=1)
    assert out["phase_s_per_iteration"]["noc.fabric"] == pytest.approx(1e-6)


HLO = """HloModule jit_run

%fused_computation.7 (p: s32[4]) -> s32[4] {
  %p = s32[4]{0} parameter(0)
  ROOT %scatter.3 = s32[4]{0} scatter(%p), metadata={op_name="jit(run)/while/body/closed_call/noc.ni_rings/scatter"}
}

%body (x: s32[4]) -> s32[4] {
  %x = s32[4]{0} parameter(0)
  %fusion.9 = s32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.7
  %add.2 = s32[4]{0} add(%fusion.9, %x), metadata={op_name="jit(run)/while/body/closed_call/noc.stats/add"}
  ROOT %copy.4 = s32[4]{0} copy(%add.2)
}

%fused_computation.8 (q: s32[4]) -> s32[8] {
  %q = s32[4]{0} parameter(0)
  %reshape.5 = s32[4]{0} reshape(%q), metadata={op_name="jit(run)/while/body/closed_call/noc.ni_rings/concatenate"}
  ROOT %scatter.6 = s32[8]{0} scatter(%q, %reshape.5), to_apply=%region_1
}

%fusion.10 = s32[8]{0} fusion(%x), kind=kCustom, calls=%fused_computation.8
"""


def test_hlo_fallback_gives_a_fusion_its_roots_phase():
    got = phases.hlo_scopes(HLO)
    assert got["fusion.9"] == "noc.ni_rings"
    assert got["scatter.3"] == "noc.ni_rings"
    assert got["add.2"] == "noc.stats"
    assert "copy.4" not in got
    # a root the compiler left without metadata: the fusion's last op
    # with a phase
    assert got["fusion.10"] == "noc.ni_rings"
    assert phases.op_key("%fusion.9 = s32[4]{0} fusion(s32[4]{0} %x), "
                         "kind=kLoop") == "fusion.9"


def test_boundary_splits_by_span_and_call_totals_unchanged():
    planes = synthetic()
    host = phases.reduce_host(planes)
    parts = dict(host["host_parts"])
    assert parts == pytest.approx({
        "host finishing a call (noc.wait)": 0.1e-3,
        "host finishing a call (noc.results)": 0.4e-3,
        "host between calls (no program span)": 0.1e-3,
        "host starting a call (noc.simulate)": 0.05e-3,
        "host starting a call (noc.verify)": 0.05e-3,
        "host starting a call (noc.schedules)": 0.5e-3,
        "host starting a call (noc.dispatch)": 0.7e-3,
        "host starting a call (noc.wait)": 0.1e-3})
    # the next call's init op, 10 us of the boundary
    assert host["device_busy_s_in_boundary"] == pytest.approx(10e-6)
    assert host["host_prepare_s_per_experiment"] == pytest.approx(1.25e-3)
    assert host["host_results_s_per_experiment"] == pytest.approx(0.4e-3)
    assert sum(parts.values()) == pytest.approx(
        host["host_s_per_experiment"])
    # the existing reduction reads the same boundary and the same parts
    old = tracing.reduce(phases.plain(planes), points=1)
    assert old["host_s_per_experiment"] == pytest.approx(
        host["host_s_per_experiment"]) == pytest.approx(2e-3)
    assert old == tracing.reduce(phases.plain(synthetic(spans=False)),
                                 points=1)


def test_a_span_begun_before_the_window_is_left_out():
    planes = synthetic()
    first_op = planes[0]["lines"][0]["events"][0][1]
    spans = phases.host_spans(planes, since=first_op)
    assert ("noc.wait", 1) not in {(s[0], s[3]) for s in spans}
    assert ("noc.results", 1) in {(s[0], s[3]) for s in spans}
    assert len(phases.host_spans(planes)) == len(spans) + 2


def test_one_call_id_falls_back_to_the_span_names():
    b0, b1 = 0, 10
    spans = [("noc.results", 0, 4, None), ("noc.dispatch", 6, 10, None)]
    got = phases.boundary_split((b0, b1), spans)
    assert got["parts"] == {"host finishing a call (noc.results)": 4,
                            "host between calls (no program span)": 2,
                            "host starting a call (noc.dispatch)": 4}
    assert got["prepare"] == 4 and got["results"] == 4


def test_nothing_to_read_without_scopes_or_spans():
    bare = phases.plain(synthetic(spans=False))      # 3-tuples reduce
    out = phases.reduce_phases(bare, points=1)
    assert out["phase_s_per_iteration"] is None
    assert out["unscoped_s_per_iteration"] == pytest.approx(
        out["busy_s_per_iteration"])
    assert phases.reduce_host(bare) is None
    got = phases.summary({"cell": "x", "setup_spans": {}, "phases": out,
                          "tracing": tracing.reduce(bare, points=1),
                          "host": None})
    assert got == {"cell": "x", "setup_build_s": None}
    host_only = [{"name": "/host:CPU", "lines": [{"name": "python",
                                                  "events": []}]}]
    assert phases.reduce_phases(host_only, points=1) is None
    assert phases.reduce_host(host_only) is None


def test_a_nested_scope_and_a_new_span_are_keys():
    """A scope nested in a phase and a host span the reduction does not
    name each get an entry of their own, and every number that was
    there before reads as on the trace without them."""
    out = phases.reduce_phases(nested(), points=1)
    assert out["scope_s_per_iteration"] == pytest.approx({
        "noc.ni_issue": 3e-6, "noc.fabric": 2.5e-6, "noc.vc_arb": 0.5e-6})
    flat = phases.reduce_phases(nested(flat=True), points=1)
    assert out["phase_s_per_iteration"] == flat["phase_s_per_iteration"]
    assert out["phase_s_per_iteration"]["noc.fabric"] == pytest.approx(
        2.5e-6)
    assert "noc.vc_arb" not in flat["scope_s_per_iteration"]
    old = tracing.reduce(phases.plain(nested(flat=True, plan=False)),
                         points=1)
    assert tracing.reduce(phases.plain(nested()), points=1) == old
    host = phases.reduce_host(nested())
    before = phases.reduce_host(nested(plan=False))
    assert host["span_s_per_experiment"]["noc.plan"] == pytest.approx(
        0.05e-3)
    assert "noc.plan" not in before["span_s_per_experiment"]
    for k in ("host_s_per_experiment", "host_prepare_s_per_experiment",
              "host_results_s_per_experiment"):
        assert host[k] == before[k], k
    # the traced run's reduction: reduce's own numbers, the splits beside
    merged = tracing.reduce_split(nested(), points=1)
    assert {k: merged[k] for k in old} == old
    assert merged["scope_s_per_iteration"] == out["scope_s_per_iteration"]
    assert merged["host_prepare_s_per_experiment"] == pytest.approx(1.25e-3)


def test_span_totals_count_a_nested_span_in_its_parents():
    planes = nested()
    got = phases.reduce_host(planes)["span_s_per_experiment"]
    # call 1's noc.simulate and noc.wait began before the window
    assert got == pytest.approx({
        "noc.results": 0.4e-3, "noc.simulate": 1.4e-3, "noc.plan": 0.05e-3,
        "noc.verify": 0.05e-3, "noc.schedules": 0.5e-3,
        "noc.dispatch": 0.7e-3, "noc.wait": 0.1e-3})


@pytest.mark.parametrize("op_name, path", [
    ("jit(run)/while/body/noc.fabric/fabric_update/noc.vc_arb/select",
     "noc.fabric/noc.vc_arb"),
    ("jit(run)/while/body/noc.stats/farm.halo/noc.fabric/x",
     "noc.stats/noc.fabric"),
    ("jit(run)/while/body/closed_call/add", None),
])
def test_scope_path_keeps_every_program_scope(op_name, path):
    assert phases.scopes_of(op_name) == path
    assert phases.scopes_of_stats({"long_name": op_name}) == path
    assert phases.phase_of(path) == phases.phase_of(op_name)
