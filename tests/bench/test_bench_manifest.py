"""BENCHMARK.json against its contract, and every name in it resolving
to its files."""
import json
import re
import time

import numpy as np
import pytest

from bench_helpers import CPU, REPO, run_tiny, tiny_root

MAN = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in MAN["workloads"]]


def test_manifest_keys_and_names():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51
    for p in MAN["paths"]:
        assert (REPO / p).is_dir() and not p.startswith("/") and ".." not in p
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                  "per_layer") for x in MAN[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) <= max(
        1, len(CELLS) // 2)
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    moves = {m["name"] for m in MAN["end_to_end"]}
    assert all(m["moves"] in moves for m in MAN["per_layer"])


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    from bench import harness
    c = harness.resolve(cell, REPO)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    # each per-layer metric's cells report the metric it moves
    for m in c.per_layer:
        assert m["moves"] in e2e


@pytest.mark.parametrize("cell", CELLS)
def test_names_resolve_to_files(cell):
    from bench import harness
    c = harness.resolve(cell, REPO)
    assert c.config["name"] == next(
        w["config"] for w in MAN["workloads"] if w["name"] == cell)
    assert c.mix["call"] in ("simulate", "sweep")
    assert hasattr(c.reference(), "simulate")
    for m in c.end_to_end + c.per_layer:
        assert callable(c.reader(m["name"]).read)
    # the layout the cell's chips select exists in its configuration
    assert str(c.chips) in c.config["layout"]


def test_config_files_state_their_cut():
    for entry in MAN["configs"]:
        cfg = json.loads((REPO / entry["file"]).read_text())
        assert cfg["name"] == entry["name"]
        assert cfg["source"] == entry["source"]
        assert cfg["reduced"] == entry["reduced"]
        assert "assumed" in cfg and "guarantees" in cfg
        assert set(cfg["layout"]) <= {"1", "4"}


def test_unknown_cell_is_refused():
    from bench import harness
    with pytest.raises(KeyError):
        harness.resolve("no_such_cell", REPO)


def test_cell_from_new_files_only(tmp_path):
    """A configuration, a mix and a per-layer metric reader added as new
    files plus manifest entries are found and run, with no file that was
    there edited."""
    root = tiny_root(tmp_path)
    before = {p: p.read_bytes() for p in (REPO / "bench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    (root / "bench/metrics/calls_in_window.py").write_text(
        "def read(run):\n    return float(len(run['calls']))\n")
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["end_to_end"].append({
        "name": "calls_in_window", "unit": "calls", "better": "higher",
        "bound": 0.25, "source": "host_clock", "workloads": ["tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    # a 4x4 call takes 0.1-0.4 s on the CPU, loaded or not: the window
    # holds the two calls experiment_s_p90 needs with room to spare
    line = run_tiny(root, seconds=3.0)
    assert line["correct"] is True
    assert line["metrics"]["calls_in_window"]["value"] == line["attempted"]
    assert set(line["metrics"]) == {"router_cycles_per_s", "setup_s",
                                    "experiment_s_p90", "calls_in_window"}
    for p, data in before.items():            # the repo's files untouched
        assert p.read_bytes() == data


def test_metric_of_a_new_scope_from_new_files_only(tmp_path, monkeypatch):
    """A metric reader added as a new file reads a scope the program
    nests in a phase, and a new host span, from the traced run's
    reduction, with no file that was there edited.  The CPU's trace has
    no device plane, so the traced run is handed a synthetic one."""
    from bench import phases
    from test_bench_phases import nested
    root = tiny_root(tmp_path)
    before = {p: p.read_bytes() for p in (REPO / "bench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    (root / "bench/metrics/vc_arb_us_per_cycle.py").write_text(
        "def read(run):\n"
        "    tr = run['trace']\n"
        "    s = (tr or {}).get('scope_s_per_iteration', {}).get("
        "'noc.vc_arb')\n"
        "    return None if s is None else 1e6 * s / tr['points']\n")
    (root / "bench/metrics/plan_ms_per_experiment.py").write_text(
        "def read(run):\n"
        "    tr = run['trace'] or {}\n"
        "    s = tr.get('span_s_per_experiment', {}).get('noc.plan')\n"
        "    return None if s is None else 1e3 * s\n")
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["per_layer"] += [
        {"name": "vc_arb_us_per_cycle", "unit": "us", "better": "lower",
         "source": "device_trace", "layer": "engine scan step",
         "moves": "router_cycles_per_s", "workloads": ["tiny"]},
        {"name": "plan_ms_per_experiment", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "API",
         "moves": "experiment_s_p90", "workloads": ["tiny"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    monkeypatch.setattr(phases, "load",
                        lambda path, hlo=None, sample=0: (nested(), []))
    line = run_tiny(root, seconds=0.3, trace=True)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["vc_arb_us_per_cycle"] == pytest.approx(0.5)
    assert got["plan_ms_per_experiment"] == pytest.approx(0.05)
    split = {f"{k}_us_per_cycle": v for k, v in [
        ("ni_issue", 3.0), ("fabric", 2.5), ("ni_rings", 0.0),
        ("stats", 0.0), ("unscoped", 1.0)]}
    assert {k: got[k] for k in split} == pytest.approx(split)
    assert got["device_us_per_cycle"] == pytest.approx(6.5)
    assert got["host_prepare_ms_per_experiment"] == pytest.approx(1.25)
    assert got["host_results_ms_per_experiment"] == pytest.approx(0.4)
    assert got["host_ms_per_experiment"] == pytest.approx(2.0)
    assert got["setup_build_s"] > 0
    assert line["device"]["busy_s"] > 0 and "breakdown" in line
    for p, data in before.items():            # the repo's files untouched
        assert p.read_bytes() == data


@pytest.mark.parametrize("traffic,pattern", [
    ("bidir_rw", {"sources": [0, 15], "dest": {"pairs": {"0": 15, "15": 0}}}),
    ("hotspot_rw", {"dest": {"nodes": {"5": 0.5}}}),
    ("a2a_rw", {"dest": {"offsets": list(range(1, 16))}})])
def test_traffic_pattern_from_a_new_mix_file_only(tmp_path, traffic,
                                                  pattern):
    """Another traffic pattern is a new mix file plus a manifest entry:
    the harness drives it through the program and the reference agrees,
    with no file that was there edited."""
    from bench import harness
    root = tiny_root(tmp_path)
    before = {p: p.read_bytes() for p in (REPO / "bench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    mix = json.loads((root / "bench/traffic/tiny/uniform_rw.json")
                     .read_text())
    (root / f"bench/traffic/tiny/{traffic}.json").write_text(
        json.dumps({**mix, **pattern}))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["workloads"].append({"name": f"tiny.{traffic}", "config": "tiny",
                             "traffic": traffic, "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    cell = harness.resolve(f"tiny.{traffic}", root)
    assert cell.mix["dest"] == pattern["dest"]
    # the program is given the pattern, not the uniform default
    wide = harness.System(cell, 5).call(0)[0].classes["wide"]
    sent = wide.done + wide.w_done
    if "sources" in pattern:
        assert set(np.flatnonzero(sent)) == {0, 15}
    else:
        assert np.all(sent > 0)
    line = harness.run_cell(cell, 2 ** 31 + 901, 0.3, False,
                            time.perf_counter(), dict(CPU))
    assert line["correct"] is True
    assert line["checks"]["checked_points"]["value"] >= 1
    for p, data in before.items():
        assert p.read_bytes() == data
