"""Configuration files state their fabric (topology kind, express links,
routing algorithm and VCs, per-class streams and service jitter): the
harness builds what a file states and counts every departure, or
refuses the file.  It never builds another fabric that passes the
check."""
import dataclasses
import json
import shutil

import pytest

from bench_helpers import REPO, tiny_root

MAN = json.loads((REPO / "BENCHMARK.json").read_text())


def _torus_vc(cfg):
    cfg["topology"].update(kind="torus", n_vcs=2)


def _wide_streams(cfg):
    cfg["classes"][1]["n_streams"] = 2


def add_config(root, name, edit):
    """A configuration ``name`` (the tiny one, changed by ``edit``) and a
    cell ``name`` of the tiny mix on it, as new files and manifest
    entries only; returns the resolved cell."""
    from bench import harness
    cfg = json.loads((root / "bench/configs/tiny.json").read_text())
    cfg["name"] = name
    edit(cfg)
    (root / f"bench/configs/{name}.json").write_text(json.dumps(cfg))
    (root / f"bench/traffic/{name}").mkdir()
    shutil.copy(root / "bench/traffic/tiny/uniform_rw.json",
                root / f"bench/traffic/{name}/uniform_rw.json")
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": name, "source": "test",
                           "file": f"bench/configs/{name}.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": name, "config": name,
                             "traffic": "uniform_rw", "chips": 1,
                             "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return harness.resolve(name, root)


def _expected(name, args):
    from repro.noc import NocSpec, RoutingPolicy, Torus
    if name == "torus_vc":
        return NocSpec.narrow_wide(**args, topology=Torus(4, 4),
                                   routing=RoutingPolicy.xy(2))
    spec = NocSpec.narrow_wide(**args)
    narrow, wide = spec.classes
    return spec.with_(classes=(narrow,
                               dataclasses.replace(wide, n_streams=2)))


@pytest.mark.parametrize("name,edit", [("torus_vc", _torus_vc),
                                       ("mesh_streams", _wide_streams)])
def test_stated_fabric_is_built_and_checked(tmp_path, name, edit):
    """A torus under dateline VCs, and a mesh whose wide class has two
    AXI ID streams, each from new files only: built as stated, with no
    fact departing."""
    from bench import harness
    cell = add_config(tiny_root(tmp_path), name, edit)
    system = harness.System(cell, 2 ** 31 + 11)
    assert system.mismatches == 0
    assert system.specs[0] == _expected(name, cell.config["preset_args"])


@pytest.mark.parametrize("base,fact,flip", [
    (_torus_vc, "kind", lambda c: c["topology"].update(kind="mesh")),
    (_torus_vc, "n_vcs", lambda c: c["topology"].update(n_vcs=1)),
    (_torus_vc, "algorithm",
     lambda c: c["topology"].update(routing="o1turn")),
    (_wide_streams, "streams",
     lambda c: c["classes"][1].update(n_streams=1)),
    (_wide_streams, "jitter",
     lambda c: c["classes"][1].update(service_jitter=3)),
    (_wide_streams, "express",
     lambda c: c["topology"].update(express=[2])),
])
def test_each_stated_fact_flipped_is_a_mismatch(base, fact, flip):
    from bench import harness
    cfg = json.loads((REPO / "bench/configs/floonoc_7x7.json").read_text())
    cfg["preset_args"].update(nx=4, ny=4)
    cfg["topology"].update(nx=4, ny=4)
    base(cfg)
    spec = harness.build_spec(cfg)
    assert harness.spec_mismatches(spec, cfg) == 0
    flip(cfg)
    assert harness.spec_mismatches(spec, cfg) >= 1, fact


@pytest.mark.parametrize("edit", [
    lambda c: c["topology"].update(kind="hypercube"),
    lambda c: c["topology"].pop("kind"),
    lambda c: c["topology"].update(routing="west_first"),
    lambda c: c["topology"].update(n_valiant=4),
    lambda c: c["topology"].update(wrap=True),
    lambda c: c["classes"][0].update(qos=2),
    lambda c: c.update(faults={"dead_links": [[0, 1]]}),
], ids=["kind", "no_kind", "routing", "n_valiant_without_valiant",
        "topology_key", "class_key", "faults"])
def test_a_fabric_the_harness_cannot_build_is_refused(tmp_path, edit):
    with pytest.raises(ValueError, match="bench/configs/odd.json"):
        add_config(tiny_root(tmp_path), "odd", edit)


def test_a_fabric_the_program_refuses_names_the_file(tmp_path):
    from bench import harness
    cell = add_config(tiny_root(tmp_path), "valiant_torus",
                      lambda c: c["topology"].update(
                          kind="torus", routing="valiant", n_vcs=4))
    with pytest.raises(ValueError, match="valiant_torus.json"):
        harness.System(cell, 1)


@pytest.mark.parametrize("cell", [w["name"] for w in MAN["workloads"]])
def test_committed_cells_build_the_presets_spec(cell):
    """The committed configurations state no topology, routing, stream
    or jitter beyond the defaults: every point builds the preset's own
    spec, field for field, with no fact departing."""
    from bench import harness
    from repro.noc import NocSpec
    system = harness.System(harness.resolve(cell, REPO), 1)
    assert system.mismatches == 0
    for cfg, spec in zip(system.configs, system.specs):
        want = getattr(NocSpec, cfg["preset"])(**cfg["preset_args"])
        for f in dataclasses.fields(NocSpec):
            assert getattr(spec, f.name) == getattr(want, f.name), f.name
