"""The torus reference with dateline VCs (``bench/reference/torus_vc.py``)
decides ``correct`` for the torus cell: it agrees with the program
exactly on a small torus under ``xy(2)``, its control is rejected, on a
single-VC mesh it is ``mesh_axi``'s model result for result, and a
torus without VCs wedges alike in both."""
import json

import numpy as np
import pytest

from bench_helpers import REPO, tiny_root
from test_bench_control import SEEDS, fresh_sims  # noqa: F401

TORUS = json.loads((REPO / "bench/configs/torus_16x16.json").read_text())
A2A = json.loads((REPO / "bench/traffic/torus_16x16/a2a_vc.json").read_text())


def torus_root(tmp, *, kind="torus", n_vcs=2, cycles=2500, counts=(6, 4),
               wide_rate=0.5):
    """The tiny root with a cell ``torus4``: the torus configuration at
    4x4 (``kind`` and ``n_vcs`` as given) under the all-to-all mix, its
    offsets the 15 of a 4x4 fabric in the same spread order (a stride
    prime to 16 near 0.38 of it, as 97 is to 256)."""
    root = tiny_root(tmp)
    cfg = json.loads(json.dumps(TORUS))
    cfg.update(name="torus4", cycles=cycles)
    cfg["preset_args"].update(nx=4, ny=4, cycles=cycles)
    cfg["topology"].update(nx=4, ny=4, kind=kind, n_vcs=n_vcs)
    (root / "bench/configs/torus4.json").write_text(json.dumps(cfg))
    mix = dict(A2A, counts={"narrow": counts[0], "wide": counts[1]},
               rates=dict(A2A["rates"], wide=wide_rate),
               dest={"offsets": [7 * k % 16 for k in range(1, 16)]})
    (root / "bench/traffic/torus4").mkdir()
    (root / "bench/traffic/torus4/a2a_vc.json").write_text(json.dumps(mix))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "torus4", "source": "test",
                           "file": "bench/configs/torus4.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "torus4", "config": "torus4",
                             "traffic": "a2a_vc", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def _program_and_reference(root, name, seed, depth_offset=0):
    from bench import harness
    cell = harness.resolve(name, root)
    system = harness.System(cell, seed)
    assert system.mismatches == 0
    got = system.call(0)
    want = harness.reference_results(cell, seed, [(0, 0)],
                                      depth_offset=depth_offset)
    return got, want


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_agrees_on_a_torus_with_dateline_vcs(tmp_path, seed):
    from bench import harness
    root = torus_root(tmp_path)
    got, want = _program_and_reference(root, "torus4", seed)
    assert bool(got[0].drained) and bool(want[0]["drained"])
    # both VCs carry flits: the dateline path does work
    occ = want[0]["channels.wide.vc_occupancy"]
    assert occ.shape == (2,) and (occ > 0).all()
    assert harness.mismatched_values(got, want) == 0
    _, control = _program_and_reference(root, "torus4", seed, depth_offset=1)
    assert harness.mismatched_values(got, control) > 0


@pytest.mark.parametrize("cell", ["tiny", "torus4"])
def test_single_vc_mesh_is_the_mesh_axi_model(tmp_path, cell):
    """At ``n_vcs = 1`` on a 4x4 mesh the two references give identical
    flattened results: on three networks (the tiny copy of the 7x7
    configuration) and on the torus file's one wide network laid out as
    a mesh."""
    from bench import compare, harness
    c = harness.resolve(cell, torus_root(tmp_path, kind="mesh", n_vcs=1))
    picks = [(0, 0), (1, 0)]
    out = {}
    for ref in ("mesh_axi", "torus_vc"):
        c.config["reference"] = ref
        out[ref] = harness.reference_results(c, SEEDS[0], picks)
    for a, b in zip(out["torus_vc"], out["mesh_axi"]):
        assert set(a) == set(b)
        assert compare.differences(a, b) == {}


def test_torus_without_vcs_wedges_alike(tmp_path):
    """A 4x4 torus at ``n_vcs = 1`` under saturating bursts deadlocks on
    its rings: program and reference both read ``drained == False``, and
    agree field for field."""
    from bench import harness
    root = torus_root(tmp_path, n_vcs=1, cycles=1500, counts=(4, 8),
                      wide_rate=1.0)
    got, want = _program_and_reference(root, "torus4", SEEDS[0])
    assert not bool(got[0].drained) and not bool(want[0]["drained"])
    assert int(want[0]["max_stall_cycles"]) > 100
    assert harness.mismatched_values(got, want) == 0


def test_reference_is_independent_of_the_program():
    src = (REPO / "bench/reference/torus_vc.py").read_text()
    assert "repro" not in src and "jax" not in src
    assert "import mesh_axi" not in src      # a sibling, loaded by path


def test_reference_refuses_what_it_does_not_model():
    from bench import harness
    ref = harness._load_module(REPO / "bench/reference/torus_vc.py",
                               "test_torus_vc")
    z = np.zeros((1, 2, 256, 1), np.int64)
    for topo in ({"routing": "o1turn"}, {"express": [2]},
                 {"kind": "ring"}):
        cfg = json.loads(json.dumps(TORUS))
        cfg["topology"].update(topo)
        with pytest.raises(ValueError):
            ref.simulate(cfg, z + (1 << 30), z, z)


# --------------------------------------------------------------------- #
# the noc.vc_drain scope and its reader
# --------------------------------------------------------------------- #
def test_vc_drain_metric_reads_its_scope(tmp_path, monkeypatch):
    """``vc_drain_us_per_cycle`` reads the ``noc.fabric/noc.vc_drain``
    scope from a traced run's reduction (a synthetic trace: the CPU's
    has no device plane), and nothing without a trace or without the
    scope."""
    from bench import harness, phases
    from bench_helpers import run_tiny
    from test_bench_phases import BODY, synthetic
    root = tiny_root(tmp_path)
    body = BODY + [("fusion.v", 500, "noc.fabric/noc.vc_drain")]
    monkeypatch.setattr(phases, "load", lambda path, hlo=None, sample=0: (
        synthetic(body=body), []))
    line = run_tiny(root, seconds=0.3, trace=True)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["vc_drain_us_per_cycle"] == pytest.approx(0.5)
    assert got["fabric_us_per_cycle"] == pytest.approx(2.5)
    reader = harness.resolve("tiny", root).reader("vc_drain_us_per_cycle")
    assert reader.read({"trace": None}) is None
    monkeypatch.setattr(phases, "load", lambda path, hlo=None, sample=0: (
        synthetic(), []))
    line = run_tiny(root, seconds=0.3, trace=True)
    assert "vc_drain_us_per_cycle" not in line["metrics"]
    assert "fabric_us_per_cycle" in line["metrics"]


def _lowered(spec):
    """The simulator program of ``spec`` as the benchmark's trace reads
    it, lowered from the shapes of a short run."""
    import jax
    from repro.noc import Workload, engine, sim_cache_clear, simulate
    sim_cache_clear()
    simulate(spec, Workload.make("uniform_random", counts={"narrow": 2,
                                                        "wide": 2}))
    [((_, _, avals), prog)] = engine._programs.items()
    return prog.lower(*[jax.ShapeDtypeStruct(s, d) for s, d in avals])


@pytest.mark.parametrize("fabric", ["mesh_7x7", "torus_4x4_xy2"])
def test_vc_drain_scope_is_metadata_only(monkeypatch, fresh_sims, fabric):
    """The scope changes no op: the lowered program is the same with the
    scope left out.  The 7x7 mesh (one VC) never enters it; the VC
    torus carries it in its metadata."""
    import contextlib

    import jax
    from repro.noc import NocSpec, RoutingPolicy, Torus
    spec = (NocSpec.narrow_wide(7, 7, cycles=200) if fabric == "mesh_7x7"
            else NocSpec.wide_only(4, 4, cycles=200, topology=Torus(4, 4),
                                   routing=RoutingPolicy.xy(2)))
    scoped = _lowered(spec)
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope", lambda name: (
        contextlib.nullcontext() if name == "noc.vc_drain" else real(name)))
    plain = _lowered(spec)
    assert scoped.as_text() == plain.as_text()
    meta = scoped.as_text(debug_info=True)
    assert ("noc.vc_drain" in meta) == (fabric != "mesh_7x7")
