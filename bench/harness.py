"""The benchmark harness: finds a cell's files by the names in
``BENCHMARK.json``, drives the program through its public entry, and
assembles the result line.

Everything that belongs to one configuration, traffic mix or metric is
a file of its own, found by name:

* ``BENCHMARK.json``'s ``configs[].file`` — the configuration;
* ``bench/traffic/<config>/<traffic>.json`` — the traffic mix, read by
  the one generator in ``bench/traffic.py``;
* ``bench/metrics/<metric>.py`` — one reader per metric, ``read(run)``
  returning a number, or ``None`` where it finds nothing to read;
* ``bench/reference/<config["reference"]>.py`` — the plain reference
  that decides ``correct``.

So a later change adds a cell, a configuration or a metric by adding
files and manifest entries, never by editing a file that is here.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import compare, traffic

ROOT = Path(__file__).resolve().parents[1]


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``workloads`` with every file it names, resolved."""
    name: str
    chips: int
    config: dict
    mix: dict
    layout: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    root: Path
    config_file: str = ""
    readers: dict = field(default_factory=dict)

    def reader(self, metric: str):
        if metric not in self.readers:
            self.readers[metric] = _load_module(
                self.root / "bench" / "metrics" / f"{metric}.py",
                f"bench_metric_{metric.replace('.', '_')}")
        return self.readers[metric]

    def reference(self):
        return _load_module(
            self.root / "bench" / "reference"
            / f"{self.config['reference']}.py",
            f"bench_reference_{self.config['reference']}")


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell called ``name`` with its configuration, mix, layout and
    the metrics it reports; raises ``KeyError``/``FileNotFoundError``
    for a name or file that does not exist, and ``ValueError`` (naming
    the file) for a configuration that states a fabric the harness
    cannot build (:func:`stated`)."""
    man = load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in man["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    try:
        stated(config)
    except ValueError as e:
        raise ValueError(f"{cfg_entry['file']}: {e}") from None
    mix = json.loads((root / "bench" / "traffic" / w["config"]
                      / f"{w['traffic']}.json").read_text())
    layout = config["layout"][str(w["chips"])]
    return Cell(name=name, chips=w["chips"], config=config, mix=mix,
                layout=layout,
                end_to_end=[m for m in man["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in man["per_layer"] if _applies(m, name)],
                root=root, config_file=cfg_entry["file"])


# --------------------------------------------------------------------- #
# the system under test
# --------------------------------------------------------------------- #
def _point_config(config: dict, spec_kw: dict) -> dict:
    """The configuration as one point of a call states it (a sweep's
    ``spec.depth`` sets every network's FIFO depth)."""
    cfg = json.loads(json.dumps(config))
    cfg["preset_args"].update(spec_kw)
    if "cycles" in spec_kw:
        cfg["cycles"] = spec_kw["cycles"]
    if "depth" in spec_kw:
        for ch in cfg["channels"]:
            ch["depth"] = spec_kw["depth"]
    return cfg


# what a configuration file may state about its fabric, and the value
# of each key a file leaves out
TOPOLOGY_DEFAULTS = {"express": [], "routing": "xy", "n_vcs": 1}
CLASS_DEFAULTS = {"service_jitter": 0, "n_streams": 1}
KINDS = ("mesh", "torus")
# routing algorithm -> its route planes; ``valiant`` has ``n_valiant``
PLANES = {"xy": 1, "o1turn": 2}
ALGORITHMS = (*PLANES, "valiant")
VALIANT_PLANES = 2
TOPOLOGY_KEYS = {"kind", "nx", "ny", "n_valiant", *TOPOLOGY_DEFAULTS}
CLASS_KEYS = {"name", "burst_beats", "max_outstanding", "payload_bits",
              *CLASS_DEFAULTS}


def stated(cfg: dict) -> dict:
    """The fabric a configuration states, every default filled in:
    ``kind``, ``express`` (sorted), ``routing`` as ``(algorithm, n_vcs,
    n_planes)``, ``n_valiant``, and ``classes`` as ``{name:
    {service_jitter, n_streams}}``.  Raises ``ValueError`` for a kind,
    algorithm or key the harness cannot build, and for a fault model,
    which it never builds, so that no file is built as a fabric other
    than the one it states."""
    topo = cfg["topology"]
    unknown = set(topo) - TOPOLOGY_KEYS
    unknown |= {k for c in cfg["classes"] for k in c if k not in CLASS_KEYS}
    if unknown:
        raise ValueError(f"keys the harness cannot build: {sorted(unknown)}")
    if "faults" in cfg:
        raise ValueError("fault configurations are not built by the "
                         "harness")
    if topo.get("kind") not in KINDS:
        raise ValueError(f"topology kind {topo.get('kind')!r}; the harness "
                         f"builds {list(KINDS)}")
    top = {**TOPOLOGY_DEFAULTS, **topo}
    algorithm = top["routing"]
    if algorithm not in ALGORITHMS:
        raise ValueError(f"routing {algorithm!r}; the harness builds "
                         f"{list(ALGORITHMS)}")
    if "n_valiant" in topo and algorithm != "valiant":
        raise ValueError("n_valiant is stated only with routing 'valiant'")
    n_valiant = topo.get("n_valiant", VALIANT_PLANES)
    planes = n_valiant if algorithm == "valiant" else PLANES[algorithm]
    return {"kind": top["kind"], "express": sorted(top["express"]),
            "routing": (algorithm, top["n_vcs"], planes),
            "n_valiant": n_valiant,
            "classes": {c["name"]: {k: c.get(k, v)
                                    for k, v in CLASS_DEFAULTS.items()}
                        for c in cfg["classes"]}}


def build_spec(cfg: dict):
    """The configuration's preset called with its ``preset_args`` and
    the topology and routing the file states, each class given the
    streams and service jitter it states."""
    from dataclasses import replace
    from repro.noc import Mesh, NocSpec, RoutingPolicy, Torus
    fab = stated(cfg)
    args = cfg["preset_args"]
    topology = (Torus(args["nx"], args["ny"]) if fab["kind"] == "torus"
                else Mesh(args["nx"], args["ny"],
                          express=tuple(fab["express"])))
    algorithm, n_vcs, _ = fab["routing"]
    spec = getattr(NocSpec, cfg["preset"])(
        **args, topology=topology,
        routing=RoutingPolicy(algorithm, n_vcs, fab["n_valiant"]))
    return spec.with_(classes=tuple(
        replace(c, **fab["classes"][c.name]) if c.name in fab["classes"]
        else c for c in spec.classes))


def spec_mismatches(spec, cfg: dict) -> int:
    """How many of the facts the configuration states the program's
    spec departs from (topology and its kind and express links,
    horizon, networks, classes with their jitter and streams, flow map,
    service latency, queue capacity, routing algorithm with its VCs and
    planes); a fault model always departs."""
    fab = stated(cfg)
    stated_facts = {
        "topology": (cfg["topology"]["nx"], cfg["topology"]["ny"]),
        "kind": fab["kind"],
        "express": fab["express"],
        "cycles": cfg["cycles"],
        "service_lat": cfg["service_lat"],
        "resp_q_cap": cfg["resp_q_cap"],
        "channels": [(c["name"], c["depth"], c["width_bits"])
                     for c in cfg["channels"]],
        "classes": [(c["name"], c["burst_beats"], c["max_outstanding"],
                     c["payload_bits"],
                     fab["classes"][c["name"]]["service_jitter"],
                     fab["classes"][c["name"]]["n_streams"])
                    for c in cfg["classes"]],
        "class_map": sorted(cfg["class_map"].items()),
        "routing": fab["routing"],
    }
    got = {
        "topology": (spec.topology.nx, spec.topology.ny),
        "kind": type(spec.topology).__name__.lower(),
        "express": sorted(getattr(spec.topology, "express", ())),
        "cycles": spec.cycles,
        "service_lat": spec.service_lat,
        "resp_q_cap": spec.resp_q_cap,
        "channels": [(c.name, c.depth, c.width_bits) for c in spec.channels],
        "classes": [(c.name, c.burst_beats, c.max_outstanding,
                     c.payload_bits, c.service_jitter, c.n_streams)
                    for c in spec.classes],
        "class_map": sorted(spec.class_map),
        "routing": (spec.routing.algorithm, spec.routing.n_vcs,
                    spec.routing.n_planes),
    }
    bad = sum(stated_facts[k] != got[k] for k in stated_facts)
    bad += spec.faults is not None
    bad += any(c.service_lat not in (None, cfg["service_lat"])
               for c in spec.classes)
    return int(bad)


class System:
    """The configuration's specs and the one call the window drives."""

    def __init__(self, cell: Cell, seed: int):
        from repro.noc import RowShard
        self.cell, self.seed = cell, seed
        traffic.register(cell.config["classes"])
        self.points = traffic.point_params(cell.mix)
        self.configs = [_point_config(cell.config, kw)
                        for kw, _ in self.points]
        try:
            self.specs = [build_spec(c) for c in self.configs]
        except (TypeError, ValueError) as e:
            raise ValueError(f"{cell.config_file}: {e}") from e
        self.mismatches = sum(spec_mismatches(s, c)
                              for s, c in zip(self.specs, self.configs))
        rows = cell.layout.get("shard_rows")
        self.shard = RowShard(rows) if rows else None
        self.devices = cell.layout.get("sweep_devices")
        spec = self.specs[0]
        self.router_cycles = compare.router_cycles(
            spec.cycles, spec.n_routers, len(spec.channels), len(self.specs))

    def workloads(self, experiment: int | None):
        from repro.noc import Workload
        return [Workload.make(traffic.PATTERN, params=p,
                              seed=tuple(traffic.seed_of(
                                  self.seed, experiment, j)))
                for j, (_, p) in enumerate(self.points)]

    def call(self, experiment: int | None) -> list:
        """One experiment call through the public entry; returns one
        ``SimResult`` per point, on the host."""
        from repro.noc import simulate, sweep
        wls = self.workloads(experiment)
        if self.cell.mix["call"] == "simulate":
            return [simulate(self.specs[0], wls[0], shard=self.shard)]
        return sweep(list(zip(self.specs, wls)), devices=self.devices)


# --------------------------------------------------------------------- #
# the check against the plain reference
# --------------------------------------------------------------------- #
def check_sample(cell: Cell, seed: int, n_calls: int) -> list[tuple]:
    """``[(experiment, point)]`` the reference re-runs: for one
    experiment per call, ``check`` experiments of the window drawn from
    the seed; for a campaign, the last call's most heavily loaded point
    and ``check - 1`` more drawn from the seed."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    k = int(cell.mix.get("check", 1))
    if n_calls == 0:
        return []
    if cell.mix["call"] == "simulate":
        picks = rng.choice(n_calls, size=min(k, n_calls), replace=False)
        return [(int(i), 0) for i in sorted(picks)]
    n_pts = len(traffic.point_params(cell.mix))
    rest = rng.choice(n_pts - 1, size=min(k, n_pts) - 1, replace=False)
    return [(n_calls - 1, int(j)) for j in sorted(rest)] + [
        (n_calls - 1, n_pts - 1)]


def reference_results(cell: Cell, seed: int, picks: list[tuple],
                      depth_offset: int = 0) -> list[dict]:
    """The reference's flattened statistics for each picked point, all
    points run side by side in one call."""
    if not picks:
        return []
    ref = cell.reference()
    points = traffic.point_params(cell.mix)
    cfgs = [_point_config(cell.config, points[j][0]) for _, j in picks]
    ins = []
    for (i, j), cfg in zip(picks, cfgs):
        R = cfg["topology"]["nx"] * cfg["topology"]["ny"]
        sched = traffic.schedules(R, cfg["classes"], points[j][1],
                                  traffic.seed_of(seed, i, j))
        ins.append(traffic.stacked(sched, cfg["classes"]))
    T = max(x[0].shape[-1] for x in ins)

    def pad(a, fill):
        return np.pad(a, ((0, 0), (0, 0), (0, T - a.shape[-1])),
                      constant_values=fill)

    times = np.stack([pad(x[0], traffic.BIG) for x in ins])
    dests = np.stack([pad(x[1], 0) for x in ins])
    writes = np.stack([pad(x[2], 0) for x in ins])
    depths = [[c["depth"] for c in cfg["channels"]] for cfg in cfgs]
    return ref.simulate(cfgs[0], times, dests, writes, depths=depths,
                        depth_offset=depth_offset)


def mismatched_values(got: list, want: list[dict]) -> int:
    return sum(sum(compare.differences(compare.flatten(g), w).values())
               for g, w in zip(got, want))


# --------------------------------------------------------------------- #
# the run
# --------------------------------------------------------------------- #
def _count_compiles():
    """A list that grows by one for every XLA compilation (or
    persistent-cache load) from now on."""
    import jax.monitoring
    seen: list[float] = []

    def on_event(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            seen.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return seen


def _gc_pauses():
    """``(pauses, stop)``: a list that grows by ``(generation, seconds)``
    for every collection of Python's garbage collector until ``stop()``
    is called."""
    seen, began = [], {}

    def on_gc(phase, info):
        if phase == "start":
            began["t"] = time.perf_counter()
        elif "t" in began:
            seen.append((info["generation"],
                         time.perf_counter() - began.pop("t")))

    gc.callbacks.append(on_gc)
    return seen, lambda: gc.callbacks.remove(on_gc)


def slow_calls(calls: list[dict], factor: float = 1.25) -> str:
    """The window's calls that took over ``factor`` x the median, as
    ``index@start: seconds``, to tell a host stall from a slow device."""
    secs = sorted(c["seconds"] for c in calls)
    if not secs:
        return "none"
    med = secs[len(secs) // 2]
    slow = [f"{k}@{c['start']:.1f}s: {c['seconds']:.4f}"
            for k, c in enumerate(calls) if c["seconds"] > factor * med]
    return (f"median {med:.4f} s, max {secs[-1]:.4f} s; over {factor}x "
            f"the median: {', '.join(slow) or 'none'}")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, device_info: dict) -> dict:
    """Set up, warm up, measure for ``seconds``, check against the
    reference; returns the result line as a dict."""
    from repro.noc import sim_cache_stats, span_stats

    compiles = _count_compiles()
    system = System(cell, seed)
    system.call(None)                       # the cell's shapes, compiled
    setup_s = time.perf_counter() - t_start
    setup_spans = span_stats()
    misses0, n_compiles0 = sim_cache_stats()["misses"], len(compiles)

    calls, results, failed, undrained = [], [], 0, 0
    pauses, stop_pauses = _gc_pauses()
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        a = time.perf_counter()
        try:
            out = system.call(i)
        except Exception as e:              # a call that fails is counted
            log(f"experiment {i} failed: {type(e).__name__}: {e}")
            out = None
        b = time.perf_counter()
        failed += out is None
        undrained += 0 if out is None else sum(not bool(r.drained)
                                               for r in out)
        calls.append({"seconds": b - a, "start": a - t0,
                      "router_cycles": system.router_cycles})
        results.append(out)
        i += 1
    stop_pauses()
    window_compiles = len(compiles) - n_compiles0
    log(f"window: {len(calls)} calls in {time.perf_counter() - t0:.3f} s; "
        f"compiles in window: {window_compiles}; simulator builds in "
        f"window: {sim_cache_stats()['misses'] - misses0}; points not "
        f"drained within the horizon: {undrained}")
    log(f"window calls: {slow_calls(calls)}; garbage collections: "
        f"{len(pauses)}, {sum(t for _, t in pauses):.4f} s in all, longest "
        f"{max((t for _, t in pauses), default=0.0):.4f} s")

    run = {"setup_s": setup_s, "setup_spans": setup_spans,
           "calls": calls, "cell": cell.name,
           "points": len(system.specs), "cycles": system.specs[0].cycles,
           "chips": cell.chips, "trace": None}
    if trace:
        from bench import tracing
        run["trace"] = tr = tracing.traced_call(
            system, cell.root / ".bench_trace",
            sum(c["seconds"] for c in calls) / max(len(calls), 1))
        if tr is not None:
            log(f"trace: {tr['devices']} device(s), {tr['iterations']} "
                f"loop iterations, {tr['ops_per_iteration']:.1f} ops and "
                f"{1e6 * tr['collective_s_per_iteration']:.2f} us of "
                f"collectives per iteration, reduced in "
                f"{tr['reduce_s']:.1f} s after {tr['hlo_s']:.1f} s "
                f"lowering the programs' HLO")
    device = dict(device_info)
    device["memory_peak_bytes"] = peak_memory(cell.chips)

    picks = check_sample(cell, seed, len(calls))
    got = [results[i][j] for i, j in picks if results[i] is not None]
    del results
    t_ref = time.perf_counter()
    want = reference_results(cell, seed, picks)
    mism = mismatched_values(got, want) if len(got) == len(want) else -1
    log(f"reference: {len(picks)} points in "
        f"{time.perf_counter() - t_ref:.1f} s")

    checks = {
        "mismatched_values": {"value": mism, "limit": 0},
        "failed_calls": {"value": failed, "limit": 0},
        "spec_mismatches": {"value": system.mismatches, "limit": 0},
        "checked_points": {"value": len(got), "at_least": 1},
    }
    correct = (mism == 0 and failed == 0 and system.mismatches == 0
               and len(got) >= 1)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = cell.reader(m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": len(calls),
            "failed": failed, "metrics": metrics, "device": device}
    if trace and run["trace"] is not None:
        tr = run["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = tr["breakdown"]
    line["checks"] = checks
    for k, v in checks.items():
        lim = (f"limit {v['limit']}" if "limit" in v
               else f"at least {v['at_least']}")
        log(f"check {k} = {v['value']} ({lim})")
    return line


def peak_memory(chips: int) -> int | None:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None

