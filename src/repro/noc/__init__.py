"""repro.noc — declarative NoC experiment API.

    from repro.noc import Mesh, Torus, NocSpec, Workload, simulate

    spec = NocSpec.narrow_wide(nx=4, ny=4, cycles=8000)
    wl = Workload.make("fig5", rates={"narrow": 0.05, "wide": 1.0},
                       counts={"narrow": 100, "wide": 200}, bidir=True,
                       write_frac={"wide": 0.5})    # half the wide txns
    result = simulate(spec, wl)                      # pure-jnp reference
    result = simulate(spec, wl, backend="pallas")    # Pallas router kernel
    print(result.classes["narrow"].avg_lat)          # reads (AR -> R)
    print(result.classes["wide"].w_avg_lat)          # writes (AW -> W -> B)

Specs declare a first-class topology (``Mesh(nx, ny)``, ``Torus(nx,
ny)``, ``Mesh(nx, ny, express=(2,))`` for >5-port express routers), a
channel layout (any number of physical networks), and the full AXI4
flow map — every class's AR/R/AW/W/B flows assigned to channels (the
paper maps address/ack flows narrow, data bursts wide).  A
``routing=RoutingPolicy(...)`` entry picks the routing algorithm and
virtual-channel count (XY, O1TURN, Valiant; escape-VC dateline
discipline makes the torus deadlock-free — see ``repro.noc.routing``).
Workloads
declare typed traffic patterns with per-class read/write mixes; sweeps
vmap over rates/seeds/latency distributions in one jit
(``simulate_batch``, ``sweep``).  The router hot loop is a pluggable,
flow-agnostic backend (``backends.list_backends()``) behind the
identical surface — every backend is flit-for-flit equivalent,
including on mixed read/write traffic.

Static verification (``repro.noc.analyze``): ``analyze(spec)`` proves
or refutes deadlock freedom from the compiled route tables (Dally
channel-dependency graph over (link, VC) channels), lints the AXI
flow->channel protocol order and ROB/credit budgets, and reports named
route-table checks; ``simulate(..., verify="full")`` rejects
deadlock-prone specs before stepping, and ``python -m
repro.noc.analyze --all-presets`` is the CI gate.

Observability: ``span_stats()`` counts every API call's host phases
(``noc.verify``, ``noc.schedules``, ``noc.build``, ``noc.dispatch``,
``noc.wait``, ``noc.results`` under one ``noc.simulate``/``noc.sweep``
span) with their seconds, the same spans a ``jax.profiler`` trace shows
with a ``call=<id>`` argument; the scan step's device ops run under the
named scopes of :data:`PHASES`.
"""
from .api import (jitter_table, simulate, simulate_batch,  # noqa: F401
                  simulate_schedules, stack_schedules, sweep)
from .backends import (get_backend, list_backends,  # noqa: F401
                       register_backend)
from .engine import (PHASES, FlowPlan, build_channel_plan,  # noqa: F401
                     build_flow_plan, compiled_sim, sim_cache_clear,
                     sim_cache_stats, span_stats, span_stats_clear)
from .farm import (RowShard, farm_batch, merge_spec,  # noqa: F401
                   partition_spec)
from .faults import (FaultModel, UnroutableCutError,  # noqa: F401
                     cut_tables, dynamic_events)
from .result import (ChannelStats, ClassStats,  # noqa: F401
                     FaultStats, SimResult)
from .routing import RouteTables, RoutingPolicy  # noqa: F401
from .spec import NocSpec, PhysicalChannel, TrafficClass  # noqa: F401
from .topology import (Mesh, Topology, Torus, hop_table,  # noqa: F401
                       validate_tables)
from .traces import (EXPANDERS, expand_collective,  # noqa: F401
                     ledger_schedules, register_expander)
from .workload import PATTERNS, Workload, register_pattern  # noqa: F401

# repro.noc.analyze exports resolve lazily (PEP 562): the analyzer is
# only needed when a spec is constructed or verified, and keeping it
# out of the eager package import lets `python -m repro.noc.analyze`
# run as __main__ without a runpy double-import warning.  The name
# ``analyze`` resolves to the submodule (whose main entry point is
# ``analyze.analyze(spec)``), never a shadowing function.
_ANALYZE_EXPORTS = ("AnalysisError", "AnalysisReport", "CheckResult",
                    "analyze_routing", "check_protocol", "verify_spec")


def __getattr__(name: str):
    if name == "analyze" or name in _ANALYZE_EXPORTS:
        from importlib import import_module
        mod = import_module(".analyze", __name__)
        return mod if name == "analyze" else getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
