"""Design-space exploration with the declarative repro.noc API.

Demonstrates what the NocSpec/Workload/simulate redesign buys beyond
the paper's two fixed configurations:

1. a vmapped injection-rate sweep (one jitted call for the whole
   curve — the Python-loop-free path for Fig.-5-style studies),
2. arbitrary channel counts: 1 (wide-only), 3 (paper narrow-wide), and
   journal-style 2/4-stream parallel wide channels, compared under an
   all-to-all DNN-phase workload,
3. workload patterns beyond paired tiles: hotspot and transpose,
4. first-class fabric topologies (mesh / torus / express-link mesh)
   and the pluggable Pallas router backend behind the same simulate().

    PYTHONPATH=src python examples/noc_sweep.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.noc import (Mesh, NocSpec, Torus, Workload, hop_table, simulate,
                       simulate_batch)

enable_compile_cache()

# ------------------------------------------------------------------ #
# 1. one-jit rate sweep
# ------------------------------------------------------------------ #
print("=== vmapped rate sweep (one jit call) ===")
spec = NocSpec.narrow_wide(4, 4, cycles=4000)
rates = [0.2, 0.4, 0.6, 0.8, 1.0]
wls = [Workload.make("fig5", rates={"narrow": 0.05, "wide": r},
                     counts={"narrow": 50, "wide": 48}, src=0, dst=15)
       for r in rates]
res = simulate_batch(spec, wls)          # arrays carry a leading sweep axis
for i, r in enumerate(rates):
    pt = res.point(i)
    print(f"  wide_rate={r:.1f}: narrow avg "
          f"{pt.classes['narrow'].avg_lat[0]:5.1f} cyc, wide eff bw "
          f"{pt.classes['wide'].eff_bw[0]:.2f} beats/cyc")

# ------------------------------------------------------------------ #
# 2. channel-count exploration under an all-to-all phase
# ------------------------------------------------------------------ #
print("\n=== channel topologies under all-to-all (DNN exchange phase) ===")


def all_to_all_wl(spec, per_wide_rate):
    wide_classes = [c.name for c in spec.classes if c.burst_beats > 1]
    rates = {"narrow": 0.1}
    rounds = {"narrow": 4}
    for w in wide_classes:
        rates[w] = per_wide_rate / len(wide_classes)
        rounds[w] = max(1, 4 // len(wide_classes))
    return Workload.make("all_to_all", rates=rates, rounds=rounds)


topologies = [
    ("wide-only (1 ch) ", NocSpec.wide_only(4, 4, cycles=6000)),
    ("narrow-wide (3 ch)", NocSpec.narrow_wide(4, 4, cycles=6000)),
    ("2-stream (4 ch)   ", NocSpec.multi_stream(4, 4, n_wide=2,
                                                cycles=6000)),
    ("4-stream (6 ch)   ", NocSpec.multi_stream(4, 4, n_wide=4,
                                                cycles=6000)),
]
for label, topo in topologies:
    r = simulate(topo, all_to_all_wl(topo, per_wide_rate=1.0))
    s = r.summary()
    wide_done = sum(int(np.sum(st.done)) for name, st in r.classes.items()
                    if name != "narrow")
    print(f"  {label}: narrow avg {float(s['narrow_avg_lat']):6.1f} cyc, "
          f"wide txns {wide_done:4d}, link energy "
          f"{float(s['total_energy_pj'])/1e6:7.2f} uJ "
          f"({len(topo.channels)} nets)")

# ------------------------------------------------------------------ #
# 3. beyond paired tiles: hotspot and transpose
# ------------------------------------------------------------------ #
print("\n=== hotspot vs transpose (narrow-wide, 4x4) ===")
spec = NocSpec.narrow_wide(4, 4, cycles=6000)
patterns = [
    Workload.make("hotspot", rates={"narrow": 0.1, "wide": 0.5},
                  counts={"narrow": 20, "wide": 8}, hot_frac=0.7),
    Workload.make("transpose", rates={"narrow": 0.1, "wide": 0.5},
                  counts={"narrow": 20, "wide": 8}),
]
res = simulate_batch(spec, patterns)     # different patterns, one jit
for name, i in (("hotspot  ", 0), ("transpose", 1)):
    pt = res.point(i)
    nl = pt.classes["narrow"]
    active = nl.done > 0
    avg = float(np.sum(nl.avg_lat * active) / max(np.sum(active), 1))
    print(f"  {name}: narrow avg {avg:6.1f} cyc "
          f"(worst NI {float(np.max(nl.max_lat)):5.0f}), wide beats "
          f"{int(np.sum(pt.classes['wide'].beats_rx)):5d}")

# ------------------------------------------------------------------ #
# 4. fabric topologies + pluggable backends
# ------------------------------------------------------------------ #
print("\n=== fabric topologies (corner-to-corner, narrow-wide) ===")
wl = Workload.make("fig5", rates={"narrow": 0.05, "wide": 1.0},
                   counts={"narrow": 30, "wide": 16}, src=0, dst=15)
for label, fabric in (("mesh 4x4        ", Mesh(4, 4)),
                      ("torus 4x4       ", Torus(4, 4)),
                      ("mesh + express-2", Mesh(4, 4, express=(2,)))):
    spec = NocSpec.narrow_wide(4, 4, topology=fabric, cycles=4000)
    r = simulate(spec, wl)
    print(f"  {label}: max hops {int(hop_table(fabric).max())}, "
          f"narrow avg {float(r.classes['narrow'].avg_lat[0]):5.1f} cyc, "
          f"link moves {int(r.total_link_moves):6d} "
          f"({fabric.n_ports}-port routers)")

print("\n=== backend equivalence (jnp reference vs Pallas arbiter) ===")
spec = NocSpec.narrow_wide(4, 4, cycles=2000)
ref = simulate(spec, wl)
pal = simulate(spec, wl, backend="pallas")
same = np.array_equal(ref.classes["narrow"].done, pal.classes["narrow"].done)
print(f"  flit-for-flit identical: {same and int(ref.total_link_moves) == int(pal.total_link_moves)}")
print("OK")
