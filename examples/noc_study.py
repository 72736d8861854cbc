"""NoC design study: reproduce the paper's evaluation interactively.

Declares the paper's two network configurations as NocSpecs, sweeps the
cycle-level simulator over the Fig. 5 operating points with vmapped
``simulate_batch`` calls (one jit per topology instead of a Python loop
per point), and prints the latency/bandwidth tables plus the analytic
Table-I/Fig-6 quantities.

    PYTHONPATH=src python examples/noc_study.py
"""
import numpy as np

from repro.core.noc_sim import PAPER, PAPER_CLAIMS
from repro.compile_cache import enable_compile_cache
from repro.noc import NocSpec, Workload, simulate, simulate_batch

enable_compile_cache()

print("=== Table I / bandwidth (analytic) ===")
print(f"wide link: {PAPER.wide_link_gbps():.0f} Gbps "
      f"(paper {PAPER_CLAIMS['wide_link_gbps']:.0f})")
print(f"duplex   : {PAPER.wide_link_duplex_tbps():.2f} Tbps "
      f"(paper {PAPER_CLAIMS['wide_link_duplex_tbps']})")
print(f"7x7 mesh boundary: {PAPER.mesh_boundary_bandwidth_tbs(7, 7):.1f} TB/s "
      f"(paper {PAPER_CLAIMS['mesh7x7_boundary_tbs']})")

print("\n=== zero-load latency ===")
spec = NocSpec.narrow_wide(2, 1, cycles=200)
m = simulate(spec, Workload.make("fig5", rates={"narrow": 0.01},
                                 counts={"narrow": 1}, src=0, dst=1))
print(f"adjacent-tile round trip: {m.classes['narrow'].avg_lat[0]:.0f} cycles "
      f"(paper {PAPER_CLAIMS['zero_load_round_trip_cycles']})")

print("\n=== Fig 5a: narrow latency vs wide interference ===")
wide_rates = (0.0, 0.25, 0.5, 0.75, 1.0)
for preset, label in ((NocSpec.narrow_wide, "narrow-wide"),
                      (NocSpec.wide_only, "wide-only  ")):
    spec = preset(4, 4, cycles=8000)
    wls = [Workload.make("fig5",
                         rates={"narrow": 0.05, "wide": rate},
                         counts={"narrow": 100, "wide": 200 if rate else 0},
                         src=0, dst=15, bidir=True)
           for rate in wide_rates]
    m = simulate_batch(spec, wls)              # one vmapped jit call
    row = m.classes["narrow"].avg_lat[:, 0]
    print(f"{label}: "
          + "  ".join(f"{r/row[0]:4.2f}x" for r in row))

print("\n=== Fig 5b: wide effective bandwidth vs narrow interference ===")
narrow_rates = (0.0, 0.25, 1.0)
for preset, label in ((NocSpec.narrow_wide, "narrow-wide"),
                      (NocSpec.wide_only, "wide-only  ")):
    spec = preset(4, 4, cycles=6000)
    wls = [Workload.make("fig5",
                         rates={"narrow": nrate, "wide": 1.0},
                         counts={"narrow": 3000 if nrate else 0,
                                 "wide": 256},
                         src=0, dst=5)
           for nrate in narrow_rates]
    m = simulate_batch(spec, wls)
    row = m.classes["wide"].eff_bw[:, 0]
    print(f"{label}: util " + "  ".join(f"{u:.2f}" for u in row)
          + f"  (relative: {row[-1]/max(row[0],1e-9):.2f})")

print("\n=== per-channel link energy (Fig 6 model) ===")
spec = NocSpec.narrow_wide(4, 4, cycles=6000)
m = simulate(spec, Workload.make("fig5",
                                 rates={"narrow": 0.05, "wide": 1.0},
                                 counts={"narrow": 100, "wide": 64},
                                 src=0, dst=15))
for name, ch in m.channels.items():
    print(f"  {name:6s}: {int(ch.link_moves):6d} link moves, "
          f"{float(ch.energy_pj)/1e3:8.1f} nJ")
print(f"1 kB x 1 hop: {PAPER.energy_pj(1024, 1):.0f} pJ "
      f"({PAPER.pj_per_byte_hop} pJ/B/hop)")
print("OK")
