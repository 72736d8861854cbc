"""Routing & virtual-channel study: deadlock freedom and path diversity.

The journal FlooNoC evaluation pairs the wide physical channels with a
routing layer: dimension-ordered XY by default, an escape virtual
channel with a dateline discipline to make torus wrap links
deadlock-free, and optional multi-plane policies (O1TURN, Valiant) for
path diversity under adversarial traffic.  This study reproduces that
story on the cycle-level simulator:

1. the wedge: a minimal-wrap torus under saturating wormhole bursts
   deadlocks with a single VC — visible as ``drained=False``, a stall
   streak the length of the remaining horizon, and VC0 occupancy pinned
   at its peak,
2. the fix: the identical spec with ``RoutingPolicy.xy(n_vcs=2)``
   (dateline escape VC) drains, and at equal load completes at least as
   many transactions as the mesh — wrap links now pay off instead of
   wedging,
3. path diversity: O1TURN splits flows across XY and YX planes
   (both VC groups show occupancy), Valiant trades hops for balance.

    PYTHONPATH=src python examples/noc_routing_study.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.noc import (Mesh, NocSpec, RoutingPolicy, Torus, Workload,
                       simulate)

enable_compile_cache()

CYCLES = 3500
wl = Workload.make("all_to_all", rates={"wide": 1.0}, rounds={"wide": 4},
                   write_frac=0.5)


def run(topo, pol):
    spec = NocSpec.wide_only(4, 4, topology=topo, burstlen=32,
                             cycles=CYCLES, max_wide_outstanding=16,
                             routing=pol)
    return simulate(spec, wl)


def report(tag, m):
    st = m.classes["wide"]
    done = int(st.done.sum()) + int(st.w_done.sum())
    occ = np.round(m.channels["wide"].vc_occupancy, 1)
    print(f"  {tag:22s} done={done:4d} drained={str(bool(m.drained)):5s} "
          f"max_stall={int(m.max_stall_cycles):4d} vc_occ={occ.tolist()}")
    return done


print("=== 1. the wedge: saturating bursts on a VC-less torus ===")
wedged = run(Torus(4, 4), RoutingPolicy.xy(1))
report("torus xy 1vc (wedged)", wedged)
assert not bool(wedged.drained)

print("\n=== 2. the fix: escape-VC dateline routing ===")
mesh_done = report("mesh  xy 1vc", run(Mesh(4, 4), RoutingPolicy.xy(1)))
torus_done = report("torus xy 2vc (fixed)",
                    run(Torus(4, 4), RoutingPolicy.xy(2)))
print(f"  -> torus with escape VC completes {torus_done} >= mesh "
      f"{mesh_done} at equal load (wrap links now help)")
assert torus_done >= mesh_done

print("\n=== 3. path diversity: multi-plane policies ===")
report("mesh  o1turn 2vc", run(Mesh(4, 4), RoutingPolicy.o1turn(2)))
report("torus o1turn 4vc", run(Torus(4, 4), RoutingPolicy.o1turn(4)))
report("mesh  valiant 4vc", run(Mesh(4, 4), RoutingPolicy.valiant(4)))
print("  (o1turn: both VC planes occupied -> flows split XY/YX; "
      "valiant pays detour hops for load balance)")
