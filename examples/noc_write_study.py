"""AXI4 write-traffic study: the full AW/W/B flow model end to end.

Sweeps the read/write mix of the Fig. 5 workload through one vmapped
``simulate_batch`` call, showing (1) per-direction latency/bandwidth,
(2) how write data shifts the per-channel link-energy ledger (W bursts
ride the wide channel, B acks load the narrow rsp channel — the
paper's AW/AR/B-narrow, W/R-wide mapping), (3) per-class
service-latency *distributions* (mean + seeded jitter), and (4) the
liveness fields on a saturating VC-less torus, where minimal-wrap
wormhole bursts can wedge (see ROADMAP).

    PYTHONPATH=src python examples/noc_write_study.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.noc import NocSpec, Torus, Workload, simulate, simulate_batch

enable_compile_cache()

print("=== read/write mix sweep (one vmapped jit) ===")
spec = NocSpec.narrow_wide(4, 4, cycles=6000)
mixes = (0.0, 0.25, 0.5, 0.75, 1.0)
wls = [Workload.make("fig5", rates={"narrow": 0.05, "wide": 1.0},
                     counts={"narrow": 60, "wide": 48},
                     src=0, dst=15, bidir=True, write_frac=mix)
       for mix in mixes]
m = simulate_batch(spec, wls)
print("write_frac   reads  writes  rd_lat  wr_lat  rsp_moves  wide_moves")
for i, mix in enumerate(mixes):
    wide = m.classes["wide"]
    reads = int(wide.done[i].sum())
    writes = int(wide.w_done[i].sum())
    rd_lat = float(np.max(wide.avg_lat[i])) if reads else float("nan")
    wr_lat = float(np.max(wide.w_avg_lat[i])) if writes else float("nan")
    print(f"  {mix:4.2f}      {reads:4d}   {writes:4d}   "
          f"{rd_lat:6.1f}  {wr_lat:6.1f}  "
          f"{int(m.channels['rsp'].link_moves[i]):8d}  "
          f"{int(m.channels['wide'].link_moves[i]):9d}")

print("\n=== per-channel energy at 50/50 (B acks on rsp, W on wide) ===")
r = simulate(spec, wls[2])
for name, ch in r.classes.items():
    print(f"  {name:6s}: rd {int(ch.done.sum()):3d} done "
          f"/ {int(ch.beats_rx.sum()):4d} R beats | "
          f"wr {int(ch.w_done.sum()):3d} done "
          f"/ {int(ch.w_beats_rx.sum()):4d} W beats")
for name, ch in r.channels.items():
    print(f"  {name:6s}: {int(ch.link_moves):6d} moves "
          f"{float(ch.energy_pj) / 1e3:8.1f} nJ")

print("\n=== per-class service-latency distributions ===")
wl = Workload.make("uniform_random", rates={"narrow": 0.4, "wide": 0.8},
                   counts={"narrow": 40, "wide": 10}, seed=3,
                   write_frac=0.5)
flat = simulate(spec, wl, service_lat=[8, 24], service_jitter=0)
jit = simulate(spec, wl, service_lat=[8, 24], service_jitter=[6, 0])
for tag, res in (("jitter=0", flat), ("narrow +/-6", jit)):
    st = res.classes["narrow"]
    print(f"  {tag:12s}: narrow avg {float(np.mean(st.avg_lat)):6.1f} "
          f"max {int(np.max(st.max_lat)):3d} cycles")

print("\n=== liveness: saturating bursts, mesh vs VC-less torus ===")
burst_wl = Workload.make("all_to_all", rates={"wide": 1.0},
                         rounds={"wide": 4}, write_frac=0.5)
for tag, topo in (("mesh ", None), ("torus", Torus(4, 4))):
    s = NocSpec.wide_only(4, 4, topology=topo, burstlen=32, cycles=2500,
                          max_wide_outstanding=16)
    res = simulate(s, burst_wl)
    print(f"  {tag}: drained={str(bool(res.drained)):5s} "
          f"max_stall={int(res.max_stall_cycles):4d} cycles "
          f"completed={int(res.classes['wide'].done.sum()) + int(res.classes['wide'].w_done.sum()):3d}")
print("OK")
