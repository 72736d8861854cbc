"""Replay the repo's OWN ML workloads on the simulated NoC.

The trace bridge (`repro.noc.traces`) end to end: trace real
train/prefill/decode steps on a 2x2 device mesh, capture their
collective byte ledgers, and replay them as AXI4 traffic on a 7x7
narrow/wide NoC — then compare MoE all-to-all dispatch against the
classic hotspot archetype, and show what per-stream AXI IDs
(`TrafficClass(n_streams=)`) buy on a real decode trace.

    PYTHONPATH=src python examples/noc_ml_traffic_study.py
"""
import dataclasses
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import jax                                                   # noqa: E402
import numpy as np                                           # noqa: E402

from repro.compile_cache import enable_compile_cache        # noqa: E402
from repro.configs import ShapeConfig, get_arch              # noqa: E402
from repro.configs.base import MeshConfig, RunConfig         # noqa: E402
from repro.core.channels import Ledger                       # noqa: E402
from repro.dist import step as step_lib                      # noqa: E402
from repro.models import build_model                         # noqa: E402
from repro.noc import NocSpec, Workload, simulate            # noqa: E402

enable_compile_cache()
MESH_CFG = MeshConfig(data=2, model=2, pod=1)


def trace_ledger(arch: str, phase: str) -> Ledger:
    """Build one step and trace it (no compute) — the ledger records
    every collective the step would run on real devices."""
    mcfg = get_arch(arch).smoke()
    cfg = RunConfig(model=mcfg, shape=ShapeConfig("p", 32, 4, "prefill"),
                    mesh=MESH_CFG)
    mesh = jax.make_mesh(MESH_CFG.shape, MESH_CFG.axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    model = build_model(mcfg, cfg)
    if phase == "train":
        art = step_lib.build_train_step(
            model, ShapeConfig("t", 32, 4, "train"), mesh)
    elif phase == "prefill":
        art = step_lib.build_prefill_step(
            model, ShapeConfig("p", 32, 4, "prefill"), mesh)
    else:
        art = step_lib.build_decode_step(
            model, ShapeConfig("d", 64, 4, "decode"), mesh)
    art.fn.lower(*art.in_sds)          # trace time populates the ledger
    return art.ledger


def streamed(spec: NocSpec, n: int) -> NocSpec:
    return spec.with_(classes=tuple(
        dataclasses.replace(c, n_streams=min(n, c.max_outstanding))
        for c in spec.classes))


print("=== train vs prefill vs decode on a 7x7 narrow/wide NoC ===")
# the traced job ran on a 2x2 device mesh: map its 4 ranks onto a 2x2
# corner of the 7x7 fabric (the rest of the mesh carries no traffic)
MAP = {"data": 2, "model": 2}
spec = NocSpec.narrow_wide(7, 7, cycles=6000)
ledgers = {ph: trace_ledger("llama3.2-1b", ph)
           for ph in ("train", "prefill", "decode")}
print("phase     entries    wide KB  narrow KB   done  w_lat avg/max"
      "  makespan  drained")
for ph, led in ledgers.items():
    by_cls = {"wide": 0, "narrow": 0}
    for e in led.entries:
        by_cls[e.traffic_class] += e.nbytes
    # scale production-sized tensors down to a simulable burst count
    r = simulate(spec, Workload.from_ledger(led, spec, mapping=MAP,
                                            scale=0.25))
    w = r.classes["wide"]
    lat = w.w_avg_lat[w.w_done > 0]
    done = sum(int(c.done.sum() + c.w_done.sum())
               for c in r.classes.values())
    mk = max(int(c.stream_w_last_t.max()) for c in r.classes.values())
    print(f"{ph:8s}  {len(led.entries):5d}  {by_cls['wide'] / 2**10:9.1f} "
          f" {by_cls['narrow'] / 2**10:9.2f}  {done:5d}"
          f"  {float(lat.mean()) if lat.size else float('nan'):6.1f}/"
          f"{int(w.w_max_lat.max()):4d}  {mk:8d}  {bool(r.drained)}")

print("\n=== MoE all-to-all dispatch vs hotspot archetype ===")
moe = trace_ledger("grok-1-314b", "prefill")
a2a = Ledger(entries=[e for e in moe.entries if e.op == "all_to_all"])
a2a_bytes = sum(e.nbytes for e in a2a.entries)
print(f"grok-1 prefill logs {len(a2a.entries)} all_to_all entries, "
      f"{a2a_bytes / 2**10:.0f} KiB")
spec_a2a = NocSpec.narrow_wide(7, 7, cycles=20000)
r_a2a = simulate(spec_a2a, Workload.from_ledger(a2a, spec_a2a, scale=0.25))
# a hotspot pattern pushing a comparable wide write volume at one tile
burst_bytes = 16 * 512 // 8
txns = max(1, int(a2a_bytes * 0.25 / burst_bytes) // spec.n_routers)
r_hot = simulate(spec_a2a, Workload.make(
    "hotspot", rates={"wide": 1.0}, counts={"wide": txns},
    hot=spec.n_routers // 2, hot_frac=1.0, write_frac=1.0, seed=0))
for tag, r in (("all_to_all", r_a2a), ("hotspot", r_hot)):
    w = r.classes["wide"]
    lat = w.w_avg_lat[w.w_done > 0]
    moves = int(r.channels["wide"].link_moves)
    print(f"  {tag:10s}: {int(w.w_done.sum()):4d} writes  "
          f"avg lat {float(lat.mean()):6.1f}  max {int(w.w_max_lat.max()):4d}"
          f"  wide-link moves {moves:6d}  drained {bool(r.drained)}")
print("  (the exchange spreads load across every link; the hotspot "
      "serializes at one ejection port)")

print("\n=== per-stream AXI IDs on the decode trace ===")
led = ledgers["decode"]
print("n_streams  wide w_avg_lat  per-stream last W beat")
for n in (1, 2, 4):
    sp = streamed(NocSpec.narrow_wide(7, 7, cycles=6000), n)
    r = simulate(sp, Workload.from_ledger(led, sp, mapping=MAP, scale=0.25))
    w = r.classes["wide"]
    lat = float(w.w_avg_lat[w.w_done > 0].mean())
    per = np.asarray(w.stream_w_last_t).max(axis=-1).astype(int)
    print(f"    {n}        {lat:8.1f}     {per.tolist()}")
print("(consecutive collectives round-robin across AXI IDs: with more "
      "streams, a bulk transfer in flight no longer holds the next "
      "collective's transactions in the shared in-order ROB, so the "
      "mean write latency of the SAME trace drops)")
