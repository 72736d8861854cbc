"""Smoke run of the simulator on one TPU chip: does the main path start,
finish and give the right answer there?

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the multi-device paths, 4 chips

It drives ``repro.noc.simulate`` / ``simulate_batch`` on the paper's
7x7 narrow/wide mesh (three physical networks at the paper's widths)
under two mixed read/write workloads, and checks that

* every run drains, and the 2x1 zero-load round trip is 18 cycles;
* the chip's results equal, field for field, the same runs on the
  host CPU of this process;
* the ``pallas_fused`` backend (Mosaic kernels) equals ``jnp`` flit for
  flit, and a vmapped ``simulate_batch`` equals its per-point runs.

``--four-chips`` runs only what spans devices: a 32x32 mesh row-sharded
over 4 chips (``RowShard(4)``) against the same run on one chip, and a
64-spec ``sweep(devices=4)`` against ``devices=1``.

Any failed check raises (non-zero exit).  Without a TPU it exits
non-zero before running anything.  The last line of stdout is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Times printed on earlier lines are informational, not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
# the host-CPU reference runs need the CPU backend beside the chip's
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.noc import (NocSpec, RowShard, Workload, simulate,  # noqa: E402
                       simulate_batch, sweep)


def info(msg: str) -> None:
    print(f"# {msg}", flush=True)


def assert_same(a, b, what: str) -> None:
    """Every field of two SimResults is equal (bit for bit)."""
    def same(x, y, where):
        if dataclasses.is_dataclass(x):
            for f in dataclasses.fields(x):
                if f.name != "spec":
                    same(getattr(x, f.name), getattr(y, f.name),
                         f"{where}.{f.name}")
        elif isinstance(x, (list, tuple)):
            if len(x) != len(y):
                raise AssertionError(f"{what}: {where} lengths differ")
            for i, (xi, yi) in enumerate(zip(x, y)):
                same(xi, yi, f"{where}[{i}]")
        elif hasattr(x, "keys"):
            if set(x) != set(y):
                raise AssertionError(f"{what}: {where} keys differ")
            for k in x:
                same(x[k], y[k], f"{where}[{k}]")
        elif x is not None or y is not None:
            if not np.array_equal(np.asarray(x), np.asarray(y)):
                raise AssertionError(
                    f"{what}: {where} differs:\n{x}\nvs\n{y}")
    same(a, b, "result")


def timed_twice(label: str, router_cycles: int, fn):
    """Run ``fn`` twice (the second call hits the jit cache); both
    results must be equal.  Prints compile seconds and router-cycles/s
    (informational)."""
    t0 = time.perf_counter()
    first = fn()
    t1 = time.perf_counter()
    second = fn()
    t2 = time.perf_counter()
    assert_same(first, second, f"{label} repeat")
    run_s = t2 - t1
    info(f"{label}: compile_s={max(t1 - t0 - run_s, 0.0)} run_s={run_s} "
         f"router_cycles_per_s={router_cycles / run_s} (informational)")
    return second


def router_cycles(spec: NocSpec, points: int = 1) -> int:
    """cycles x routers x physical networks (x sweep points)."""
    return spec.cycles * spec.n_routers * len(spec.channels) * points


def smoke_workloads():
    return {
        "fig5": Workload.make(
            "fig5", rates={"narrow": 0.05, "wide": 1.0},
            counts={"narrow": 100, "wide": 200}, src=0, dst=48,
            bidir=True, write_frac={"wide": 0.5}),
        "uniform_random": Workload.make(
            "uniform_random", rates={"narrow": 0.05, "wide": 0.25},
            counts={"narrow": 40, "wide": 40}, seed=0, write_frac=0.5),
    }


def one_chip() -> None:
    spec = NocSpec.narrow_wide(7, 7, cycles=8000)
    cpu = jax.devices("cpu")[0]

    zspec = NocSpec.narrow_wide(2, 1, cycles=200)
    z = simulate(zspec, Workload.make("fig5", rates={"narrow": 0.01},
                                      counts={"narrow": 1}, src=0, dst=1))
    lat = float(z.classes["narrow"].avg_lat[0])
    if lat != 18.0:
        raise AssertionError(f"zero-load round trip {lat} cycles, want 18")
    info("zero_load_round_trip_cycles=18 ok")

    rc = router_cycles(spec)
    jnp_res = {}
    for name, wl in smoke_workloads().items():
        r = timed_twice(f"jnp/{name}", rc, lambda: simulate(spec, wl))
        if not bool(r.drained):
            raise AssertionError(f"jnp/{name}: not drained")
        with jax.default_device(cpu):
            host = simulate(spec, wl)
        assert_same(r, host, f"jnp/{name} chip vs host cpu")
        info(f"jnp/{name}: drained, equals host cpu")
        jnp_res[name] = r

    for name, wl in smoke_workloads().items():
        r = timed_twice(f"pallas_fused/{name}", rc,
                        lambda: simulate(spec, wl, backend="pallas_fused"))
        assert_same(r, jnp_res[name], f"pallas_fused/{name} vs jnp")
        info(f"pallas_fused/{name}: equals jnp flit for flit")

    rates = (0.25, 0.5, 0.75, 1.0)
    wls = [Workload.make("fig5", rates={"narrow": 0.05, "wide": w},
                         counts={"narrow": 100, "wide": 200}, src=0, dst=48,
                         bidir=True, write_frac={"wide": 0.5})
           for w in rates]
    batch = timed_twice("simulate_batch/4_wide_rates",
                        router_cycles(spec, len(rates)),
                        lambda: simulate_batch(spec, wls))
    for i, wl in enumerate(wls):
        assert_same(batch.point(i), simulate(spec, wl),
                    f"simulate_batch point {i} vs simulate")
    info("simulate_batch: equals the per-point runs")


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "noc_bench", ROOT / "benchmarks" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def four_chips() -> None:
    from jax.sharding import PartitionSpec

    from repro.noc.farm import _device_mesh

    if jax.device_count() < 4:
        raise SystemExit(f"--four-chips needs 4 devices, jax sees "
                         f"{jax.device_count()}")
    # the farm's device mesh must put one shard on each of 4 chips
    mesh = _device_mesh(4, "rows")
    ids = jax.jit(jax.shard_map(
        lambda x: x + jax.lax.axis_index("rows"), mesh=mesh,
        in_specs=PartitionSpec("rows"), out_specs=PartitionSpec("rows")))(
            np.zeros(4, np.int32))
    placed = {s.device for s in ids.addressable_shards}
    if len(placed) != 4 or list(np.asarray(ids)) != [0, 1, 2, 3]:
        raise AssertionError(f"farm mesh placed shards on {placed}")
    info(f"farm device mesh spans {sorted(d.id for d in placed)}")

    spec = NocSpec.narrow_wide(32, 32)
    wl = Workload.make("uniform_random", rates={"narrow": 0.05, "wide": 0.25},
                       counts={"narrow": 4, "wide": 4}, seed=0,
                       write_frac=0.5)
    rc = router_cycles(spec)
    single = timed_twice("jnp/32x32 one chip", rc, lambda: simulate(spec, wl))
    sharded = timed_twice("jnp/32x32 RowShard(4)", rc,
                          lambda: simulate(spec, wl, shard=RowShard(4)))
    assert_same(sharded, single, "RowShard(4) vs one chip")
    info(f"RowShard(4) equals one chip (drained={bool(single.drained)})")

    bench = _load_bench()
    pts = bench._sweep_scaling_points(False)
    digests = {}
    for n in (1, 4):
        out = timed_twice(f"sweep/{len(pts)} specs devices={n}",
                          router_cycles(pts[0][0], len(pts)),
                          lambda: sweep(pts, devices=n))
        digests[n] = bench.sweep_digest(out)
    if digests[1] != digests[4]:
        raise AssertionError(f"sweep digests differ: {digests}")
    info(f"sweep digest devices=4 equals devices=1: {digests[1]}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the paths that span 4 devices")
    args = ap.parse_args()
    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax platform {dev.platform!r})")
    info(f"device kind={dev.device_kind} count={jax.device_count()}")
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
