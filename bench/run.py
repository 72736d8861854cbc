"""Run one benchmark cell on the accelerator and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
through ``BENCHMARK.json`` (see ``bench/harness.py``).  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero before it
measures anything.  JAX's persistent compilation cache lives in
``.jax_cache/`` at the root of the checkout, so only a checkout's first
run of a cell compiles.  The last line of stdout is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` with ``--trace 1``) and, last, ``checks``: each number
the correctness check compared, with its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    try:
        cell = harness.resolve(args.workload, ROOT)
    except (KeyError, FileNotFoundError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    # the compile cache is the checkout's own, whatever the environment
    # says, so the two sides of a comparison never share one
    cache = ROOT / ".jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"jax sees {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    cache.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": cell.chips}
    line = harness.run_cell(cell, args.seed, args.seconds,
                            bool(args.trace), T_START, device)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
