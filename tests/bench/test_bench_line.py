"""The result line's shape, and the exits without a chip."""
import json
import os
import shutil
import subprocess
import sys

from bench_helpers import REPO, run_tiny, tiny_root


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    return env


def test_last_line_shape(tmp_path):
    line = run_tiny(tiny_root(tmp_path), seconds=0.5)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float) and m["value"] > 0, name
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    for name, c in line["checks"].items():
        assert "value" in c and ("limit" in c or "at_least" in c), name
    text = json.dumps(line)
    assert "\n" not in text and json.loads(text) == line


def test_traced_line_on_cpu_has_no_device_metrics(tmp_path):
    """The CPU trace holds no device plane: the readers find nothing and
    the line leaves their metrics out, never writing a 0.  Only the
    set-up build time, read from the program's span table, is there."""
    line = run_tiny(tiny_root(tmp_path), seconds=0.3, trace=True)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_build_s"}
    assert line["metrics"]["setup_build_s"]["value"] > 0
    assert "breakdown" not in line and "busy_s" not in line["device"]
    assert not (tmp_path / ".bench_trace").exists()


def test_exits_nonzero_without_a_tpu():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "floonoc_7x7.uniform_rw", "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_exits_nonzero_for_an_unknown_cell():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nope", "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's own
    directories has no program to measure."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in json.loads((REPO / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "floonoc_7x7.uniform_rw", "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""
    # past the chip check too: the harness finds no program to drive
    code = ("import sys, time; from pathlib import Path; "
            "sys.path.insert(0, '.'); from bench import harness; "
            "cell = harness.resolve('floonoc_7x7.uniform_rw', Path('.')); "
            "harness.run_cell(cell, 3, 1.0, False, time.perf_counter(), {})")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         env=_env(), capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "No module named 'repro'" in out.stderr


def test_slow_calls_are_named_on_stderr():
    from bench import harness
    calls = [{"seconds": 1.0, "start": float(k)} for k in range(9)]
    calls.append({"seconds": 2.5, "start": 9.0})
    text = harness.slow_calls(calls)
    assert "9@9.0s: 2.5000" in text and text.count("@") == 1
    assert "median 1.0000 s, max 2.5000 s" in text
    assert harness.slow_calls(calls[:9]).endswith("the median: none")
