"""The harness runs on the chip only: no TPU, or a checkout holding nothing
but the benchmark, means a non-zero exit and no result line."""

import io
import os
import shutil
import subprocess
import sys
from contextlib import redirect_stdout

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench import run as bench_run  # noqa: E402


def test_refuses_a_platform_that_is_no_tpu():
    cell = harness.load_manifest()["workloads"][0]["name"]
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(["--workload", cell, "--seed", "1",
                             "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert out.getvalue() == ""


def test_a_checkout_of_the_benchmark_alone_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = harness.load_manifest()["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
