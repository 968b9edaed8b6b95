"""The command refuses to run where it cannot measure: no result line."""
import os
import shutil
import subprocess
import sys

import common

CMD = [sys.executable, "benchmarks/chip/run.py", "--workload",
       "pass.whisper_tiny.k16", "--seed", "1", "--seconds", "1",
       "--trace", "0"]


def run_in(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(CMD, cwd=root, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_tpu_no_result():
    out = run_in(common.ROOT)
    assert out.returncode == 1
    assert "platform=cpu" in out.stdout
    assert "correct" not in out.stdout


def test_bare_benchmark_directory_fails(tmp_path):
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(common.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = run_in(tmp_path)
    assert out.returncode != 0
    assert "correct" not in out.stdout


def test_every_metric_has_a_reader():
    """Each metric of BENCHMARK.json resolves to a reader under metrics/,
    a split name (``mfu_pct.pop``) to the reader of its quantity."""
    bench = common.load_json(common.ROOT / "BENCHMARK.json")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(common.load_module("metrics", m["name"]).read)
    assert common.module_path("metrics", "mfu_pct.pop").name == "mfu_pct.py"
    assert common.module_path("metrics", "server_round_ms").name == \
        "server_round_ms.py"
