"""The whole path on the CPU at a tiny size: server, engine, the client
child and the comparison with the reference. The flag that allows the
CPU is the test's alone; without it the harness refuses to report."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
DEVICE_SOURCES = ("device_trace",)


def run(args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, str(BENCH / "run.py")] + args,
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_is_correct_and_names_its_device(cell, trace):
    p = run(["--workload", cell, "--seed", str(2**31 + 17), "--seconds",
             "2", "--trace", str(trace), "--tiny-cpu"])
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "check"
    arena = line["arena"]
    assert 0 < arena["peak_in_use"] <= arena["blocks"]
    assert line["correct"] is True, line["check"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    device_metrics = {m["name"] for m in bm["per_layer"] + bm["end_to_end"]
                      if m["source"] in DEVICE_SOURCES}
    assert not device_metrics & set(line["metrics"])
    if trace:
        assert "prefill_p50_ms" in line["metrics"]
        assert "busy_s" in line["device"] and "breakdown" in line
    else:
        assert {"setup_s", "out_tokens_per_s", "req_latency_p50_ms",
                "req_latency_p90_ms"} <= set(line["metrics"])
    tail = p.stderr.strip().splitlines()[-len(line["check"]):]
    assert all(t.startswith("check ") for t in tail)


def test_refuses_without_a_chip():
    p = run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
             "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "BENCHMARK.json").write_bytes(
        (ROOT / "BENCHMARK.json").read_bytes())
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
