"""A configuration, a traffic mix and a metric added as new files are
found by name, with no edit to any file the benchmark already has."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def test_new_files_are_found_by_name(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((BENCH / "configs" / "internlm2-20b.json").read_text())
    cfg["num_hidden_layers"] = 4
    (tmp_path / "bench" / "configs" / "new-model.json").write_text(
        json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    mix["rate_per_s"] = 0.5
    (tmp_path / "bench" / "traffic" / "new-mix.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bm["configs"].append({"name": "new-model", "source": "x",
                          "file": "bench/configs/new-model.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "new-model.new-mix",
                            "config": "new-model", "traffic": "new-mix",
                            "chips": 1, "why": "x"})
    bm["per_layer"].append({"name": "new_metric", "unit": "x",
                            "better": "lower", "source": "program_counter",
                            "layer": "engine", "moves": "setup_s",
                            "workloads": ["new-model.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    probe = (
        "import harness\n"
        "c = harness.find_cell(harness.load_benchmark(), "
        "'new-model.new-mix')\n"
        "print(c.cfg['num_hidden_layers'], c.mix['rate_per_s'],\n"
        "      [m['name'] for m in c.per_layer],\n"
        "      harness.load_reader('new_metric')(None))\n")
    out = subprocess.run([sys.executable, "-c", probe],
                         cwd=tmp_path / "bench", capture_output=True,
                         text=True, check=True).stdout.split()
    assert out[:2] == ["4", "0.5"]
    assert "'new_metric']" in out[-2] and out[-1] == "42.0"
    after = {p: p.read_bytes() for p in before}
    assert after == before
