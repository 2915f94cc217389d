"""Architecture modules: found by the name a configuration file gives,
free of the program outside ``program_config``, and enough on their own:
an architecture the harness has no code for (``tests/data``) runs the
whole tiny CPU path to a correct result. The moved dense reference gives
the gaps its earlier home gave, bit for bit."""
import ast
import json
import time
from pathlib import Path

import numpy as np
import pytest

import harness
import reference as R
from arch import dense

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).parent / "data"
FIXTURE = "bench/tests/data/qkv-bias-fixture.json"


def fixture_benchmark() -> dict:
    bm = harness.load_benchmark()
    bm["configs"] = [{"name": "qkv-bias-fixture", "source": "x",
                      "file": FIXTURE, "reduced": [], "why": "x"}]
    bm["workloads"] = [{"name": "qkv-bias-fixture.chat",
                        "config": "qkv-bias-fixture", "traffic": "chat",
                        "chips": 1, "why": "x"}]
    return bm


def test_a_fixture_architecture_runs_from_its_own_files():
    cell = harness.find_cell(fixture_benchmark(), "qkv-bias-fixture.chat",
                             arch_dir=DATA)
    assert cell.arch.__file__ == str(DATA / "dense_qkv_bias.py")
    line = harness.run_cell(cell, 2**31 + 41, 3.0, False,
                            t_start=time.monotonic(), tiny=True)
    assert line["correct"] is True, line["check"]
    assert line["check"]["tokens_compared"]["value"] > 0
    assert {"setup_s", "out_tokens_per_s"} <= set(line["metrics"])
    # the program served biases (the layout check holds it to the
    # program's tree with them)
    assert cell.arch.program_config("x", cell.arch.dims(cell.cfg)).qkv_bias


def test_the_fixture_biases_change_the_answer():
    # with the biases left out, the fixture's reference is another model
    cfg = json.loads((BENCH.parent / FIXTURE).read_text())
    fx = harness.load_arch(cfg, FIXTURE, DATA)
    m = fx.dims(cfg)
    tokens = np.arange(1, 17, dtype=np.int32)[None]
    with_b = R.Reference(fx, cfg, 5, 1, 16).hidden(tokens)
    without = R.Reference(dense, cfg, 5, 1, 16).hidden(tokens)
    assert with_b.shape == without.shape == (1, 16, m.d)
    assert not np.allclose(np.asarray(with_b), np.asarray(without),
                           atol=1e-2)


@pytest.mark.parametrize("arch, says", [(None, "names no architecture"),
                                        ("moe_mla", "'moe_mla'"),
                                        ("../harness", "'../harness'")])
def test_a_configuration_without_a_known_arch_is_refused(arch, says):
    cfg = json.loads((BENCH / "configs" / "internlm2-20b.json").read_text())
    cfg.pop("arch")
    if arch is not None:
        cfg["arch"] = arch
    with pytest.raises(harness.BenchError, match=says) as e:
        harness.load_arch(cfg, "bench/configs/x.json")
    assert "bench/configs/x.json" in str(e.value)


def test_find_cell_refuses_a_file_that_names_no_arch(tmp_path,
                                                     monkeypatch):
    cfg = json.loads((BENCH / "configs" / "internlm2-20b.json").read_text())
    cfg.pop("arch")
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "configs" / "x.json").write_text(json.dumps(cfg))
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    bm = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bm["configs"][0]["file"] = "bench/configs/x.json"
    name = next(w["name"] for w in bm["workloads"]
                if w["config"] == bm["configs"][0]["name"])
    with pytest.raises(harness.BenchError, match="names no architecture"):
        harness.find_cell(bm, name)


def _repro_imports_outside_program_config(path: Path) -> list[int]:
    tree = ast.parse(path.read_text())
    allowed = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and \
                node.name == "program_config":
            allowed |= {id(n) for n in ast.walk(node)}
    bad = []
    for node in ast.walk(tree):
        names = [a.name for a in node.names] \
            if isinstance(node, ast.Import) else \
            [node.module or ""] if isinstance(node, ast.ImportFrom) else []
        if any(n == "repro" or n.startswith("repro.") for n in names) \
                and id(node) not in allowed:
            bad.append(node.lineno)
    return bad


MODULES = sorted((BENCH / "arch").glob("*.py")) + [
    DATA / "dense_qkv_bias.py", BENCH / "reference.py",
    BENCH / "weights.py"]


@pytest.mark.parametrize("path", MODULES, ids=lambda p: p.name)
def test_no_architecture_module_imports_the_program(path):
    assert _repro_imports_outside_program_config(path) == []


def test_the_import_check_sees_a_stray_import(tmp_path):
    p = tmp_path / "m.py"
    p.write_text("from repro.models import layers\n"
                 "def program_config(name, m):\n"
                 "    import repro.config\n"
                 "def ref_layer():\n"
                 "    import repro\n")
    assert _repro_imports_outside_program_config(p) == [1, 5]


# The parent's ``bench/reference.py`` (before the move) on these inputs:
# served_gaps and its float8 control, float32 values as Python floats.
PARENT = {
    "internlm2-20b": {
        "gaps": [5.090243339538574, 5.808840751647949, 2.816483974456787,
                 4.5719828605651855, 3.7739346027374268, 2.706902265548706,
                 1.5026500225067139, 3.6309571266174316, 3.4814505577087402,
                 2.1284260749816895, 3.210418224334717, 4.405341625213623],
        "control": [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0,
                    0.0004248619079589844, 0.0, 0.0, 0.0]},
    "starcoder2-7b": {
        "gaps": [3.7082481384277344, 5.538060188293457, 2.66019868850708,
                 3.2903990745544434, 3.7096173763275146, 3.5089612007141113,
                 2.6374239921569824, 2.838207960128784, 5.919132709503174,
                 0.49379563331604004, 2.619694948196411, 4.123023986816406],
        "control": [0.0, 0.0, 0.04837608337402344, 0.0, 0.0, 0.0, 0.0, 0.0,
                    0.0, 0.0, 0.0, 0.0]},
}
SMALL = dict(hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=512, vocab_size=4096, num_hidden_layers=2,
             head_dim=64)


@pytest.mark.parametrize("config", sorted(PARENT))
def test_the_moved_reference_gives_the_parent_gaps_bit_for_bit(config):
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    cfg.update(SMALL)
    rng = np.random.default_rng(5)
    ref = R.Reference(dense, cfg, 2**31 + 7, 2, 48)
    prompts = [rng.integers(0, 4096, n, dtype=np.int32) for n in (9, 17)]
    served = [rng.integers(0, 4096, 6).tolist() for _ in range(2)]
    gaps = R.served_gaps(ref, prompts, served)
    control = R.served_gaps(ref, prompts, served, control=True)
    assert gaps.dtype == control.dtype == np.float32
    assert gaps.tolist() == PARENT[config]["gaps"]
    assert control.tolist() == PARENT[config]["control"]
