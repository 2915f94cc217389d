"""The ``deepseek_v3`` architecture (Moonlight-16B-A3B): its configuration
file keeps the published widths, its ``BENCHMARK.json`` entries are
the cell's, its tiny CPU run is correct from its own files, its counts
match hand counts, ``moe_spans`` joins the program's ``MOE_ROUTE``
events to its steps, and the two readers count those steps."""
import json
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import harness
import moe_spans
import spans
from arch import deepseek_v3 as DS
from spans import Step
from repro.core.genesys.trace import EV_MOE_ROUTE, EVENT_DTYPE, \
    MOE_ROUTE_SHIFT

BENCH = Path(__file__).resolve().parents[1]
CELL = "moonlight-16b-a3b.chat"


def test_the_event_code_and_packing_are_the_programs():
    assert moe_spans.EV_MOE_ROUTE == EV_MOE_ROUTE
    assert moe_spans.SHIFT == MOE_ROUTE_SHIFT


def test_the_configuration_keeps_every_published_width():
    cfg = json.loads((BENCH / "configs" / "moonlight-16b-a3b.json")
                     .read_text())
    m = DS.dims(cfg)
    assert (m.d, m.heads, m.rank, m.rope, m.nope, m.vd) == \
        (2048, 16, 512, 64, 128, 128)
    assert (m.dense_ff, m.ff, m.shared_ff, m.experts, m.top_k) == \
        (11264, 1408, 2816, 64, 6)
    assert (m.layers, m.dense_layers, m.vocab) == (27, 1, 163840)
    assert (m.lo, m.hi) == (0, 16)
    assert list(cfg["changed_from_source"]) == ["n_routed_experts"]


def test_the_benchmark_lists_the_configuration_its_cell_and_readers():
    bm = harness.load_benchmark()
    conf = [c for c in bm["configs"] if c["name"] == "moonlight-16b-a3b"]
    assert len(conf) == 1
    assert conf[0]["file"] == "bench/configs/moonlight-16b-a3b.json"
    cfg = json.loads((BENCH.parent / conf[0]["file"]).read_text())
    assert conf[0]["source"] == cfg["source"]
    assert conf[0]["reduced"] == list(cfg["changed_from_source"])
    cell = harness.find_cell(bm, CELL)
    assert cell.chips == 1 and cell.mix["arrival"] == {"kind": "poisson"}
    moe = {mt["name"]: mt for mt in bm["per_layer"]
           if mt["name"].startswith("moe_")}
    assert set(moe) == {"moe_step_hbm_share", "moe_decode_mfu"}
    assert all(mt["workloads"] == [CELL] for mt in moe.values())
    # the dense readers build steps without counters: not this cell's
    for name in ("decode_mfu", "step_hbm_share"):
        mt = next(m for m in bm["per_layer"] if m["name"] == name)
        assert CELL not in mt["workloads"]


def test_a_setting_the_module_does_not_compute_is_refused():
    cfg = json.loads((BENCH / "configs" / "moonlight-16b-a3b.json")
                     .read_text())
    with pytest.raises(ValueError, match="scoring_func"):
        DS.dims(dict(cfg, scoring_func="softmax"))


def test_the_cell_runs_to_a_correct_result_from_its_own_files():
    cell = harness.find_cell(harness.load_benchmark(), CELL)
    assert cell.arch.__name__ == "bench_arch_deepseek_v3"
    line = harness.run_cell(cell, 2**31 + 43, 3.0, False,
                            t_start=time.monotonic(), tiny=True)
    assert line["correct"] is True, line["check"]
    assert line["check"]["tokens_compared"]["value"] > 0
    assert {"setup_s", "out_tokens_per_s"} <= set(line["metrics"])
    mc = cell.arch.program_config("x", cell.arch.dims(cell.cfg))
    assert mc.mla and mc.n_dense_layers == 1 and mc.experts_held == (0, 2)


# d 8, 2 heads, latent 4 + rope 2, 3 layers (1 dense), 4 of 8 experts
M = DS.Dims(layers=3, dense_layers=1, d=8, heads=2, rank=4, nope=2, rope=2,
            vd=2, dense_ff=16, ff=4, shared_ff=8, experts=8, lo=0, hi=4,
            top_k=2, scale=1.0, vocab=10, padded_vocab=256, theta=1e4,
            eps=1e-5)
STEP = Step([1, 5], {"experts_hit": [2, 3], "assignments": [3, 4]})


def test_counts_by_hand():
    # attention: wq 8x2x4 64, wkv_a 8x6 48, wk_b 4x2x2 16, wv_b 16,
    # wo 2x2x8 32 = 176
    assert DS.attn_params(M) == 176
    # 3 x (176 + kv_norm 4 + 2 norms 16) + dense 3x8x16 384
    # + 2 x (router 64 + bias 8 + shared 3x8x8 192) + final 8 + head 80
    assert DS.weight_bytes(M) == (588 + 384 + 528 + 8 + 80) * 2
    # per layer 2 x (64 + 48 + absorb 2x2x4 16 + 2x4x2 16 + 32) + 2 x 2
    # heads x c x (6 + 4); dense 2x384; expert layers 2 x 2 x (64 + 192);
    # head 160
    assert DS.token_flops(M, 1) == 3 * (352 + 40) + 768 + 1024 + 160
    # 7 assignments x 2 x (3 x 8 x 4)
    assert DS.step_flops(M, STEP) == 3128 + 3608 + 7 * 192
    # weights, 5 hit experts x 96 x 2 B, 2 embedding rows x 16 B, the
    # entries read (0 + 4 positions) and written (2), 3 x 6 x 2 B each
    assert DS.step_bytes(M, STEP) == 3176 + 960 + 32 + 4 * 36 + 2 * 36


MS = 1_000_000
W0, W1 = 1000 * MS, 2000 * MS
A, B = 101, 102


def events(rows):
    out = np.zeros(len(rows), EVENT_DTYPE)
    for i, (ev, sysno, seq, ts, aux) in enumerate(rows):
        out[i] = (ts, ev, 0, sysno, seq, aux)
    return out


def route(step, layer, hit, assigned, ts):
    return (EV_MOE_ROUTE, step, layer, ts, hit + (assigned << 16))


def routed_events():
    # A (a 4-token prompt) and B (one token) seated; step 1 before the
    # window, steps 2 and 3 inside it, each with the routes of layers 1, 2
    return events([
        (spans.EV_ADMIT, -2, A, W0 - 100 * MS, 0),
        (spans.EV_STEP, 1, A, W0 - 10 * MS, 0),
        route(1, 1, 1, 2, W0 - 10 * MS), route(1, 2, 2, 2, W0 - 10 * MS),
        (spans.EV_ADMIT, -2, B, W0 - 5 * MS, 1),
        (spans.EV_STEP, 2, A, W0 + 20 * MS, 0),
        (spans.EV_STEP, 2, B, W0 + 20 * MS, 0),
        route(2, 2, 3, 4, W0 + 20 * MS), route(2, 1, 2, 3, W0 + 20 * MS),
        (spans.EV_STEP, 3, A, W0 + 50 * MS, 0),
        route(3, 1, 1, 2, W0 + 50 * MS), route(3, 2, 2, 2, W0 + 50 * MS),
    ])


def test_routes_join_their_steps_in_layer_order():
    got = moe_spans.routed_steps(routed_events(), W0, W1, [4, 1])
    assert got == [Step([5, 1], {"experts_hit": [2, 3],
                                 "assignments": [3, 4]}),
                   Step([6], {"experts_hit": [1, 2],
                              "assignments": [2, 2]})]


def test_the_counts_of_routed_steps_by_hand():
    steps = moe_spans.routed_steps(routed_events(), W0, W1, [4, 1])
    # step 2: 2 tokens at contexts 5 and 1, 7 assignments; step 3: one
    # token at 6, 4 assignments
    flops = ((3008 + 600) + (3008 + 120) + 7 * 192
             + (3008 + 720) + 4 * 192)
    assert sum(DS.step_flops(M, s) for s in steps) == flops
    nbytes = ((3176 + 5 * 192 + 2 * 16 + 4 * 36 + 2 * 36)
              + (3176 + 3 * 192 + 16 + 5 * 36 + 36))
    assert sum(DS.step_bytes(M, s) for s in steps) == nbytes


def test_no_routed_steps_without_routes():
    # a program with no MOE_ROUTE events (a dense model, or one older
    # than the event) gives no step
    evs = routed_events()
    assert moe_spans.routed_steps(evs[evs["ev"] != EV_MOE_ROUTE], W0, W1,
                                  [4, 1]) == []


def fake_run(evs, step_device_s, prompt_len=(4, 1)):
    return SimpleNamespace(
        events=evs, w0_ns=W0, w1_ns=W1, dims=M,
        requests=[{"prompt_len": p} for p in prompt_len],
        trace={"step_device_s": step_device_s},
        cell=SimpleNamespace(arch=DS),
        peaks={"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e6})


def test_the_readers_count_routed_steps_by_hand():
    # steps 2 and 3 as above: 12_576 operations and 8_368 bytes in all,
    # over two steps of 2 ms each on a chip of 1 MFLOP/s and 1 MB/s
    run = fake_run(routed_events(), [0.002, 0.002])
    mfu = harness.load_reader("moe_decode_mfu")(run)
    hbm = harness.load_reader("moe_step_hbm_share")(run)
    assert mfu == pytest.approx(100.0 * (12_576 / 2) / (0.002 * 1e6))
    assert hbm == pytest.approx(100.0 * (8_368 / 2) / (0.002 * 1e6))


def test_the_readers_read_nothing_without_routes_or_a_trace():
    evs = routed_events()
    for run in (fake_run(evs[evs["ev"] != EV_MOE_ROUTE], [0.002]),
                fake_run(evs, []),
                SimpleNamespace(events=None, trace=None)):
        assert harness.load_reader("moe_decode_mfu")(run) is None
        assert harness.load_reader("moe_step_hbm_share")(run) is None


# ------------------------------------------------------ planted faults --

def _planted(kind):
    """The paged decode step, broken underneath as ``kind`` says."""
    from repro.train import steps

    orig = steps.make_paged_serve_step

    def make(cfg, rules):
        step = orig(cfg, rules)

        def broken(params, arenas, bt, tok, cl):
            nxt, new, *hits = step(params, arenas, bt, tok, cl)
            if kind == "token_altered":
                nxt = (nxt + 1) % cfg.vocab_size
            elif kind == "half_the_slots_left_out":
                nxt = nxt.at[1::2].set(nxt[0])
            return (nxt, new, *hits)
        return broken
    return make


def _shifted_route(route):
    def shifted(mp, cfg, x):
        w, idx = route(mp, cfg, x)
        return w, (idx + 1) % cfg.n_experts
    return shifted


def _run_tiny(cell, seed=2**31 + 11):
    return harness.run_cell(cell, seed, 3.0, False,
                            t_start=time.monotonic(), tiny=True)


def _choosing(cell, monkeypatch):
    """The tiny cell with a choice to make (2 of 8 experts a token, 4
    held) and the program in float32, so that no rounding flips a
    choice: only a planted fault can."""
    import dataclasses

    monkeypatch.setattr(cell.arch, "TINY", dict(
        cell.arch.TINY, n_router_experts=8, n_routed_experts=4,
        experts_held=[0, 4], num_experts_per_tok=2))
    pc = cell.arch.program_config
    monkeypatch.setattr(cell.arch, "program_config", lambda name, m:
                        dataclasses.replace(pc(name, m),
                                            compute_dtype="float32",
                                            kv_cache_dtype="float32"))


@pytest.mark.parametrize("kind", ["token_altered", "half_the_slots_left_out"])
def test_a_broken_step_is_not_correct(kind, monkeypatch):
    from repro.train import steps
    monkeypatch.setattr(steps, "make_paged_serve_step", _planted(kind))
    line = _run_tiny(harness.find_cell(harness.load_benchmark(), CELL))
    assert line["correct"] is False, line["check"]
    gap = line["check"]["widest_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", [False, True])
def test_a_wrong_expert_choice_widens_the_gap(fault, monkeypatch):
    # each token routed to the experts after its own: the reference sees
    # it (a gap over 1 where the sound program reads none), though at
    # this size not beyond the cell's limit, which a routing flip of the
    # bf16 program at the published widths sets (PERF.md section 2)
    from repro.models import moe
    cell = harness.find_cell(harness.load_benchmark(), CELL)
    _choosing(cell, monkeypatch)
    if fault:
        monkeypatch.setattr(moe, "route", _shifted_route(moe.route))
    line = _run_tiny(cell)
    gap = line["check"]["widest_gap"]["value"]
    assert gap > 1.0 if fault else gap < 1e-3, line["check"]
