"""A run with the timed path broken underneath must come out not
correct. The harness's look for a chip is skipped (the tiny CPU size);
the rest of the run is whole: server, engine, client, reference."""
import time

import pytest

import harness

CELLS = ["internlm2-20b.chat", "starcoder2-7b.complete"]


def _broken(kind):
    from repro.train import steps

    orig = steps.make_paged_serve_step

    def make(cfg, rules):
        step = orig(cfg, rules)

        def broken(params, arenas, bt, tok, cl):
            nxt, new = step(params, arenas, bt, tok, cl)
            if kind == "token_altered":
                return (nxt + 1) % cfg.vocab_size, new
            if kind == "state_unchanged":
                return nxt, arenas
            if kind == "half_the_slots_left_out":
                # every other slot's token is slot 0's, not its own
                return nxt.at[1::2].set(nxt[0]), new
            raise ValueError(kind)
        return broken
    return make


@pytest.mark.parametrize("kind", ["token_altered", "state_unchanged",
                                  "half_the_slots_left_out"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_step_is_not_correct(cell, kind, monkeypatch):
    from repro.train import steps
    monkeypatch.setattr(steps, "make_paged_serve_step", _broken(kind))
    line = harness.run_cell(cell, 2**31 + 99, 3.0, False,
                            t_start=time.monotonic(), tiny=True)
    assert line["correct"] is False, line["check"]
    assert line["check"]["widest_gap"]["value"] > \
        line["check"]["widest_gap"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_step_is_correct(cell):
    line = harness.run_cell(cell, 2**31 + 99, 3.0, False,
                            t_start=time.monotonic(), tiny=True)
    assert line["correct"] is True, line["check"]
