"""The control: the dense reference computed one precision below the
configuration's bfloat16 (float8 e4m3, ``arch/dense.py``) must fail the
check's limit. On the chip it ran at each cell's own size (PERF.md);
here at a size a test run holds, with every width cut to 256 and the
vocabulary to 4096, on three seeds."""
import json
from pathlib import Path

import numpy as np
import pytest

import reference as R
from arch import dense

BENCH = Path(__file__).resolve().parents[1]
SMALL = dict(hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=512, vocab_size=4096, num_hidden_layers=2,
             head_dim=64)


@pytest.mark.parametrize("config", ["internlm2-20b", "starcoder2-7b"])
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 5])
def test_float8_control_fails_the_limit(config, seed):
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    limit = cfg["check"]["widest_gap_limit"]
    cfg.update(SMALL)
    rng = np.random.default_rng(seed)
    ref = R.Reference(dense, cfg, seed, 4, 128)
    prompts = [rng.integers(0, 4096, 40, dtype=np.int32) for _ in range(4)]
    served = [rng.integers(0, 4096, 80).tolist() for _ in range(4)]
    control = R.served_gaps(ref, prompts, served, control=True)
    assert len(control) == 320
    assert control.max() > limit
    # the served tokens here are random: far from the best, unlike a
    # sound program's
    assert R.served_gaps(ref, prompts, served).min() >= 0


def test_the_reference_own_choice_has_no_gap():
    cfg = json.loads((BENCH / "configs" / "internlm2-20b.json").read_text())
    cfg.update(SMALL)
    ref = R.Reference(dense, cfg, 3, 1, 32)
    prompt = np.arange(1, 9, dtype=np.int32)
    toks = list(prompt)
    served = []
    for _ in range(6):                 # greedy decode with the reference
        h = ref.hidden(R.pack([np.asarray(toks, np.int32)], 1, 32))
        lg = np.asarray(ref.head_logits(h[0, len(toks) - 1:len(toks)]))
        served.append(int(lg.argmax()))
        toks.append(served[-1])
    gaps = R.served_gaps(ref, [prompt], [served])
    assert np.all(gaps == 0)
