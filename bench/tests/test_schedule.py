"""Each traffic mix repeats exactly from its seed, gives every seed the
same work, and keeps prompts within what the wire carries."""
from pathlib import Path

import numpy as np
import pytest

import schedule as SCH

MIXES = sorted(p.stem for p in (Path(SCH.__file__).parent / "traffic")
               .glob("*.json"))
SEEDS = [0, 7, 2**31 + 12345, 2**40 + 3]


@pytest.mark.parametrize("mix", MIXES)
def test_repeats_exactly_from_its_seed(mix):
    m = SCH.load_mix(Path(SCH.__file__).parent / "traffic" / f"{mix}.json")
    for seed in SEEDS:
        a = SCH.make_schedule(m, seed, 51, 50000)
        b = SCH.make_schedule(m, seed, 51, 50000)
        assert np.array_equal(a.due_s, b.due_s)
        assert np.array_equal(a.budget, b.budget)
        assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))


def gaps(s, seconds=51):
    return np.diff(np.concatenate([s.due_s, [seconds]]))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_work_in_the_same_order(mix):
    m = SCH.load_mix(Path(SCH.__file__).parent / "traffic" / f"{mix}.json")
    runs = [SCH.make_schedule(m, s, 51, 50000) for s in SEEDS]
    for r in runs[1:]:
        assert len(r) == len(runs[0])
        assert sorted(r.prompt_len) == sorted(runs[0].prompt_len)
        assert sorted(r.budget) == sorted(runs[0].budget)
        assert np.allclose(np.sort(gaps(r)), np.sort(gaps(runs[0])))
        assert np.array_equal(r.prompt_len, runs[0].prompt_len)
        assert np.array_equal(r.budget, runs[0].budget)
        assert np.allclose(r.due_s, runs[0].due_s)
    assert not np.array_equal(runs[0].prompts[0], runs[1].prompts[0])


@pytest.mark.parametrize("mix", MIXES)
def test_prompts_fit_the_wire_and_the_window(mix):
    m = SCH.load_mix(Path(SCH.__file__).parent / "traffic" / f"{mix}.json")
    s = SCH.make_schedule(m, 3, 51, 50000)
    assert m["prompt"]["max"] <= SCH.MAX_PROMPT_TOKENS
    assert max(len(p) for p in s.prompts) <= SCH.MAX_PROMPT_TOKENS
    assert all(len(s.payload(i)) <= 4096 for i in range(len(s)))
    assert s.due_s[0] == 0 and s.due_s[-1] < 51
    assert len(s) == round(m["rate_per_s"] * 51)
    assert all(p.min() >= 0 and p.max() < 50000 for p in s.prompts)


def test_arrival_shapes():
    pois = SCH.unit_gaps({"kind": "poisson"}, 4000)
    burst = SCH.unit_gaps({"kind": "gamma", "cv": 2.0}, 4000)
    assert abs(pois.mean() - 1) < 1e-9 and abs(burst.mean() - 1) < 1e-9
    assert abs(pois.std() - 1.0) < 0.05
    assert abs(burst.std() - 2.0) < 0.15


def test_lognormal_sizes_median_and_clip():
    x = SCH.lognormal_sizes({"median": 160, "sigma": 1.0, "min": 16,
                             "max": 1016}, 1001)
    assert np.median(x) == 160 and x.min() >= 16 and x.max() <= 1016


def test_saved_schedule_loads_unchanged(tmp_path):
    m = SCH.load_mix(Path(SCH.__file__).parent / "traffic" / f"{MIXES[0]}.json")
    a = SCH.make_schedule(m, 11, 5, 1000)
    a.save(tmp_path / "s.npz")
    b = SCH.Schedule.load(tmp_path / "s.npz")
    assert [a.payload(i) for i in range(len(a))] == \
        [b.payload(i) for i in range(len(b))]
