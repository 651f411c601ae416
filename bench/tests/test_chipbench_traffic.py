"""The traffic generator: one schedule per schedule seed, the same work
for every schedule seed, clipped lengths, the stated rate."""
import numpy as np
import pytest

from harness import traffic

CHAT = {"loop": "open", "arrivals": "poisson", "rate_rps": 2.0,
        "schedule_seed": 0,
        "prompt": {"dist": "lognormal", "median": 384, "sigma": 0.8,
                   "min": 32, "max": 1536},
        "output": {"dist": "uniform", "min": 16, "max": 48}}
DOCS = dict(CHAT, prompt={"dist": "uniform", "min": 1024, "max": 3072})


def test_open_schedule_is_a_function_of_the_seed():
    a = traffic.open_schedule(dict(CHAT, schedule_seed=2**33 + 5), 30.0)
    b = traffic.open_schedule(dict(CHAT, schedule_seed=2**33 + 5), 30.0)
    c = traffic.open_schedule(dict(CHAT, schedule_seed=7), 30.0)
    assert a == b
    assert a != c


@pytest.mark.parametrize("mix", [CHAT, DOCS], ids=["poisson", "uniform"])
def test_every_seed_gets_the_same_work(mix):
    s1 = traffic.open_schedule(dict(mix, schedule_seed=1), 30.0)
    s2 = traffic.open_schedule(dict(mix, schedule_seed=99), 30.0)
    assert len(s1) == len(s2) == 60           # rate 2/s over 30 s
    assert sorted(a.prompt_len for a in s1) == sorted(a.prompt_len
                                                      for a in s2)
    assert sorted(a.output_len for a in s1) == sorted(a.output_len
                                                      for a in s2)
    assert [a.prompt_len for a in s1] != [a.prompt_len for a in s2]


@pytest.mark.parametrize("mix", [CHAT, DOCS], ids=["poisson", "uniform"])
def test_arrivals_fall_inside_the_window_in_order(mix):
    s = traffic.open_schedule(mix, 30.0)
    due = np.array([a.due_s for a in s])
    assert (np.diff(due) >= 0).all()
    assert due[0] >= 0 and due[-1] < 30.0
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    for a in s:
        assert lo <= a.prompt_len <= hi and 16 <= a.output_len <= 48


def test_lognormal_median_and_clips():
    rng = np.random.default_rng(0)
    x = traffic.stratified_lengths(CHAT["prompt"], 1001, rng)
    assert np.median(x) == 384
    assert x.min() >= 32 and x.max() <= 1536


def test_load_mix_refuses_another_loop(tmp_path):
    p = tmp_path / "mix.json"
    p.write_text('{"loop": "closed"}')
    with pytest.raises(ValueError):
        traffic.load_mix(str(p))


def test_prompt_tokens_in_vocabulary_and_seeded():
    t1 = traffic.prompt_tokens(2**40, 3, 500, 32000)
    t2 = traffic.prompt_tokens(2**40, 3, 500, 32000)
    assert (t1 == t2).all() and t1.dtype == np.int32
    assert t1.min() >= 0 and t1.max() < 32000
