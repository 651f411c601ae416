"""The traffic generator: one schedule per schedule seed, the same work
for every schedule seed, clipped lengths, the stated rate, on/off bursts
inside their on-phases, and the committed mix's schedule as it was."""
import json
import os

import numpy as np
import pytest

from harness import traffic

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHAT = {"loop": "open", "arrivals": "poisson", "rate_rps": 2.0,
        "schedule_seed": 0,
        "prompt": {"dist": "lognormal", "median": 384, "sigma": 0.8,
                   "min": 32, "max": 1536},
        "output": {"dist": "uniform", "min": 16, "max": 48}}
DOCS = dict(CHAT, prompt={"dist": "uniform", "min": 1024, "max": 3072})
# 1 s at three times the mean rate, then 2 s silent
BURST = dict(CHAT, arrivals="onoff", on_s=1.0, off_s=2.0, burst_factor=3.0)
MIXES = pytest.mark.parametrize("mix", [CHAT, DOCS, BURST],
                                ids=["poisson", "uniform", "onoff"])


@pytest.mark.parametrize("mix", [CHAT, BURST], ids=["poisson", "onoff"])
def test_open_schedule_is_a_function_of_the_seed(mix):
    # the schedule seed alone draws it: a run's --seed never reaches it
    a = traffic.open_schedule(dict(mix, schedule_seed=2**33 + 5), 30.0)
    b = traffic.open_schedule(dict(mix, schedule_seed=2**33 + 5), 30.0)
    c = traffic.open_schedule(dict(mix, schedule_seed=7), 30.0)
    assert a == b
    assert a != c


@MIXES
def test_every_seed_gets_the_same_work(mix):
    s1 = traffic.open_schedule(dict(mix, schedule_seed=1), 30.0)
    s2 = traffic.open_schedule(dict(mix, schedule_seed=99), 30.0)
    assert len(s1) == len(s2) == 60           # rate 2/s over 30 s
    assert sorted(a.prompt_len for a in s1) == sorted(a.prompt_len
                                                      for a in s2)
    assert sorted(a.output_len for a in s1) == sorted(a.output_len
                                                      for a in s2)
    assert [a.prompt_len for a in s1] != [a.prompt_len for a in s2]


@MIXES
def test_arrivals_fall_inside_the_window_in_order(mix):
    s = traffic.open_schedule(mix, 30.0)
    due = np.array([a.due_s for a in s])
    assert (np.diff(due) >= 0).all()
    assert due[0] >= 0 and due[-1] < 30.0
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    for a in s:
        assert lo <= a.prompt_len <= hi and 16 <= a.output_len <= 48


@pytest.mark.parametrize("seconds", [30.0, 31.5, 29.0])
def test_onoff_arrivals_fall_in_on_phases(seconds):
    s = traffic.open_schedule(BURST, seconds)
    assert len(s) == round(BURST["rate_rps"] * seconds)
    period = BURST["on_s"] + BURST["off_s"]
    phase = np.array([a.due_s for a in s]) % period
    assert (phase < BURST["on_s"]).all()
    # spread over the on-phases: none of them is left empty
    whole = int(seconds // period)
    counts = np.bincount([int(a.due_s // period) for a in s],
                         minlength=whole)
    assert (counts[:whole] > 0).all()


def test_onoff_mean_rate_over_whole_periods():
    s = traffic.open_schedule(BURST, 30.0)
    period = BURST["on_s"] + BURST["off_s"]
    counts = np.bincount([int(a.due_s // period) for a in s], minlength=10)
    assert len(counts) == 10
    assert counts.mean() / period == pytest.approx(BURST["rate_rps"])
    # inside an on-phase the rate is burst_factor times the mean
    assert counts.mean() / BURST["on_s"] == pytest.approx(
        BURST["rate_rps"] * BURST["burst_factor"])


@pytest.mark.parametrize("change", [
    {"burst_factor": 2.5}, {"off_s": 1.0}, {"on_s": 0.0},
    {"burst_factor": None}, {"arrivals": "gamma"}],
    ids=["factor", "off_s", "on_s", "no_factor", "unknown"])
def test_load_mix_refuses_a_wrong_burst(tmp_path, change):
    mix = dict(BURST, loop="open", **change)
    mix = {k: v for k, v in mix.items() if v is not None}
    p = tmp_path / "mix.json"
    p.write_text(json.dumps(mix))
    with pytest.raises(ValueError):
        traffic.load_mix(str(p))


def test_load_mix_takes_a_sound_burst(tmp_path):
    p = tmp_path / "mix.json"
    p.write_text(json.dumps(dict(BURST, on_s=0.5, off_s=1.0,
                                 burst_factor=3.0)))
    assert traffic.load_mix(str(p))["arrivals"] == "onoff"


@pytest.mark.parametrize("seconds", ["10", "51", "153"])
def test_committed_poisson_schedule_is_unchanged(seconds):
    """``bench/traffic/minitron-chat.json`` sends what it sent before the
    on/off arrivals came (recorded from that generator)."""
    with open(os.path.join(DATA, "schedule_minitron_chat.json")) as f:
        want = json.load(f)["schedules"][seconds]
    mix = traffic.load_mix(os.path.join(BENCH, "traffic",
                                        "minitron-chat.json"))
    got = traffic.open_schedule(mix, float(seconds))
    assert [[a.due_s, a.prompt_len, a.output_len] for a in got] == want


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
