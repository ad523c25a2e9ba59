"""The traffic generator: same seed, same schedule; another seed, the
same lengths and gaps in another order."""
import numpy as np
import pytest

import traffic

MIXES = ["chat", "burst"]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = traffic.load(name)
    a = traffic.schedule(mix, 2 ** 31 + 12345, 45)
    b = traffic.schedule(mix, 2 ** 31 + 12345, 45)
    assert a == b
    assert np.array_equal(traffic.prompt_tokens(2 ** 31 + 5, 3, 40, 151936),
                          traffic.prompt_tokens(2 ** 31 + 5, 3, 40, 151936))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_work(name):
    mix = traffic.load(name)
    a = traffic.schedule(mix, 1, 45)
    b = traffic.schedule(mix, 2 ** 33 + 7, 45)
    assert sorted(s.prompt_len for s in a) == sorted(s.prompt_len for s in b)
    assert sorted(s.output_len for s in a) == sorted(s.output_len for s in b)
    assert [s.prompt_len for s in a] != [s.prompt_len for s in b]
    for s in a:
        assert mix["prompt"]["min"] <= s.prompt_len <= mix["prompt"]["max"]
        assert s.prompt_len <= mix["engine"]["prompt_pad"]
        assert s.prompt_len + mix["output"]["max"] <= mix["engine"]["s_cache"]


def test_open_loop_rates():
    chat = traffic.load("chat")
    s = traffic.schedule(chat, 9, 45)
    assert len(s) == round(chat["arrivals"]["rate"] * 45)
    due = [x.due_s for x in s]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 45
    burst = traffic.load("burst")
    s = traffic.schedule(burst, 9, 45)
    arr = burst["arrivals"]
    first = [x.due_s for x in s[:arr["burst"]]]
    assert max(first) < arr["burst_s"]
    period = arr["burst"] / arr["rate"]
    assert s[arr["burst"]].due_s >= period


def test_poisson_gaps_are_shuffled_whole():
    """Every seed offers the same set of exponential gaps, shuffled with
    no structure: any gap may follow any other, so the shortest gaps
    crowd together in some orders (three of the shortest eighth within
    eight consecutive gaps, which an order balanced in blocks of eight
    could never hold)."""
    chat = traffic.load("chat")
    rate = chat["arrivals"]["rate"]
    runs = []
    for seed in range(2 ** 31, 2 ** 31 + 40):
        due = np.array([x.due_s for x in traffic.schedule(chat, seed, 45)])
        runs.append(np.diff(due))
    g = traffic._gaps(rate, len(runs[0]) + 1)
    for r in runs:                      # all but the one after the last
        assert np.isin(np.round(r, 9), np.round(g, 9)).all()
    assert np.mean(g) == pytest.approx(1 / rate, rel=0.05)
    short = np.sort(g)[len(g) // 8]
    crowd = [np.convolve(r <= short, np.ones(8), "valid").max()
             for r in runs]
    assert max(crowd) >= 3
