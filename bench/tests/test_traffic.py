"""The traffic generator: a mix's schedule, lengths and prompts."""
import json

import numpy as np

from bench import traffic
from conftest import ROOT


def mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


def test_open_loop_schedule_is_fixed_by_the_mix():
    m = mix("chat")
    r = traffic.open_loop(m, 51.0)
    n = round(m["arrivals"]["rate_per_s"] * 51)
    assert len(r) == n
    assert r[0].due_ms == 0.0 and r[-1].due_ms < 51_000
    assert all(16 <= a.max_new <= 512 for a in r)
    # the same mix gives the same schedule; another order seed the same
    # gaps and lengths in another order
    assert traffic.open_loop(m, 51.0) == r
    other = traffic.open_loop({**m, "order_seed": 2**33 + 7}, 51.0)
    assert [a.max_new for a in other] != [a.max_new for a in r]
    assert sorted(a.max_new for a in other) == sorted(a.max_new for a in r)
    gaps = [np.sort(traffic.exp_gaps(n, 51_000, traffic.rng(s, 1)))
            for s in (1, 2)]
    assert np.allclose(gaps[0], gaps[1]) and np.isclose(gaps[0].sum(), 51_000)


def test_lognormal_lengths_follow_their_median():
    lens = traffic.output_lengths(mix("chat")["output_tokens"], 1001,
                                  traffic.rng(3, 2))
    assert sorted(lens)[500] == 129


def test_onoff_bursts_have_a_fixed_count_per_cycle():
    m = mix("code-bursts")
    a = m["arrivals"]
    per_burst = round(a["rate_per_s"] * (a["on_s"] + a["off_s"]))
    for order in (4, 5):
        due = [r.due_ms for r in traffic.open_loop({**m, "order_seed": order},
                                                   24.0)]
        assert len(due) == 4 * per_burst
        for k in range(4):
            cycle = [t for t in due if 6000 * k <= t < 6000 * (k + 1)]
            assert len(cycle) == per_burst
            assert max(cycle) < 6000 * k + 2000


def test_prompts_depend_on_seed_and_request():
    a = traffic.prompt(2**33 + 1, 7, 1024, 151_936)
    assert a.shape == (1, 1024) and a.dtype == np.int32
    assert (a == traffic.prompt(2**33 + 1, 7, 1024, 151_936)).all()
    assert not (a == traffic.prompt(1, 7, 1024, 151_936)).all()
    assert not (a == traffic.prompt(2**33 + 1, 8, 1024, 151_936)).all()
