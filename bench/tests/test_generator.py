"""Traffic: the seed orders a fixed set of work, and sizes stay in range."""
from bench import generator

MIX = {"prompt": {"dist": "lognormal", "median": 256, "sigma": 0.45,
                  "min": 64, "max": 512},
       "output": {"dist": "lognormal", "median": 96, "sigma": 0.5,
                  "min": 32, "max": 256}}
ARRIVAL = {"process": "poisson", "rate_per_s": 2.0}
BIG = 2 ** 31 + 12345  # driver seeds exceed 32 signed bits
SEGMENTS = (8.0, 30.0, 8.0)


def reqs(seed, arrival=ARRIVAL):
    return generator.schedule(MIX, arrival, seed=seed, segments_s=SEGMENTS,
                              vocab=1000)


def window(rs):
    return [r for r in rs if 8.0 <= r.due < 38.0]


def test_same_seed_same_schedule():
    assert reqs(BIG) == reqs(BIG)


def test_seeds_reorder_one_set_of_work_in_the_window():
    a, b = window(reqs(7)), window(reqs(BIG))
    assert a != b and len(a) == len(b) == 60
    assert sorted((len(r.prompt), r.out_len) for r in a) == \
        sorted((len(r.prompt), r.out_len) for r in b)
    assert a[0].due == b[0].due == 8.0
    assert [r.due for r in a] != [r.due for r in b]


def test_sizes_rates_and_tokens_in_range():
    rs = reqs(3)
    assert len(rs) == 16 + 60 + 16
    assert all(64 <= len(r.prompt) <= 512 for r in rs)
    assert all(32 <= r.out_len <= 256 for r in rs)
    assert all(0 <= t < 1000 for r in rs for t in r.prompt)
    assert [r.due for r in rs] == sorted(r.due for r in rs)
    assert rs[-1].due < sum(SEGMENTS)


def test_gamma_arrivals_keep_the_count_per_segment():
    rs = reqs(3, {"process": "gamma", "rate_per_s": 2.0, "cv": 3.0})
    assert len(window(rs)) == 60
