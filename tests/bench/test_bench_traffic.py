"""The traffic generator: seeded, same work for every seed."""
import numpy as np

from bench import traffic

CHAT = {"driver": "serve_open_loop",
        "arrivals": {"process": "poisson", "rate_per_s": 10.0},
        "prompt_tokens": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                          "min": 32, "max": 896},
        "output_tokens": {"dist": "lognormal", "median": 64, "sigma": 0.5,
                          "min": 16, "max": 128},
        "profiles": {"count": 1024, "popularity": "zipf", "s": 1.0},
        "block": 32}
BACKLOG = {"driver": "serve_backlog",
           "arrivals": {"process": "backlog", "max_rate_per_s": 4.0},
           "prompt_tokens": {"dist": "uniform", "min": 1024, "max": 3584},
           "output_tokens": {"dist": "uniform", "min": 128, "max": 512},
           "profiles": {"count": 8, "popularity": "uniform"},
           "block": 16}


def _key(arrs):
    return [(a.due_s, a.max_new, a.profile, a.prompt.tobytes())
            for a in arrs]


def test_same_seed_same_traffic_other_seed_other_traffic():
    big = 2 ** 31 + 12345
    a = traffic.generate(CHAT, big, 30, 151936)
    b = traffic.generate(CHAT, big, 30, 151936)
    c = traffic.generate(CHAT, big + 1, 30, 151936)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)
    assert [x.prompt.size for x in a] != [x.prompt.size for x in c]


def test_every_seed_gets_the_same_sizes_and_rate():
    runs = [traffic.generate(CHAT, s, 30, 1000) for s in (1, 2, 3)]
    for arrs in runs:
        assert len(arrs) == 288                  # 300 to whole blocks
        assert arrs[0].due_s == 0.0 and arrs[-1].due_s < 30.0
        assert all(x.due_s <= y.due_s for x, y in zip(arrs, arrs[1:]))
        assert all(32 <= x.prompt.size <= 896 and 16 <= x.max_new <= 128
                   for x in arrs)
        assert all((0 <= x.prompt).all() and (x.prompt < 1000).all()
                   for x in arrs)
    prompts = [sorted(x.prompt.size for x in arrs) for arrs in runs]
    outputs = [sorted(x.max_new for x in arrs) for arrs in runs]
    assert prompts[0] == prompts[1] == prompts[2]
    assert outputs[0] == outputs[1] == outputs[2]
    # the gaps between arrivals, and the one after the last, are the
    # same multiset for every seed
    gaps = [sorted(np.diff([x.due_s for x in arrs] + [30.0]).round(9))
            for arrs in runs]
    assert gaps[0] == gaps[1] == gaps[2]


def test_backlog_blocks_hold_the_same_sizes():
    arrs = traffic.generate(BACKLOG, 5, 30, 102400)
    assert len(arrs) == 128 and all(a.due_s == 0.0 for a in arrs)
    blocks = [sorted(a.prompt.size for a in arrs[i:i + 16])
              for i in range(0, 128, 16)]
    assert all(b == blocks[0] for b in blocks)
    assert blocks[0][0] >= 1024 and blocks[0][-1] <= 3584
    per_profile = np.bincount([a.profile for a in arrs], minlength=8)
    assert (per_profile == 16).all()


def test_zipf_popularity_follows_the_seeded_order():
    arrs = traffic.generate(CHAT, 9, 30, 1000)
    counts = np.bincount([a.profile for a in arrs], minlength=1024)
    hot = traffic.hottest_profiles(CHAT, 9, 3)
    assert counts[hot[0]] == counts.max()
    assert counts[hot[0]] > counts[hot[1]] >= counts[hot[2]]
    # H_1024 ~ 7.5: the top profile takes about 13% of requests
    assert 0.10 < counts[hot[0]] / len(arrs) < 0.16
