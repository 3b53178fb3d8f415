"""The traffic generator: deterministic per seed, one schedule of sizes
and arrivals for every seed, the stated medians and clips."""

import numpy as np
import pytest

from bench import traffic

SEED = 2 ** 33 + 17          # seeds past 32 bits are valid


@pytest.mark.parametrize("mix_name", ["chat", "decode"])
def test_same_seed_same_requests(mix_name):
    mix = traffic.load_mix(mix_name)
    n = traffic.count_for(mix, 50, 0.5)
    a = traffic.make(mix, SEED, n, 64000, 0.5)
    b = traffic.make(mix, SEED, n, 64000, 0.5)
    assert [(r.max_new, r.due, r.client) for r in a] == \
        [(r.max_new, r.due, r.client) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("mix_name", ["chat", "decode"])
def test_medians_and_clips(mix_name):
    mix = traffic.load_mix(mix_name)
    reqs = traffic.make(mix, SEED, 800, 64000, 1.0)
    for key, dist in ((lambda r: len(r.prompt), mix["prompt_tokens"]),
                      (lambda r: r.max_new, mix["output_tokens"])):
        v = np.asarray([key(r) for r in reqs])
        assert v.min() >= dist["min"] and v.max() <= dist["max"]
        assert abs(np.median(v) - dist["median"]) <= 0.02 * dist["median"]
    assert all(r.prompt.max() < 64000 and r.prompt.min() >= 0 for r in reqs)


def test_every_block_holds_one_draw_per_stratum():
    mix = traffic.load_mix("chat")
    reqs = traffic.make(mix, SEED, 64, 64000, 1.0)
    lens = np.asarray([len(r.prompt) for r in reqs])
    strata = np.sort(lens).reshape(mix["block"], -1)
    for j in range(0, 64, mix["block"]):
        block = np.sort(lens[j:j + mix["block"]])
        assert all(strata[s].min() <= block[s] <= strata[s].max()
                   for s in range(mix["block"]))


def test_van_der_corput_is_a_permutation():
    for m in (1, 2, 5, 8, 13):
        assert sorted(traffic.van_der_corput(m)) == list(range(m))


def test_open_loop_gaps_have_the_rate():
    mix = traffic.load_mix("chat")
    reqs = traffic.make(mix, SEED, 800, 64000, 0.5)
    mean_gap = reqs[-1].due / (len(reqs) - 1)
    assert abs(mean_gap - 2.0) < 0.1


@pytest.mark.parametrize("mix_name", ["chat", "decode"])
def test_every_seed_gets_the_one_schedule(mix_name):
    mix = traffic.load_mix(mix_name)
    a = traffic.make(mix, 1, 64, 64000, 0.5)
    b = traffic.make(mix, 2 ** 35, 64, 64000, 0.5)
    assert [(len(r.prompt), r.max_new, r.due) for r in a] == \
        [(len(r.prompt), r.max_new, r.due) for r in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
