"""The traffic generator: the same work for every seed, in the seed's order."""
import numpy as np

from chipbench import traffic

MIX = {"arrivals": {"kind": "poisson", "rate_rps": 1.5},
       "output": {"median": 129, "sigma": 0.8, "min": 16, "max": 512},
       "lead_in_s": 16.0}


def test_a_stratified_order_deals_every_stratum_to_every_block():
    rng = np.random.default_rng(5)
    v = np.arange(40.0)
    got = traffic._order(v, rng, 8)
    assert sorted(got) == list(v)
    # 5 blocks of 8; stratum s holds the values 5s .. 5s+4
    for block in got.reshape(5, 8):
        assert sorted(int(x) // 5 for x in block) == list(range(8))
    assert not np.array_equal(got, traffic._order(v, np.random.default_rng(6), 8))


def test_every_seed_offers_the_same_gaps_and_lengths_in_another_order():
    a = traffic.schedule(dict(MIX, lead_in_s=0.0), 3, 200.0)
    b = traffic.schedule(dict(MIX, lead_in_s=0.0), 2 ** 31 + 9, 200.0)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    # the gaps between requests are the exponential's quantiles, all but the
    # one that would follow the last request
    q = traffic._exp_quantiles(300, 1.5)
    for s in (a, b):
        assert len(s) == 300
        gaps = np.diff([r.due_s for r in s])
        assert np.abs(gaps[:, None] - q[None, :]).min(axis=1).max() < 1e-9
    assert [r.max_new for r in a] != [r.max_new for r in b]
    lens = [r.max_new for r in traffic.schedule(MIX, 3, 48.0)]
    assert min(lens) >= 16 and max(lens) <= 512
    assert np.median([r.max_new for r in a]) == 129
