"""The request generator: the same seed gives the same requests; other
seeds give the same sizes and gaps in another order, spread so that every
stretch of the window holds the same mix."""
import numpy as np

from bench import common, traffic

MIX = common.load_json(common.BENCH + "/traffic/serve-chat.json")


def _sizes(reqs):
    return (sorted(len(r.prompt) for r in reqs),
            sorted(r.max_tokens for r in reqs))


def test_same_seed_same_requests():
    a = traffic.requests(2 ** 31 + 5, 30.0, MIX, 50280)
    b = traffic.requests(2 ** 31 + 5, 30.0, MIX, 50280)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_other_seeds_same_work_other_order():
    a = traffic.requests(1, 30.0, MIX, 50280)
    b = traffic.requests(2, 30.0, MIX, 50280)
    assert len(a) == len(b) == round(MIX["arrivals"]["rate"] * 30)
    assert _sizes(a) == _sizes(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    gaps = lambda rs: sorted(np.round(np.diff([0] + [r.due_s for r in rs]), 9))
    assert np.allclose(gaps(a), gaps(b))


def test_lengths_and_arrivals_stay_in_range():
    reqs = traffic.requests(9, 30.0, MIX, 50280)
    assert all(16 <= len(r.prompt) <= 64 for r in reqs)
    assert all(16 <= r.max_tokens <= 256 for r in reqs)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 30.0


def test_every_group_holds_one_size_of_each_stratum():
    reqs = traffic.requests(2 ** 31 + 11, 30.0, MIX, 50280)
    g, n = traffic.GROUP, len(reqs)
    for lengths in ([len(r.prompt) for r in reqs],
                    [r.max_tokens for r in reqs]):
        strata = np.array_split(np.sort(lengths), g)
        for j in range(0, n - n % g, g):
            got = sorted(lengths[j:j + g])
            # the sorted group has its k-th value inside the k-th stratum
            assert all(s[0] <= v <= s[-1] for v, s in zip(got, strata))


def test_spread_order_keeps_every_value():
    rs = np.random.RandomState(3)
    values = np.arange(23)
    out = traffic.spread_order(rs, values)
    assert sorted(out) == list(values)
    assert list(out) != list(values)


def test_percentile_is_nearest_rank():
    assert traffic.percentile(list(range(1, 101)), 95) == 95
    assert traffic.percentile([3.0], 95) == 3.0
    assert traffic.percentile([1, 2, float("inf")], 50) == 2
