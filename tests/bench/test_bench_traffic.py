"""traffic.py: one generator reads every mix; seeds assign a fixed set of
closed-loop lanes of request shapes to clients and draw token ids."""
import collections
import json
import os

import numpy as np
import pytest

from benchkit import CHIP_DIR

import traffic


def _mix(name):
    with open(os.path.join(CHIP_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def _request(rid, prompt, n, now):
    return (rid, tuple(prompt), n, now)


def _closed_loop(mix, seed, rounds):
    """Every lane's first ``rounds`` requests, completing lane by lane:
    {lane: [(prompt length, output length), ...]} and the requests."""
    t = traffic.Traffic(mix, seed, 1000, 640, _request)
    first = t.initial(0.0)
    assert len(first) == mix["clients"]
    lanes = {}
    reqs = list(first)
    for req in first:
        lane = t._lane[req[0]][0]
        lanes[lane] = [(len(req[1]), req[2])]
        nxt = req
        for _ in range(rounds - 1):
            nxt, = t.finished(1.0, nxt[0])
            lanes[lane].append((len(nxt[1]), nxt[2]))
            reqs.append(nxt)
    return lanes, reqs


@pytest.mark.parametrize("mix_name", ["decode"])
def test_seeds_share_the_lanes(mix_name):
    mix = _mix(mix_name)
    pool = traffic.shape_pool(mix)
    assert len(pool) == mix["pool"]
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    assert lo <= pool[:, 0].min() and pool[:, 0].max() <= hi
    seen = [_closed_loop(mix, seed, 5) for seed in (1, 2 ** 31 + 5)]
    # every seed gives each lane the same sequence of shapes (so a
    # window holds the same work), with other tokens and other clients
    # (request ids) on the lanes
    assert seen[0][0] == seen[1][0]
    assert [r[1] for r in seen[0][1]] != [r[1] for r in seen[1][1]]
    assert [r[0] for r in seen[0][1][:mix["clients"]]] == \
        list(range(mix["clients"]))
    # after the staggered first wave, two rounds of the lanes cover the
    # pool once
    later = collections.Counter(s for seq in seen[0][0].values()
                                for s in seq[1:3])
    assert later == collections.Counter(
        {tuple(map(int, s)): 1 for s in pool})


def test_stagger_scales_the_first_wave():
    mix = _mix("decode")
    lanes, _ = _closed_loop(mix, 3, 1)
    outs = [seq[0][1] for seq in lanes.values()]
    assert min(outs) < traffic.shape_pool(mix)[:, 1].min()
    assert all(n >= 1 and p >= 1 for seq in lanes.values()
               for p, n in seq)
    assert sorted(lanes) == list(range(mix["clients"]))


def test_rejects_a_mix_longer_than_the_cache():
    mix = _mix("decode")
    with pytest.raises(ValueError):
        traffic.Traffic(mix, 1, 1000, 500, _request)
    with pytest.raises(ValueError):
        traffic.quantiles({"dist": "normal", "min": 1, "max": 9}, 4)
    assert np.all(traffic.quantiles({"dist": "fixed", "min": 7}, 3) == 7)
    with pytest.raises(ValueError):
        traffic.Traffic(dict(mix, loop="open"), 1, 1000, 640, _request)
    with pytest.raises(ValueError):
        traffic.Traffic(dict(mix, clients=65), 1, 1000, 640, _request)
