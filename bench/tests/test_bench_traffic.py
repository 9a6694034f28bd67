"""The traffic generator: deterministic from the seed, the same work for every seed
in another order."""

import numpy as np
import pytest

from bench import common
from bench.traffic import generators

MIXES = ["chat", "rag"]


def mix(name):
    return common.load_json(common.BENCH / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    big = 2**31 + 12345
    a = generators.poisson_schedule(mix(name), big, 151936, 30)
    b = generators.poisson_schedule(mix(name), big, 151936, 30)
    assert [x.due for x in a] == [x.due for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_schedule_and_differ_in_tokens(name):
    """Two seeds offer the same sizes and gaps, each in its own order."""
    a = generators.poisson_schedule(mix(name), 1, 1000, 30)
    b = generators.poisson_schedule(mix(name), 2, 1000, 30)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    start = -mix(name)["preroll_s"]
    assert np.allclose(sorted(np.diff([start] + [x.due for x in a])),
                       sorted(np.diff([start] + [x.due for x in b])))
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    assert [x.due for x in a] != [x.due for x in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


@pytest.mark.parametrize("name", MIXES)
def test_every_block_offers_the_same_sizes(name):
    m = mix(name)
    arr = generators.poisson_schedule(m, 2**31 + 5, 1000, 51)
    k = m["block"]
    assert len(arr) % k == 0 and len(arr) >= 2 * k
    blocks = [arr[i:i + k] for i in range(0, len(arr), k)]
    first = sorted(x.max_new for x in blocks[0]), sorted(len(x.prompt) for x in blocks[0])
    for b in blocks[1:]:
        assert (sorted(x.max_new for x in b), sorted(len(x.prompt) for x in b)) == first
    assert [x.max_new for x in blocks[0]] != [x.max_new for x in blocks[1]]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_respect_the_mix(name):
    m = mix(name)
    arr = generators.poisson_schedule(m, 7, 1000, 30)
    p = np.array([len(x.prompt) for x in arr])
    o = np.array([x.max_new for x in arr])
    assert p.min() >= m["prompt"]["min"] and p.max() <= m["prompt"]["max"]
    assert o.min() >= m["output"]["min"] and o.max() <= m["output"]["max"]
    assert abs(np.median(p) - m["prompt"]["median"]) <= 0.1 * m["prompt"]["median"]
    assert all(x.prompt.dtype == np.int32 and x.prompt.max() < 1000 for x in arr)


@pytest.mark.parametrize("name", MIXES)
def test_every_request_fits_its_slot(name):
    m = mix(name)
    assert m["prompt"]["max"] + m["output"]["max"] <= m["engine"]["max_len"]


def test_poisson_rate_and_preroll():
    m = {**mix("chat"), "rate_per_s": 4.0, "preroll_s": 10}
    arr = generators.poisson_schedule(m, 3, 1000, 40)
    due = np.array([x.due for x in arr])
    assert due[0] < -9.0 and due[-1] > 40  # covers pre-roll, window and a tail
    in_window = ((due >= 0) & (due < 40)).sum()
    assert abs(in_window - 160) < 30
    assert np.all(np.diff(due) > 0)


def test_unknown_kind_is_refused():
    with pytest.raises(ValueError, match="unknown traffic kind"):
        generators.poisson_schedule({**mix("chat"), "kind": "backlog"}, 1, 1000, 30)
