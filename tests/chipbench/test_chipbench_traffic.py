"""The traffic generator: the same sizes for every seed, in another
order; the first wave of a staggered closed loop ends spread out."""
import json
from collections import Counter
from pathlib import Path
from statistics import NormalDist

import numpy as np
import pytest

from chipbench import traffic

MIXES = Path(__file__).resolve().parents[2] / "chipbench" / "traffic"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def take(m, seed, n):
    s = traffic.Schedule(m, seed, vocab_size=1000)
    return [s.take(0) for _ in range(n)]


@pytest.mark.parametrize("name", sorted(p.stem for p in MIXES.glob("*.json")))
def test_seeds_share_sizes(name):
    m = dict(mix(name), stagger=False)
    a, b = take(m, 1, 2 * m["block"]), take(m, 2**40 + 3, 2 * m["block"])
    assert (Counter(r.max_new_tokens for r in a)
            == Counter(r.max_new_tokens for r in b))
    assert (Counter(len(r.prompt) for r in a)
            == Counter(len(r.prompt) for r in b))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    for key, got in (("prompt", [len(r.prompt) for r in a]),
                     ("output", [r.max_new_tokens for r in a])):
        assert m[key]["min"] <= min(got) and max(got) <= m[key]["max"]
    assert all(len(r.prompt) + r.max_new_tokens
               <= traffic.Schedule(m, 1, 1000).longest() for r in a)


def test_stratified_lognormal_quantiles():
    """A block's values are the distribution's quantiles at
    (i + 0.5) / block, clipped: the median sits in the middle."""
    d = {"dist": "lognormal", "median": 1000, "sigma": 0.5, "min": 64,
         "max": 1500}
    s = traffic.Stratified(d, 33, 1, np.random.default_rng(0))
    v = sorted(s.values)
    assert v[16] == 1000
    assert v[0] == round(1000 * np.exp(0.5 * NormalDist().inv_cdf(0.5 / 33)))
    assert v[-1] == 1500 and v.count(1500) == 7


def test_groups_hold_one_value_of_each_stratum():
    d = {"dist": "uniform", "min": 0, "max": 31}
    s = traffic.Stratified(d, 32, 8, np.random.default_rng(5))
    assert s.values.tolist() == list(range(32))
    draws = [s() for _ in range(64)]
    for g in range(0, 64, 8):
        assert sorted(v // 4 for v in draws[g:g + 8]) == list(range(8))
    assert sorted(draws[:32]) == sorted(draws[32:]) == list(range(32))
    assert draws[:32] != draws[32:]
    with pytest.raises(ValueError, match="multiple"):
        traffic.Stratified(d, 32, 5, np.random.default_rng(5))


def test_same_seed_same_requests():
    m = mix("azure-conv-closed32")
    a, b = take(m, 7, 50), take(m, 7, 50)
    assert all(np.array_equal(x.prompt, y.prompt)
               and x.max_new_tokens == y.max_new_tokens for x, y in zip(a, b))


def test_stagger_spreads_the_first_wave():
    """With ``stagger``, client i's first request asks for the share
    (i + 0.5) / clients of its drawn length; later ones ask in full."""
    m = dict(mix("azure-conv-closed32"), output={
        "dist": "uniform", "min": 100, "max": 100})
    s = traffic.Schedule(m, 3, vocab_size=1000)
    first = [s.take(i).max_new_tokens for i in range(32)]
    assert first == [int(np.ceil(100 * (i + 0.5) / 32)) for i in range(32)]
    assert [s.take(i).max_new_tokens for i in range(32)] == [100] * 32
    m["stagger"] = False
    s = traffic.Schedule(m, 3, vocab_size=1000)
    assert [s.take(i).max_new_tokens for i in range(32)] == [100] * 32
