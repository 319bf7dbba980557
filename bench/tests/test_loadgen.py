import json
from pathlib import Path

import numpy as np
import pytest

import loadgen

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
BIG_SEED = 2**31 + 12345


def mix(name, **kw):
    d = json.loads((TRAFFIC / f"{name}.json").read_text())
    d.update(kw)
    return d


def keep_even_quads(ids):
    """A stand-in quality cut: keep ids whose quad index is even."""
    return (np.asarray(ids) // 4) % 2 == 0


def test_closed_loop_pool_fixed_shape_and_stagger():
    m = mix("batch")
    a = loadgen.closed_loop(m, 5, 100352, stagger=12)
    b = loadgen.closed_loop(m, 6, 100352, stagger=12)
    assert len(a) == m["pool"]
    assert sorted((len(q.prompt), q.max_new) for q in a) == \
        sorted((len(q.prompt), q.max_new) for q in b)
    p, n = m["prompt"]["value"], m["output"]["value"]
    assert all((len(q.prompt), q.max_new) == (p, n) for q in a[12:])
    steps = [len(q.prompt) + q.max_new - 1 for q in a[:12]]
    assert steps == sorted(steps) and steps[-1] == p + n - 1
    assert steps == [max(2, round((p + n - 1) * (i + 1) / 12))
                     for i in range(12)]


@pytest.mark.parametrize("seed", [1, BIG_SEED])
def test_sampled_plan_has_fixed_kept_counts_and_knotted_share(seed):
    m = mix("afdb_screen", plan_batches=3)
    plan = loadgen.campaign_plan(m, seed, 400, keep_even_quads)
    other = loadgen.campaign_plan(m, seed + 1, 400, keep_even_quads)
    assert plan == loadgen.campaign_plan(m, seed, 400, keep_even_quads)
    assert plan != other
    for a, b in zip(plan, other):
        assert len(a) == 400 and len(set(a)) == 400
        assert keep_even_quads(a).sum() == keep_even_quads(b).sum()
        knotted = [i for i in a if i % 4 != loadgen.COIL_CLASS]
        assert len(knotted) == round(m["knotted_share"] * 400)
        kk = keep_even_quads(knotted).sum()
        assert kk == keep_even_quads(
            [i for i in b if i % 4 != loadgen.COIL_CLASS]).sum()
    assert len({i for b in plan for i in b}) == 3 * 400


def test_campaign_orders_are_seeded_permutations_of_the_plan():
    m = mix("afdb_screen", plan_batches=4, batches_per_campaign=6)
    orders = loadgen.campaign_orders(m, 9)
    first = [next(orders) for _ in range(5)]
    again = loadgen.campaign_orders(m, 9)
    assert first == [next(again) for _ in range(5)]
    for o in first:
        assert len(o) == 6 and sorted(o[:4]) == [0, 1, 2, 3]
    assert len({tuple(o) for o in first}) > 1


def test_unknown_length_distribution_is_refused():
    with pytest.raises(ValueError):
        loadgen.lengths({"dist": "lognormal", "median": 64}, 3)
