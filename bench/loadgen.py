"""The one traffic generator. A traffic mix is a JSON file under
``bench/traffic/``; its ``kind`` picks the shape of the load:

* ``campaign`` — batches of structure ids for the knot campaign, closed
  loop, with a fixed share drawn from the knotted classes;
* ``closed`` — requests kept outstanding at a fixed count.

Every run seed gets the same set of sizes (a campaign's kept counts come
from the mix's own ``shape_seed``); token and structure ids come from the
run seed. Nothing here imports JAX or the program.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

COIL_CLASS = 1          # synthesize_batch keys the structure kind by id % 4


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of one run seed (any non-negative int)."""
    return np.random.default_rng([int(seed) % 2**63, *stream])


# ---------------------------------------------------------------------------
# knot campaigns
# ---------------------------------------------------------------------------


def campaign_plan(mix: dict, seed: int, b: int, keep) -> list[list[int]]:
    """The run's distinct batches: ``plan_batches`` lists of ``b`` structure
    ids. ``keep(ids)`` says which ids pass the quality cut.

    Every seed gets the same set of batch sizes. The ids are drawn from
    the run seed, but each batch keeps a count of chains, and of
    knotted-class chains, fixed by the shape seed: the program's writhe
    shapes follow the kept count, so a seed that changed the counts would
    change the work."""
    k = int(mix["plan_batches"])
    shape = np.random.default_rng([int(mix["shape_seed"]), 1])
    n_knot = round(float(mix["knotted_share"]) * b)
    p = float(mix["kept_share"])
    kept = shape.binomial(b - n_knot, p, k)
    kept_knot = shape.binomial(n_knot, p, k)
    rng = rng_for(seed, 1)
    used: set[int] = set()
    quads = int(mix["id_space"]) // 4

    def draw(knotted: bool, kept_: bool, n: int) -> list[int]:
        out: list[int] = []
        while len(out) < n:
            q = rng.integers(0, quads, 2 * (n - len(out)) + 8)
            cls = (rng.choice([0, 2, 3], len(q)) if knotted
                   else np.full(len(q), COIL_CLASS))
            ids = 4 * q + cls
            for i, ok in zip(ids.tolist(), keep(ids)):
                if bool(ok) == kept_ and i not in used and len(out) < n:
                    used.add(i)
                    out.append(i)
        return out

    plan = []
    for i in range(k):
        ids = (draw(True, True, int(kept_knot[i]))
               + draw(True, False, n_knot - int(kept_knot[i]))
               + draw(False, True, int(kept[i]))
               + draw(False, False, b - n_knot - int(kept[i])))
        plan.append([int(x) for x in rng.permutation(ids)])
    return plan


def campaign_orders(mix: dict, seed: int):
    """Batch order of each window campaign, forever: a seeded permutation
    of the plan per campaign, ``batches_per_campaign`` long (cycling the
    plan when a campaign is longer than it)."""
    rng = rng_for(seed, 2)
    k, per = int(mix["plan_batches"]), int(mix["batches_per_campaign"])
    while True:
        order: list[int] = []
        while len(order) < per:
            order.extend(int(x) for x in rng.permutation(k))
        yield order[:per]


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------


@dataclass
class Request:
    rid: str
    prompt: list[int]
    max_new: int


def lengths(dist: dict, n: int) -> np.ndarray:
    if dist["dist"] != "fixed":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.full(n, int(dist["value"]))


def _size_set(mix: dict, n: int) -> np.ndarray:
    """``n`` (prompt, output) pairs."""
    return np.stack([lengths(mix["prompt"], n), lengths(mix["output"], n)],
                    1)


def _materialize(sizes: np.ndarray, seed: int, vocab: int, tag: str
                 ) -> list[Request]:
    rng = rng_for(seed, 3, sum(map(ord, tag)))
    sizes = sizes[rng.permutation(len(sizes))]
    out = []
    for i, (p, o) in enumerate(sizes):
        prompt = rng.integers(0, vocab, int(p)).tolist()
        out.append(Request(f"{tag}{i}", prompt, int(o)))
    return out


def closed_loop(mix: dict, seed: int, vocab: int, stagger: int = 0
                ) -> list[Request]:
    """The request sequence a closed loop consumes in order: a fixed set of
    ``pool`` sizes, permuted by the seed. With ``stagger`` > 0, the first
    ``stagger`` requests are cut to evenly spaced shares of the mix's
    longest request, so that slots filled together finish at spread
    phases; the rest keep their sizes."""
    n = int(mix["pool"])
    reqs = _materialize(_size_set(mix, n), seed, vocab, "req")
    steps = max(len(q.prompt) + q.max_new - 1 for q in reqs)
    for i, q in enumerate(reqs[:stagger]):
        total = max(2, round(steps * (i + 1) / stagger))
        q.max_new = min(q.max_new, total - 1)
        q.prompt = q.prompt[:total + 1 - q.max_new]
        q.prompt += q.prompt[:1] * (total + 1 - q.max_new - len(q.prompt))
    return reqs
