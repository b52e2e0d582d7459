"""Replays of a traffic generator's seeded choices, without a
database."""
import types

import harness
from reference import queries


def power_orders(seed: int, passes: int):
    """The query order of the first ``passes`` passes of the power
    traffic for ``seed``, as ``traffic/power.py`` draws it."""
    rng = harness.Context.rng(types.SimpleNamespace(seed=seed), 3)
    names = queries.names("array")
    return [[names[i] for i in rng.permutation(len(names))]
            for _ in range(passes)]
