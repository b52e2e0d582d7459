"""The comparison that decides ``correct``: a served answer against the
reference's answer (``reference.oracle.answer``) for the same data.

Every comparison is exact: the configurations state integer answers,
bit-equal. Masks compare row by row. The program's mask of a relation
indexes its storage slots; ``slots`` maps a relation that refreshes
have changed to the slot of each of its live rows, in the reference's
row order, as the system held them when it answered. A relation that
``slots`` leaves out stores its rows in row order.
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Optional

import numpy as np

#: Share of the answers after the first of each query that a run keeps
#: for the comparison (drawn from the seed); at most one more per query
#: of the mix is kept.
SAMPLE_SHARE = 0.05


def keep(first: bool, rng: np.random.Generator, n_kept: int,
         n_queries: int) -> bool:
    """Whether a run keeps an answer: the first answer of every query,
    then a seeded ``SAMPLE_SHARE`` of the rest, up to ``2 * n_queries``
    answers in all."""
    return first or (rng.random() < SAMPLE_SHARE
                     and n_kept < 2 * n_queries)


def _mask_difference(rel: str, have: np.ndarray, want: np.ndarray,
                     slots: Optional[np.ndarray]) -> Optional[str]:
    if slots is None:
        n = max(have.size, want.size)
        a = np.zeros(n, bool)
        b = np.zeros(n, bool)
        a[:have.size], b[:want.size] = have, want
    else:
        if slots.size != want.size:
            return (f"{rel}: the system holds {slots.size} live rows, the "
                    f"reference {want.size}")
        inside = slots < have.size
        a = np.zeros(want.size, bool)
        a[inside] = have[slots[inside]]
        b = want
        extra = int(have.sum()) - int(a.sum())     # selected dead slots
        if extra:
            return f"{rel} mask selects {extra} slots of no live row"
    wrong = int((a != b).sum())
    return f"{rel} mask differs in {wrong} rows" if wrong else None


def differences(got: dict, want: dict,
                slots: Optional[Mapping[str, np.ndarray]] = None
                ) -> List[str]:
    """Why ``got`` is not ``want``; empty when they agree."""
    slots = slots or {}
    out: List[str] = []
    if want["aggregates"] is not None and got["aggregates"] != \
            want["aggregates"]:
        out.append(f"aggregates {got['aggregates']} != {want['aggregates']}")
    if want["rows"] is not None:
        if got["rows"] != want["rows"]:
            out.append(f"rows differ ({len(got['rows'] or ())} rows vs "
                       f"{len(want['rows'])})")
        return out
    for rel, mask in want["masks"].items():
        have = got["masks"].get(rel)
        if have is None:
            out.append(f"no {rel} mask")
            continue
        diff = _mask_difference(rel, np.asarray(have, bool), mask,
                                slots.get(rel))
        if diff:
            out.append(diff)
    return out


def table_differences(got: Dict[str, np.ndarray],
                      want: Dict[str, np.ndarray]) -> int:
    """Rows in which two tables ({attribute: column}) differ, counting
    every row past the shorter one."""
    n_got = len(next(iter(got.values()))) if got else 0
    n_want = len(next(iter(want.values()))) if want else 0
    n = min(n_got, n_want)
    bad = np.zeros(n, bool)
    for a in want:
        if a not in got:
            return max(n_got, n_want)
        bad |= np.asarray(got[a][:n]) != np.asarray(want[a][:n])
    return int(bad.sum()) + abs(n_got - n_want)
