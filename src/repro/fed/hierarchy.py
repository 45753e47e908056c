"""Two-tier hierarchical aggregation: edge cohorts pre-reduce before the WAN.

Flat FedAvg uplinks every client's update across the WAN — per-round WAN
bytes grow linearly in the participant count.  The two-tier topology
interposes edge aggregators: each cohort of clients uplinks over a cheap
LAN/MAN hop to its edge, the edge pre-reduces the cohort's uplinks through
the edge form of the server reduce (``fed/aggregate.make_server_reduce``
with ``edge=True``), and only ONE tree per cohort crosses the WAN.  WAN
bytes drop by the cohort fan-in factor, and — because FedAvg is a weighted
mean — the weighted-mean-of-weighted-means with cohort weights equal to
the member weight sums reproduces the flat aggregate exactly (up to float
reassociation, which is why the engine pin is tolerance-based, not
bit-exact).

The engine stays the single owner of virtual time and byte accounting;
this module only knows how to group clients and reduce a cohort.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

__all__ = ["CohortReduction", "HierarchicalAggregator", "assign_cohorts"]


def assign_cohorts(client_ids: Sequence[str], num_cohorts: int,
                   cohort_of=None) -> Dict[int, List[str]]:
    """Group client ids into cohorts.

    With ``cohort_of`` (e.g. ``Roster.cohort_of_cid``) membership follows
    the roster's contiguous population ranges; otherwise ids are split into
    ``num_cohorts`` contiguous, balanced slices in schedule order — the
    deterministic default for materialized client lists."""
    n = max(1, int(num_cohorts))
    out: Dict[int, List[str]] = {}
    if cohort_of is not None:
        for cid in client_ids:
            out.setdefault(int(cohort_of(cid)) % n, []).append(cid)
        return out
    ids = list(client_ids)
    span = -(-len(ids) // n) if ids else 1
    for i, cid in enumerate(ids):
        out.setdefault(i // span, []).append(cid)
    return out


@dataclass(frozen=True)
class CohortReduction:
    """One edge's pre-reduced contribution to the WAN hop."""
    cohort: int
    aggregate: object            # weighted-mean tree over the cohort
    weight: float                # sum of member weights (server-side weight)
    members: Tuple[str, ...]     # client ids reduced into the aggregate


class HierarchicalAggregator:
    """Edge tier: groups a round's landed clients into cohorts and runs
    each cohort through one edge reduce."""

    def __init__(self, num_cohorts: int, *, cohort_of=None):
        if num_cohorts < 1:
            raise ValueError(f"num_cohorts must be >= 1, got {num_cohorts}")
        self.num_cohorts = int(num_cohorts)
        self.cohort_of = cohort_of

    def group(self, client_ids: Sequence[str]) -> Dict[int, List[str]]:
        return assign_cohorts(client_ids, self.num_cohorts, self.cohort_of)

    def reduce_all(self, updates: Dict[str, Tuple[object, float]], reduce
                   ) -> List[CohortReduction]:
        """Reduce a full round: ``updates`` maps client id -> (uplink,
        weight); ``reduce`` is a ``fed/aggregate.ServerReduce`` (the edge
        form), which takes the cohorts one after another.  Returns one
        reduction per non-empty cohort, in cohort order: the cohort's
        weighted mean as a full tree, weighted by the sum of its member
        weights (so the server's cohort-level weighted mean equals the
        flat client-level one)."""
        grouped = self.group(list(updates.keys()))
        out: List[CohortReduction] = []
        for c in sorted(grouped):
            members = grouped[c]
            for m in members:
                reduce.add(*updates[m])
            out.append(CohortReduction(
                int(c), reduce.result(),
                float(sum(updates[m][1] for m in members)), tuple(members)))
        return out
