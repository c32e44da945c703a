"""Candidate enumeration + costed selection for every dispatch decision.

Each ``plan_*`` resolver enumerates the legal candidate configurations
for one decision, prices each from :mod:`raft_tpu.plan.cost`, and
returns a :class:`Plan` — a typed, explainable record of the choice
with the per-term breakdown of every candidate (including the ones that
lost, and the ones that were ineligible and why). The winning ``choice``
is what the call site dispatches on; the rest is the audit trail
``plan.explain`` dumps into the obs report.

Selection is deterministic: candidates are priced in a fixed
enumeration order and the first strictly-cheapest eligible candidate
wins, so a cost tie resolves to the earlier (more conservative)
engine — the same discipline the wire model's ring/gather parity uses.

Parity contract: with the gate off (``RAFT_TPU_PLAN=0``) every call
site runs its original inline heuristic; with it on, the calibrated
crossovers in :mod:`raft_tpu.plan.cost` make each resolver select the
same configuration the heuristic did across the legacy decision
envelope (swept in ``tests/test_plan.py``), and an identical resolved
configuration drives byte-identical downstream code — so gates-off
results are bit-identical either way. Where the cost models see farther
than the old one-liners (e.g. a CA exchange whose row cap cannot
undercut the full exchange), the planner deviates *toward the models*;
those deviations are enumerated in ``docs/planner.md``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

from raft_tpu import obs
from raft_tpu.plan import cost as _cost
from raft_tpu.plan.cost import CostTerm


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One enumerated configuration for a decision, with its price."""

    name: str
    terms: Tuple[CostTerm, ...] = ()
    eligible: bool = True
    reason: str = ""  # why ineligible (shown in explain)

    @property
    def cost(self) -> float:
        if not self.eligible:
            return math.inf
        return sum(t.value for t in self.terms)

    def render(self) -> str:
        if not self.eligible:
            return f"x {self.name:<12} ineligible: {self.reason}"
        breakdown = " + ".join(t.render() for t in self.terms)
        return f"- {self.name:<12} {self.cost:10.2f} cu  [{breakdown}]"


@dataclasses.dataclass(frozen=True)
class Plan:
    """A resolved dispatch decision: the choice, every candidate's cost
    breakdown, and the inputs the costing read."""

    decision: str
    choice: str
    candidates: Tuple[Candidate, ...]
    inputs: Tuple[Tuple[str, object], ...] = ()

    @property
    def cost(self) -> float:
        for c in self.candidates:
            if c.name == self.choice:
                return c.cost
        return math.inf

    def candidate(self, name: str) -> Optional[Candidate]:
        for c in self.candidates:
            if c.name == name:
                return c
        return None

    def explain(self) -> str:
        lines = [f"plan {self.decision}: {self.choice}  ({self.cost:.2f} cu)"]
        if self.inputs:
            lines.append("  inputs: " + " ".join(f"{k}={v}" for k, v in self.inputs))
        for c in sorted(self.candidates, key=lambda c: c.cost):
            lines.append("  " + c.render())
        return "\n".join(lines)


def _decide(decision: str, candidates, inputs) -> Plan:
    """First strictly-cheapest eligible candidate wins (stable ties)."""
    cands = tuple(candidates)
    best = None
    for c in cands:
        if c.eligible and (best is None or c.cost < best.cost):
            best = c
    if best is None:  # caller enumerated no eligible engine — a bug
        raise ValueError(f"plan {decision}: no eligible candidate")
    if obs.is_enabled():
        obs.inc("plan.decisions", decision=decision, choice=best.name)
    return Plan(decision=decision, choice=best.name, candidates=cands,
                inputs=tuple(inputs))


# ---------------------------------------------------------------------------
# per-decision resolvers
# ---------------------------------------------------------------------------


def plan_search_mode(algo: str, nq: int, *, on_tpu: bool, fused_ok: bool,
                     wants_f32_lut: bool = False) -> Plan:
    """IVF engine pick (``probe`` | ``scan`` | ``fused``) for one batch
    of ``nq`` queries. ``fused_ok`` is the call site's kernel-eligibility
    verdict (metric/codebook support and the vmem_model feasibility
    check); ``wants_f32_lut`` is the explicit-precision demand the bf16
    fused LUT cannot honor."""
    reasons = []
    if not on_tpu:
        reasons.append("backend is not tpu")
    if not fused_ok:
        reasons.append("kernel infeasible (metric/codebook/vmem window)")
    if wants_f32_lut:
        reasons.append("explicit f32 LUT demand (bf16 kernel LUT)")
    return _decide(
        f"{algo}.search_mode",
        [
            Candidate("probe", _cost.search_mode_terms("probe", nq)),
            Candidate("scan", _cost.search_mode_terms("scan", nq)),
            Candidate("fused", _cost.search_mode_terms("fused", nq),
                      eligible=not reasons, reason="; ".join(reasons)),
        ],
        [("nq", nq), ("on_tpu", on_tpu), ("fused_ok", fused_ok)],
    )


def plan_cagra_mode(nq: int, *, on_tpu: bool, fused_ok: bool,
                    why_not_fused: str = "") -> Plan:
    """CAGRA beam engine pick (``xla`` | ``fused``) for ``nq`` queries.
    ``why_not_fused`` is ``cagra.fused_ineligible_reason``'s verdict."""
    reasons = []
    if not on_tpu:
        reasons.append("backend is not tpu")
    if not fused_ok:
        reasons.append(why_not_fused or "needs raw dataset, init_sample>0, "
                       "dedup='post', no prefilter, graph_degree<=dim")
    return _decide(
        "cagra.search_mode",
        [
            Candidate("xla", _cost.cagra_mode_terms("xla", nq)),
            Candidate("fused", _cost.cagra_mode_terms("fused", nq),
                      eligible=not reasons, reason="; ".join(reasons)),
        ],
        [("nq", nq), ("on_tpu", on_tpu), ("fused_ok", fused_ok)],
    )


def plan_merge_mode(n_shards: int, k: Optional[int] = None,
                    tile_width: Optional[int] = None) -> Plan:
    """Cross-shard merge engine pick (``gather`` | ``ring`` |
    ``fused_ring``). ``tile_width`` is the per-shard candidate width
    entering the merge (defaults to ``k`` — the classic call sites,
    where the scan has already folded to k)."""
    k = int(k) if k else 10  # nominal: the winner is k-independent
    width = int(tile_width) if tile_width else k
    single = n_shards <= 1
    return _decide(
        "merge_mode",
        [
            Candidate("gather", _cost.merge_mode_terms("gather", n_shards, k, width)),
            Candidate("ring", _cost.merge_mode_terms("ring", n_shards, k, width),
                      eligible=not single, reason="single shard: nothing to exchange"),
            Candidate("fused_ring",
                      _cost.merge_mode_terms("fused_ring", n_shards, k, width),
                      eligible=not single, reason="single shard: nothing to exchange"),
        ],
        [("n_shards", n_shards), ("k", k), ("tile_width", width)],
    )


def plan_comm_mode(n_rows: int, d: int, n_shards: int, ca_cap=None) -> Plan:
    """Distributed-build accumulator exchange pick (``full`` | ``ca``)
    over ``[n_rows, d+1]`` f32 accumulator rows per iteration."""
    return _decide(
        "comm_mode",
        [
            Candidate("full", _cost.comm_mode_terms("full", n_rows, d, n_shards)),
            Candidate("ca", _cost.comm_mode_terms("ca", n_rows, d, n_shards,
                                                  ca_cap=ca_cap)),
        ],
        [("n_rows", n_rows), ("d", d), ("n_shards", n_shards)],
    )


def plan_delta_mode(*, eligible: bool, on_tpu: bool) -> Plan:
    """Mutable delta-scan engine pick (``exact`` | ``fused``).
    ``eligible`` is ``segments._delta_fused_eligible``'s verdict (metric
    window, banked row cap, k width)."""
    reasons = []
    if not eligible:
        reasons.append("metric/cap/k outside the lossless banked window")
    if not on_tpu:
        reasons.append("backend is not tpu")
    return _decide(
        "delta_mode",
        [
            Candidate("exact", _cost.delta_mode_terms("exact")),
            Candidate("fused", _cost.delta_mode_terms("fused"),
                      eligible=not reasons, reason="; ".join(reasons)),
        ],
        [("eligible", eligible), ("on_tpu", on_tpu)],
    )


def plan_pq_kind(pq_bits: int, per_subspace: bool, pq_dim: int = 16) -> Plan:
    """PQ code-family pick (``rabitq`` | ``nibble`` | ``kmeans``) at
    build time. Representability is eligibility; among representable
    families the decode-throughput terms decide."""
    pq_dim = max(1, int(pq_dim))
    return _decide(
        "pq_kind",
        [
            Candidate("rabitq", _cost.pq_kind_terms("rabitq", pq_dim, 1),
                      eligible=pq_bits == 1,
                      reason="1 bit/dim only (pq_bits != 1)"),
            Candidate("nibble", _cost.pq_kind_terms("nibble", pq_dim, pq_bits),
                      eligible=pq_bits == 8 and per_subspace,
                      reason="needs pq_bits=8 and per_subspace codebooks"),
            # kmeans is the fallback family: it stays eligible for
            # out-of-range pq_bits so the call site's own validation
            # raises the canonical error, not the planner
            Candidate("kmeans", _cost.pq_kind_terms("kmeans", pq_dim, pq_bits),
                      eligible=pq_bits != 1,
                      reason="1 bit/dim is rabitq's encoding"),
        ],
        [("pq_bits", pq_bits), ("per_subspace", per_subspace)],
    )


def plan_sparse_mode(n_cols: int, *, native_ok: bool) -> Plan:
    """Sparse pairwise engine pick (``densify`` | ``native``) at feature
    width ``n_cols``."""
    return _decide(
        "sparse_mode",
        [
            Candidate("densify", _cost.sparse_mode_terms("densify", n_cols)),
            Candidate("native", _cost.sparse_mode_terms("native", n_cols),
                      eligible=native_ok,
                      reason="metric has no sort-merge gram path"),
        ],
        [("n_cols", n_cols), ("native_ok", native_ok)],
    )
