"""Public kernel ops, dispatched through a resolved `KernelPlan`.

Engines resolve a plan ONCE per fit (`plan.resolve_plan`) and pass it
down; every op here takes ``plan=`` and launches accordingly. Legacy
callers that still hold a backend STRING (serve snapshots,
`NestedKMeans.predict`) pass ``backend=`` instead and get a per-bucket
cached plan resolved on the spot — same dispatch rules, no second code
path. On the CPU pallas runs in interpret mode so the kernel bodies
execute exactly as written; on TPU they compile to Mosaic. ``"ref"``
routes to the pure-jnp oracle — the fast path on CPU and the semantic
baseline everywhere.
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.cluster_sum import cluster_sum_pallas
from repro.kernels.fused_round import (fused_nested_round_pallas,
                                       fused_nested_round_ref)
from repro.kernels.kmeans_assign import assign_top2_pallas
from repro.kernels.plan import LANE, KernelPlan, resolve_plan


def _plan_for(plan: KernelPlan | None, backend: str | None, n: int,
              k: int, d: int) -> KernelPlan:
    """A resolved plan wins; otherwise resolve one from the legacy
    backend string (or None = auto) at this call's shape bucket."""
    if plan is not None:
        return plan
    return resolve_plan(backend, b=n, k=k, d=d)


def assign_top2(x: jax.Array, c: jax.Array, *,
                plan: KernelPlan | None = None,
                backend: str | None = None):
    """(a, d1_sq, d2_sq): nearest / 2nd-nearest squared distances."""
    n, k = x.shape[0], c.shape[0]
    p = _plan_for(plan, backend, n, k, x.shape[1])
    if p.backend == "ref":
        return ref.assign_top2_ref(x, c)
    return assign_top2_pallas(x, c, bn=p.row_tile(n),
                              bk=min(p.bk, k + (-k % LANE)),
                              interpret=p.interpret)


def cluster_sum(x: jax.Array, a: jax.Array, k: int, *,
                weights: jax.Array | None = None,
                plan: KernelPlan | None = None,
                backend: str | None = None):
    """Weighted per-cluster sums S (k,d) and counts v (k,)."""
    p = _plan_for(plan, backend, x.shape[0], k, x.shape[1])
    if p.backend == "ref":
        return ref.cluster_sum_ref(x, a, k, weights=weights)
    return cluster_sum_pallas(x, a, k, weights=weights,
                              bn=p.row_tile(x.shape[0]), bd=p.bd,
                              interpret=p.interpret)


def fused_nested_round(x: jax.Array, c: jax.Array, a_prev: jax.Array,
                       settled: jax.Array, d_keep: jax.Array,
                       lb_keep: jax.Array, valid: jax.Array, *,
                       plan: KernelPlan | None = None):
    """Fused nested-round pass: assign + Hamerly keep-select + delta-S/v
    + sse in one sweep over x (see `fused_round.fused_nested_round_pallas`).

    Bound DECISIONS (the ``settled`` mask) stay with the caller
    (`core.rounds`) so the growth/bound schedule cannot drift between
    backends; this op only executes them.
    """
    n, k = x.shape[0], c.shape[0]
    p = _plan_for(plan, None, n, k, x.shape[1])
    if p.backend == "ref":
        return fused_nested_round_ref(x, c, a_prev, settled, d_keep,
                                      lb_keep, valid)
    return fused_nested_round_pallas(x, c, a_prev, settled, d_keep,
                                     lb_keep, valid,
                                     bn=p.row_tile(n),
                                     interpret=p.interpret)
