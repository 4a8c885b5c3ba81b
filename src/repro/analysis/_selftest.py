"""Planted historical bug classes — the checkers' negative fixtures.

Each fixture replants a bug this repo actually shipped (and fixed), in
the exact shape a regression would take, so the selftests prove the
checkers still have teeth:

  * `LeakyRun` — the PR 2 class: a per-round schedule decision read off
    a live device scalar (branch + host coercion + ambient RNG).  The
    lint must flag its AST; the host-sync auditor must flag the sync at
    runtime with this file's line numbers.
  * `growing_update` / `replicated_smap_update` — the PR 6 class: a
    donated jit whose output cannot occupy the donated buffer (shape
    outgrows it / shard_map output replicated), so XLA silently copies.
  * `retrace_fixture_violations` — the rho-keyed retrace class: the
    same (b, capacity) bucket compiled once per round because a float
    hyperparameter rides in the jit key; plus an exact-need (non-pow2)
    capacity schedule.

This module is imported by the checkers' ``selftest()`` entry points
and by tests/test_analysis.py; it is NOT part of the production import
graph (importing it initialises jax).
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.report import Violation, rel
from repro.api.engines.local import _LocalRun, nested_jit

_HERE = rel(__file__)


# -- PR 2 class: device-scalar control flow ----------------------------------

class LeakyRun(_LocalRun):
    """A local run whose schedule leaks device state into host control
    flow — every pattern below is a planted lint/hostsync violation."""

    def nested_step(self, state, b, capacity):
        # branch + float() coercion on a live device scalar: one hidden
        # device->host sync per round, and divergent control flow on a
        # multi-process run
        if float(jnp.max(state.stats.p)) > 1e9:
            b = max(1, b // 2)
        return super().nested_step(state, b, capacity)

    def mb_step(self, state, fixed):
        # ambient entropy: processes draw different numbers
        if np.random.random() < 2.0:
            pass
        return super().mb_step(state, fixed)

    def eval_mse(self, state):
        # .item() on device state without derivation from HostRoundInfo
        _ = state.stats.sse.item(0)
        return super().eval_mse(state)


class LeakyEngine:
    def begin(self, X, config, *, X_val=None, init_C=None):
        return LeakyRun(X, config, X_val, init_C)


def leaky_line(marker: str) -> int:
    """1-based line of the first planted occurrence of ``marker``."""
    from pathlib import Path
    for i, line in enumerate(
            Path(__file__).read_text().splitlines(), start=1):
        if marker in line and "marker" not in line:
            return i
    raise AssertionError(f"marker {marker!r} not found in fixture")


def hostsync_fixture_violations(audit_backend) -> List[Violation]:
    found = audit_backend(backend="local",
                          engine_factory=lambda cfg: LeakyEngine())
    planted = [v for v in found if v.file == _HERE]
    if not planted:
        raise AssertionError(
            "hostsync selftest: the planted device-scalar branch "
            f"(PR 2 bug class) was NOT flagged; got only: "
            f"{[str(v) for v in found]}")
    return planted


# -- PR 6 class: donated-but-copying jits ------------------------------------

#: donation that XLA cannot honour: the output outgrows the donated
#: buffer, so every call silently copies.
growing_update = jax.jit(
    lambda Xs: jnp.concatenate([Xs, Xs[:1]], axis=0), donate_argnums=0)


def replicated_smap_update(mesh, axis: str = "data"):
    """The literal PR 6 spelling: a shard_map'd donated segment writer
    whose out_specs replicate — per-device output shape != donated
    piece shape, so aliasing is impossible and the whole buffer copies
    on every segment write."""
    from jax.sharding import PartitionSpec as P

    def body(Xs, seg, at):
        upd = jax.lax.dynamic_update_slice(Xs, seg, (at, 0))
        return jax.lax.all_gather(upd, axis, axis=0, tiled=True)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P(axis), P()),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn, donate_argnums=0)


def donation_fixture_violations(audit_donated_jit) -> List[Violation]:
    line = leaky_line("jnp.concatenate([Xs, Xs[:1]]")
    found = audit_donated_jit(
        growing_update, (np.zeros((256, 16), np.float32),), donated=(0,),
        file=_HERE, line=line, qualname="growing_update")
    if len(jax.devices()) > 1:
        mesh = jax.make_mesh((len(jax.devices()),), ("data",))
        fn = replicated_smap_update(mesh)
        found += audit_donated_jit(
            fn, (np.zeros((256, 16), np.float32),
                 np.ones((64, 16), np.float32),
                 jnp.zeros((), jnp.int32)),
            donated=(0,), file=_HERE,
            line=leaky_line("def replicated_smap_update"),
            qualname="replicated_smap_update")
    if not found:
        raise AssertionError(
            "donation selftest: the planted copying donation (PR 6 bug "
            "class) was NOT flagged")
    return found


# -- retrace class: per-round cache keys -------------------------------------

def retrace_fixture_violations(trace_violations, lattice_violations
                               ) -> List[Violation]:
    from repro.core.state import init_state
    from repro.util import tracecount

    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(64, 4)).astype(np.float32))
    state = init_state(X, 4)

    # rho drifting per round keys the jit cache: same (b, capacity)
    # bucket, a fresh executable every round — the historical regression
    invoked = []
    before = tracecount.snapshot()
    for rho in (1.90, 1.91, 1.92):
        nested_jit(X, state, b=32, rho=rho, bounds="hamerly2",
                   capacity=16, use_shalf=True, plan=None)
        invoked.append((32, 16))
    diff = tracecount.diff(before)
    found = trace_violations(
        diff, invoked, "nested_round", site_file=_HERE,
        site_line=leaky_line("for rho in (1.90, 1.91, 1.92)"),
        qualname="retrace_fixture[rho-keyed]")

    # exact-need capacity: off the pow2 lattice, one executable per
    # distinct need value — unbounded cache growth
    found += lattice_violations(
        [(32, 24), (48, None)], 32, 64, site_file=_HERE,
        site_line=leaky_line("[(32, 24), (48, None)]"),
        qualname="retrace_fixture[off-lattice]")
    if not [v for v in found if v.kind == "retrace"]:
        raise AssertionError(
            "retrace selftest: the planted rho-keyed retrace was NOT "
            "flagged")
    if not [v for v in found if v.kind == "off-lattice-bucket"]:
        raise AssertionError(
            "retrace selftest: the planted off-lattice schedule was "
            "NOT flagged")
    return found
