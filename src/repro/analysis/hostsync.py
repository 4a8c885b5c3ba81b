"""Host-sync auditor: run a real fit and prove the steady-state loop
performs ZERO device->host syncs outside the sanctioned crossings.

`repro.api.loop.run_loop` brackets every round with
`LoopAudit.round_scope()` and each sanctioned crossing with
`sanctioned_scope(what)` (round_info / eval_mse / sync_flag /
checkpoint).  `HostSyncAudit` subclasses that seam: inside a round and
outside a sanctioned scope, any device->host materialisation is
recorded as a violation with the CALLER's file:line.

Two detection layers, because one is blind on CPU:

  * `jax.transfer_guard_device_to_host("disallow")` — authoritative on
    accelerators, but CPU jax arrays are zero-copy views of host
    memory, so d2h "transfers" never fire there;
  * a Python-level interceptor patched onto the runtime array type's
    conversion surface (``_value``/``__float__``/``__int__``/
    ``__bool__``/``__index__``/``item``/``tolist``/``__array__``) —
    this is how every host coercion in Python-land actually lands
    (``float(x)``, ``np.asarray(x)``, ``if x:``), and it works on
    every platform.  Tracers are a different type, so jit tracing is
    never intercepted.

The audited fit runs AFTER an identical unaudited warm-up fit, so every
bucket executable is already compiled and the audit sees the steady
state, not compilation. Host->device transfers are left ungated: data
growth legitimately places new rows mid-fit (`_ensure_prefix`).

The historical bug class (PR 2): a schedule decision read off a live
device scalar per round — correct results, but every round stalled the
dispatch pipeline.  `selftest()` replants it and asserts the auditor
still catches it.
"""
from __future__ import annotations

import contextlib
import traceback
from pathlib import Path
from typing import List, Optional

from repro.analysis.report import Violation, rel, repo_root
from repro.api.loop import LoopAudit

#: conversion surface intercepted on the runtime array type.
_HOOKS = ("__float__", "__int__", "__bool__", "__index__", "item",
          "tolist", "__array__")


class HostSyncAudit(LoopAudit):
    """Records unsanctioned device->host syncs instead of raising, so
    one audited fit reports every violation site at once."""

    def __init__(self, label: str = "fit"):
        self.label = label
        self.violations: List[Violation] = []
        self._in_round = 0
        self._sanctioned = 0

    # -- LoopAudit seam ------------------------------------------------------

    @contextlib.contextmanager
    def round_scope(self):
        import jax
        self._in_round += 1
        try:
            with jax.transfer_guard_device_to_host("disallow"):
                yield
        finally:
            self._in_round -= 1

    @contextlib.contextmanager
    def sanctioned_scope(self, what: str):
        import jax
        self._sanctioned += 1
        try:
            with jax.transfer_guard_device_to_host("allow"):
                yield
        finally:
            self._sanctioned -= 1

    # -- interceptor plumbing ------------------------------------------------

    @property
    def active(self) -> bool:
        return self._in_round > 0 and self._sanctioned == 0

    def notify(self, kind: str) -> None:
        if not self.active:
            return
        file, line, qual, snippet = _caller_site()
        v = Violation(checker="hostsync", kind=f"d2h-{kind}",
                      file=file, line=line, qualname=qual,
                      detail=(f"unsanctioned device->host sync in the "
                              f"steady-state loop ({self.label}): "
                              f"{snippet}"))
        if v not in self.violations:
            self.violations.append(v)

    @contextlib.contextmanager
    def installed(self):
        _active.append(self)
        _ensure_patched()
        try:
            yield self
        finally:
            _active.remove(self)
            if not _active:
                _unpatch()


_active: List[HostSyncAudit] = []
_saved = {}


def _caller_site():
    """Deepest stack frame inside this repo (and outside this module):
    the code that triggered the sync."""
    here = str(Path(__file__).resolve())
    root = str(repo_root())
    for f in reversed(traceback.extract_stack()):
        fn = str(Path(f.filename).resolve()) if f.filename else ""
        if fn == here or "/jax/" in fn or "/numpy/" in fn:
            continue
        if fn.startswith(root):
            return (rel(fn), f.lineno, f.name,
                    (f.line or "").strip() or "<unknown>")
    return ("<outside-repo>", 0, "?", "?")


def _notify_all(kind: str) -> None:
    for audit in _active:
        audit.notify(kind)


def _array_type():
    import jax
    import numpy as np

    return type(jax.device_put(np.zeros(())))


def _ensure_patched() -> None:
    if _saved:
        return
    cls = _array_type()
    for name in _HOOKS:
        orig = getattr(cls, name, None)
        if orig is None:
            continue

        def wrapper(self, *a, __orig=orig, __kind=name, **kw):
            _notify_all(__kind.strip("_"))
            return __orig(self, *a, **kw)

        _saved[name] = orig
        setattr(cls, name, wrapper)
    # numpy reaches CPU array memory through the `_value` property
    # (np.asarray / device_get), bypassing __array__ — intercept it too
    prop = getattr(cls, "_value", None)
    if isinstance(prop, property) and prop.fget is not None:
        orig_fget = prop.fget

        def fget(self, __orig=orig_fget):
            _notify_all("value")
            return __orig(self)

        _saved["_value"] = prop
        setattr(cls, "_value", property(fget, prop.fset, prop.fdel))


def _unpatch() -> None:
    if not _saved:
        return
    cls = _array_type()
    for name, orig in _saved.items():
        setattr(cls, name, orig)
    _saved.clear()


# -- audit driver ------------------------------------------------------------

def audit_backend(backend: str = "local", *, n: int = 2048, d: int = 8,
                  k: int = 8, seed: int = 0, engine_factory=None,
                  trace_dir: Optional[str] = None,
                  kernel_backend: Optional[str] = None,
                  bounds: str = "hamerly2") -> List[Violation]:
    """Warm up, then run one audited fit on ``backend``; returns the
    unsanctioned-sync violations. ``engine_factory`` overrides engine
    construction (the selftest injects a leaky engine). ``trace_dir``
    attaches a `repro.obs.FitObserver` to the AUDITED fit — proving the
    observability plane adds no device->host syncs of its own (the
    PR 8 acceptance gate: hostsync stays green with tracing on).
    ``kernel_backend`` forces the kernel plan ("pallas" proves the fused
    dispatch adds no syncs — `scripts/smoke_kernels.py`); ``bounds``
    selects the bound family (`scripts/smoke_bounds.py` proves the
    exponion geometry rebuild syncs nothing)."""
    import numpy as np

    from repro.api.config import FitConfig
    from repro.api.engines import make_engine
    from repro.api.loop import run_loop
    from repro.analysis.retrace import _mesh_for

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    X_val = rng.normal(size=(256, d)).astype(np.float32)
    config = FitConfig(k=k, b0=max(2 * k, n // 32), seed=seed,
                       backend=backend, max_rounds=24, eval_every=4,
                       capacity_floor=32, bounds=bounds,
                       kernel_backend=kernel_backend).resolve(n)

    def fit(audit: Optional[HostSyncAudit], obs=None):
        if engine_factory is not None:
            engine = engine_factory(config)
        else:
            engine = make_engine(config, mesh=_mesh_for(backend, config))
        run = engine.begin(X, config, X_val=X_val)
        return run_loop(run, config, audit=audit, obs=obs)

    fit(None)                       # compile every bucket un-audited
    obs = None
    if trace_dir is not None:
        import jax

        from repro.obs import FitObserver
        obs = FitObserver(trace_dir, process_id=jax.process_index(),
                          k=k, d=d,
                          device_kind=jax.devices()[0].device_kind,
                          meta={"backend": backend, "audit": "hostsync"})
    audit = HostSyncAudit(label=f"backend={backend}")
    try:
        with audit.installed():
            fit(audit, obs=obs)
    finally:
        if obs is not None:
            obs.close()
    return audit.violations


def selftest() -> List[Violation]:
    """Replant the PR 2 bug class (per-round branch on a live device
    scalar) and assert the auditor flags it at the planted file:line."""
    from repro.analysis import _selftest as fx
    return fx.hostsync_fixture_violations(audit_backend)
