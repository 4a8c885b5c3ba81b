#!/usr/bin/env bash
# Fast tier-1 loop: the tier-1 pytest command restricted to the fast
# subset (tests not marked "slow"), so the edit-test loop stays under
# ~2 minutes. An UNSCOPED invocation additionally runs the mesh
# kill-and-resume subprocess test (slow-marked but checkpoint-critical)
# under its own 10-minute budget; passing any pytest args skips it.
# The full tier-1 command remains
#     PYTHONPATH=src python -m pytest -x -q
# and is what CI gates on; this script is the developer inner loop.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

# Fail loudly if the package is not importable (e.g. src/ missing or a
# clobbered PYTHONPATH) — otherwise pytest "passes" by collecting
# nothing from the api/engine tests.
if ! python -c "import repro" 2>/dev/null; then
    echo "error: cannot import 'repro' with PYTHONPATH=src —" \
         "run from the repo root with src/ present" >&2
    exit 1
fi

# static gate first: the AST lint is sub-second and catches the
# replicated-control-flow regressions before any test spends minutes.
# (The runtime auditors + selftests run in the unscoped block below.)
bash scripts/ci_static.sh lint

python -m pytest -x -q -m "not slow" "$@"

# kill-and-resume must stay green in the inner loop too — but only on
# unscoped runs, so `ci_tier1.sh -k foo` stays a fast scoped loop.
if [ "$#" -eq 0 ]; then
    timeout 600 python -m pytest -x -q tests/test_resume.py \
        -k test_mesh_resume_subprocess
    # the repro.serve concurrency tests are fast (no slow marker) and
    # already ran above; re-assert them by name so a future slow-marking
    # can't silently drop the serving path from the inner loop.
    timeout 600 python -m pytest -x -q tests/test_serve.py
    # the XL engine e2e (slow-marked subprocess smoke: fold parity,
    # run_loop bit-parity vs local/mesh, elastic XL<->local restore).
    # Outer budget > the test's own 600 s subprocess timeout, so a slow
    # smoke fails INSIDE pytest with its captured output, not as a bare
    # exit 124 from this wrapper.
    timeout 700 python -m pytest -x -q tests/test_distributed_xl.py
    # the multihost engine e2e (slow-marked subprocess smoke: 1-process
    # mesh<->multihost bit-parity, elkan-on-sharded parity, sharded
    # partial_fit, and a real 2-process jax.distributed CPU cluster
    # with identical control-flow traces + kill-one-process resume).
    # Outer budget > the test's own 900 s subprocess timeout.
    timeout 1000 python -m pytest -x -q tests/test_multihost.py
    # the out-of-core data plane (fast format/source/fit-parity tests
    # ran above; this adds the slow-marked subprocess smoke: stored-fit
    # bit-parity on local/mesh/xl/multihost, kill-and-resume from disk,
    # the dataset-fingerprint resume gate, and a 2-process cluster
    # streaming off one store directory).
    timeout 1000 python -m pytest -x -q tests/test_store.py
    # the observability plane (fast tracer/registry/instrumented-fit
    # tests ran above; this adds the slow-marked subprocess smoke:
    # traced fits on all four backends where the event log must parse
    # and its round count must equal the loop's own schedule trace,
    # plus lint + hostsync staying green on the INSTRUMENTED loop).
    timeout 700 python -m pytest -x -q tests/test_obs.py
    # the kernel dispatch plane (fast plan/parity/tile-rule tests ran
    # above; this adds the slow-marked subprocess smoke: fused-round op
    # parity, pallas-vs-ref fit bit-parity on local tb/gb and XL
    # m=2/m=1, and retrace + hostsync green with the plan active).
    timeout 700 python -m pytest -x -q tests/test_kernels.py
    # the bound families (fast parity/boundary-tie/resume tests ran
    # above; this adds the slow-marked subprocess smoke: exponion ==
    # none on local/mesh/xl/multihost incl. degenerate rings,
    # cross-backend bit-parity with exact-annulus pair counts, mesh
    # kill-and-resume, and the auditors green with exponion).
    timeout 1000 python -m pytest -x -q tests/test_bounds_smoke.py
    # full static + invariant gate: ruff (if installed), the runtime
    # auditors (hostsync / retrace / donation) across backends, and the
    # planted-bug selftests proving every checker still has teeth.
    timeout 900 bash scripts/ci_static.sh
fi
