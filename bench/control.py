"""Read the numbers that decide ``correct``, for the program and for its
control, over many seeds in one process. Not part of a benchmark run:
it is how each limit in ``configs/<config>.json`` was set.

    python bench/control.py --workload <cell> --seeds 1 2 3 [--seconds S]
        [--witness KEY=VALUE ...]

For each seed: the cell's set-up (data and warm-up), one window
(``--seconds``; a fit cell runs whole fits until then, at least one),
the check of the program's answers, then the check of the control put in the program's place (the
traffic driver's ``control``: the plain reference at the next precision
down). Prints one JSON line per seed and side, with every number the
check compares, and exits 2 with no TPU; the lines the limits were set
from are kept in ``bench/readings/<cell>.jsonl``. A mix's ``fixed_seeds``, which
hold every timed run's inputs fixed, are dropped here, so that each seed
makes its own inputs. ``--witness`` changes the configuration's
``FitConfig`` fields (such as ``bounds=none``) and reads the program
alone, as a second witness beside the configuration's own readings.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent))

from bench.lib.chip import require_chip  # noqa: E402
from bench.lib.registry import Registry  # noqa: E402
from bench.run import Context, log  # noqa: E402


def readings(registry: Registry, workload: str, seed: int,
             seconds: float, witness: dict | None = None) -> dict:
    cell = registry.cell(workload)
    config = cell.config
    if witness:
        config = {**config, "fit": {**config["fit"], **witness}}
    cell = dataclasses.replace(cell, config=config, traffic={
        k: v for k, v in cell.traffic.items() if k != "fixed_seeds"})
    mod = registry.driver(cell)
    driver = mod.make(Context(cell=cell, seed=seed, registry=registry,
                              log=log))
    driver.setup()
    driver.window(seconds, traced=False)
    limits = {n: float("inf") for n in
              cell.config["limits"][cell.traffic["driver"]]}
    out = {"workload": workload, "seed": seed, **({"witness": witness}
                                                  if witness else {})}
    t0 = time.perf_counter()
    out["program"] = driver.check(limits)[1]
    out["program_check_s"] = time.perf_counter() - t0
    if witness:
        return out
    mod.control(driver)
    out["control"] = driver.check(limits)[1]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--witness", nargs="*", default=[],
                    metavar="KEY=VALUE")
    args = ap.parse_args(argv)
    witness = dict(w.split("=", 1) for w in args.witness)
    registry = Registry(BENCH.parent)
    require_chip(registry.cell(args.workload).chips)
    from repro.util.env import enable_compile_cache
    enable_compile_cache()
    for seed in args.seeds:
        print(json.dumps(readings(registry, args.workload, seed,
                                  args.seconds, witness)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
