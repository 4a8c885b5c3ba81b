"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

Everything about the cell comes from ``BENCHMARK.json`` and the files it
names (see ``bench/lib/registry.py``); nothing here names a
configuration, a traffic mix, a cell or a metric.

One process holds the chip. It exits with code 2, printing no result,
when JAX finds no TPU or fewer chips than the cell asks for. Compiles go
to the program's persistent cache (``repro.util.env.enable_compile_cache``,
which honours ``JAX_COMPILATION_CACHE_DIR``).

A run: set-up (data, warm-up: ``setup_s`` runs from process start until
the window opens); the window of ``--seconds``, untraced with
``--trace 0`` and reporting the cell's end-to-end metrics, or traced
with ``--trace 1`` and reporting its per-layer metrics; the peak device
memory; then, with the program's state freed, the reference check of
every answer the window produced. The numbers compared are printed
beside their limits as the last lines of standard error, and under
``checks``, last, in the result line: the last line of standard output,
one JSON object with ``correct``, ``attempted``, ``failed``, ``metrics``
and ``device`` (and ``breakdown`` when traced).

A traffic driver (``bench/traffic/<driver>.py``) provides
``make(ctx)`` returning an object with ``setup()``,
``window(seconds, traced) -> {end-to-end metric: value}``,
``attempted``, ``check(limits) -> (failed, {number: value})`` and, for
the metric readers, its records of the window.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
from pathlib import Path
from typing import Any, Callable, Dict

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent))

from bench.lib.chip import (CompileLog, device_info,  # noqa: E402
                            process_age_s, require_chip)
from bench.lib.registry import Cell, Registry  # noqa: E402

OUT = BENCH / "out"
TRACE_DIR = OUT / "trace"


@dataclasses.dataclass
class Context:
    """What a traffic driver gets."""
    cell: Cell
    seed: int
    registry: Registry
    log: Callable[[str], None]


@dataclasses.dataclass
class Observations:
    """What a per-layer metric reader gets (``read(obs)``)."""
    cell: Cell
    driver: Any                 # the traffic driver, with its records
    trace: Any                  # bench.lib.trace.Trace
    window: tuple               # (start_ns, end_ns) of the traced window
    compile_setup: Dict[str, float]
    compile_window: Dict[str, float]
    peaks: Any                  # bench.lib.peaks.ChipPeaks


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(args, registry: Registry, *, need_chip: bool = True,
        trace_dir: Path = TRACE_DIR) -> Dict[str, Any]:
    """One run of one cell; returns the result object. ``need_chip``
    is off only in tests, which drive the rest of a run on the CPU."""
    cell = registry.cell(args.workload)
    # the TPU runtime logs here rather than under a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", str(OUT / "tpu_logs"))
    if need_chip:
        dev = require_chip(cell.chips)
    import jax
    from repro.util.env import enable_compile_cache
    if need_chip:
        log(f"compile cache: {enable_compile_cache()}")
    else:
        dev = jax.devices()[0]
    log(f"cell {cell.name} on {len(jax.devices())} x {dev.device_kind}")
    clog = CompileLog()
    driver = registry.driver(cell).make(
        Context(cell=cell, seed=args.seed, registry=registry, log=log))
    driver.setup()
    # the set-up's garbage is collected now and left out of later
    # collections, so that no pause for it falls inside the window
    gc.collect()
    gc.freeze()
    setup_s = process_age_s()
    compile_setup = clog.snapshot()
    log(f"set-up {setup_s:.3f} s; compile {compile_setup}")

    breakdown = None
    if args.trace:
        from bench.lib import trace as tr
        from bench.lib.peaks import PEAKS, peaks_for
        with tr.capture(trace_dir):
            with jax.profiler.TraceAnnotation("bench.window"):
                driver.window(args.seconds, traced=True)
        compile_window = clog.since(compile_setup)
        trace = tr.Trace.load(tr.find_xplane(trace_dir))
        window = trace.window("bench.window")
        obs = Observations(cell=cell, driver=driver, trace=trace,
                           window=window, compile_setup=compile_setup,
                           compile_window=compile_window,
                           peaks=(peaks_for(dev.device_kind) if need_chip
                                  else PEAKS.get(dev.device_kind)))
        values = {}
        for m in cell.per_layer:
            v = registry.metric(m["name"]).read(obs)
            if v is not None:
                values[m["name"]] = (v, m["unit"])
        breakdown = trace.breakdown(window)
        busy_s = trace.busy_ns(window) * 1e-9
        window_s = (window[1] - window[0]) * 1e-9
    else:
        values = driver.window(args.seconds, traced=False)
        compile_window = clog.since(compile_setup)
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = {n: (v, units[n]) for n, v in values.items() if n in units}
        values["setup_s"] = (setup_s, units["setup_s"])
    log(f"window: {driver.attempted} attempted; compile inside "
        f"{compile_window}")

    device = device_info(dev, cell.chips)
    if args.trace:
        device.update(busy_s=busy_s, window_s=window_s)
    limits = cell.config["limits"][cell.traffic["driver"]]
    failed, numbers = driver.check(limits)
    correct = (driver.attempted > 0 and failed == 0
               and set(numbers) >= set(limits)
               and all(numbers[n] <= limits[n] for n in limits))
    result = {
        "correct": bool(correct),
        "attempted": int(driver.attempted),
        "failed": int(failed),
        "metrics": {n: {"value": v, "unit": u}
                    for n, (v, u) in values.items()},
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {n: {"value": (float(numbers[n]) if n in numbers
                                      else None),
                            "limit": limits[n]} for n in limits}
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    result = run(args, Registry(BENCH.parent))
    print(f"correct = {result['correct']}; failed {result['failed']} of "
          f"{result['attempted']}", file=sys.stderr)
    for name, c in result["checks"].items():
        ok = c["value"] is not None and c["value"] <= c["limit"]
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
