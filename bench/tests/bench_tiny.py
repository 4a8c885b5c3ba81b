"""A tiny copy of the benchmark for tests on the CPU.

`tiny_root` copies ``BENCHMARK.json`` and ``bench/`` into a temporary
directory, links the program's sources beside them, and cuts every
configuration and traffic mix to a size the CPU runs in seconds. The
limits of the checks stay as committed.
"""
from __future__ import annotations

import json
import shutil
import sys
from argparse import Namespace
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.lib.registry import load_module  # noqa: E402

TINY = {"n": 4096, "d": 64, "k": 8}


def tiny_root(tmp: Path, **sizes) -> Path:
    root = Path(tmp) / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("out", "tests",
                                                  "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    (root / "src").symlink_to(ROOT / "src")
    for cfg in (root / "bench" / "configs").glob("*.json"):
        c = json.loads(cfg.read_text())
        c.update(TINY, **sizes)
        c["fit"]["b0"] = 256
        cfg.write_text(json.dumps(c))
    for mix in (root / "bench" / "traffic").glob("*.json"):
        t = json.loads(mix.read_text())
        if "rate_per_s" in t:
            t.update(rate_per_s=100, sizes=[1, 16, 128], pool_rows=2048)
        mix.write_text(json.dumps(t))
    # the driver's module is this copy's own, so its set-up and traced
    # lengths are cut here for this test alone
    drv = load_module(root / "bench" / "traffic" / "predict_open_loop.py")
    drv.WARMUP_CALLS, drv.WARMUP_SECONDS, drv.TRACE_SECONDS = 1, 0.2, 0.5
    return root


def run_cell(root: Path, workload: str, *, seed: int = 1,
             seconds: float = 0.3, trace: int = 0,
             trace_dir: Path | None = None) -> dict:
    from bench import run as bench_run
    from bench.lib.registry import Registry
    args = Namespace(workload=workload, seed=seed, seconds=seconds,
                     trace=trace)
    return bench_run.run(args, Registry(root), need_chip=False,
                         trace_dir=trace_dir or Path(root) / "trace")
