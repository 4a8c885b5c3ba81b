"""The control, the plain reference computed with bfloat16 distances and
put in the program's place, comes out not correct; the program at the
same size comes out correct. At the configurations' own widths (d=784,
k=50) and as many rows as a test run holds."""
import json

import pytest

import bench_tiny
from bench.control import readings
from bench.lib.registry import Registry


@pytest.mark.parametrize("workload", ["infmnist_k50.predict",
                                      "infmnist_k50.fit"])
def test_control_fails_where_the_program_passes(workload, tmp_path):
    root = bench_tiny.tiny_root(tmp_path, n=16384, d=784, k=50)
    mix = root / "bench" / "traffic" / "predict_poisson.json"
    t = json.loads(mix.read_text())
    t.update(pool_rows=16384)
    mix.write_text(json.dumps(t))
    reg = Registry(root)
    cell = reg.cell(workload)
    limits = cell.config["limits"][cell.traffic["driver"]]
    got = readings(reg, workload, seed=2**32 + 3, seconds=2.0)
    assert all(got["program"][n] <= lim for n, lim in limits.items()), got
    assert any(got["control"][n] > lim for n, lim in limits.items()), got
