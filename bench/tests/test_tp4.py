"""A cell with `chips: 4`, on four CPU devices: its files are all under
`bench/tests/data/`, and the harness runs it on a 4-chip serving mesh
with no edit of its own. A subprocess sets
`--xla_force_host_platform_device_count=4` before JAX is imported (as
`tests/test_mesh_serving.py` does) and runs the `tiny.tp4` cell, then the
same configuration as a one-chip cell, on one seed. The mesh run must be
correct, report 4 devices, compile nothing in its window, hold every
nested linear, the embedding and the LM head a quarter to a chip with no
leaf on one chip alone, and serve the one-chip run's FP16 tokens bit for
bit (FP16 GEMMs give the same tokens on a mesh)."""

import json
import math
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = r"""
import json, sys
from pathlib import Path
sys.path[:0] = [sys.argv[1] + "/src", sys.argv[1]]
import jax
import bench.run as R
from bench import weights

DATA = Path(sys.argv[1]) / "bench" / "tests" / "data"
SEED = 2**33 + 4242
placement, served = [], []
draw = weights.serving_params
def keep(seed, cfg, mesh=None):
    tree = draw(seed, cfg, mesh)
    placement.append({
        jax.tree_util.keystr(p): [list(x.shape),
                                  list(x.addressable_shards[0].data.shape),
                                  len(x.sharding.device_set), str(x.dtype)]
        for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]})
    return tree
weights.serving_params = keep
sample = R.sample_finished
def record(reqs, seed):
    served.append({r["req"].request_id: list(r["req"].output)
                   for r in reqs if r["req"].finished_s is not None})
    return sample(reqs, seed)
R.sample_finished = record
runs = []
for cell in ("tiny.tp4", "tiny_qknorm.chat"):
    logs = []
    out = R.run_once(cell, SEED, 2.0, False,
                     bench_json=DATA / "BENCHMARK.json", allow_cpu=True,
                     log=logs.append)
    rec = next(json.loads(l) for l in logs if l.startswith("{"))
    runs.append({"out": out, "window_compiles": rec["window_compiles"]})
print(json.dumps({"runs": runs, "placement": placement, "served": served}))
"""


def test_four_chip_cell_runs_on_a_mesh():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT)],
                       capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    mesh, one = res["runs"]
    assert mesh["out"]["correct"], mesh["out"]["checks"]
    assert mesh["out"]["device"]["count"] == 4
    assert mesh["window_compiles"] == 0
    assert one["out"]["correct"] and one["out"]["device"]["count"] == 1

    quartered = 0
    for path, (whole, shard, devices, dtype) in res["placement"][0].items():
        assert devices == 4, path                # no leaf on one chip alone
        if dtype == "uint8" or "embed" in path or "lm_head" in path:
            # nested planes, embedding and LM head: a quarter a chip
            assert 4 * math.prod(shard) == math.prod(whole), path
            quartered += 1
    assert quartered == 7 * 2 + 2               # 7 linears x 2 planes
    assert all(d == 1 for _, _, d, _ in res["placement"][1].values())

    on_mesh, on_one = res["served"]
    both = set(on_mesh) & set(on_one)
    assert len(both) >= 8
    assert {k: on_mesh[k] for k in both} == {k: on_one[k] for k in both}
