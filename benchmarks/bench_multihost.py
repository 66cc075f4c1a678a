"""Multi-host selection: two coordinated processes vs one process exposing
the same devices.

The row tracked in ``BENCH_selection.json``:

  * ``selection/multihost_fl_n*_p2`` — gram-free facility-location greedy
    over the global ``sel`` mesh, run by TWO real jax processes (1 CPU
    device each, gloo collectives) launched through
    ``repro.testing.faults.launch_hosts``.  The derived fields assert the
    tentpole property alongside the timing: ``bit_identical_vs_single``
    compares indices AND gain bit patterns against a single-process run
    forcing 2 local devices (the same logical program, no coordination
    service), and ``hosts_agree`` checks both processes observed identical
    replicated results.  ``single_us`` is the single-process time for the
    same work, so the trajectory shows what cross-process dispatch costs.

``BENCH_FAST=1`` shrinks n/reps (CI smoke: the multihost-smoke job runs
this module explicitly and greps for the row).

CPU only, never on the chip path: the children are started after the
parent has imported JAX, and a chip belongs to one process, so every child
runs with ``JAX_PLATFORMS=cpu``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmarks.common import csv_row
from repro.testing.faults import launch_hosts

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: each child exposes ONE CPU device; the global mesh is 2 devices.  The
#: children must stay on the CPU: the parent may already hold the chip.
CHILD_ENV = {"PYTHONPATH": "src", "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}

BENCH_SCRIPT = r"""
import json, sys, time
out, n, k, reps = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4])
from repro.distributed import multihost
multihost.initialize()
import jax, numpy as np, jax.numpy as jnp
from repro.core import make_sharded_gram_free, sharded_greedy
from repro.core.similarity import normalize_rows
from repro.distributed.sharding import selection_mesh

assert jax.device_count() == 2, jax.device_count()
rng = np.random.default_rng(0)
z = normalize_rows(jnp.asarray(rng.normal(size=(n, 16)).astype(np.float32)))
mesh = selection_mesh()
fn = make_sharded_gram_free("facility_location", n_shards=2)
res = sharded_greedy(fn, z, k, mesh=mesh)          # warm the jit cache
jax.block_until_ready(res.gains)
t0 = time.perf_counter()
for _ in range(reps):
    res = sharded_greedy(fn, z, k, mesh=mesh)
    jax.block_until_ready(res.gains)
us = (time.perf_counter() - t0) / reps * 1e6
payload = {
    "us": us,
    "indices": np.asarray(res.indices).tolist(),
    "gains_bits": np.asarray(res.gains, np.float32).view(np.uint32).tolist(),
}
with open(f"{out}.{jax.process_index()}.json", "w") as f:
    json.dump(payload, f)
print("BENCH_DONE", jax.process_index())
"""


def _run_single(out: str, n: int, k: int, reps: int, timeout: float) -> dict:
    env = dict(os.environ)
    for var in ("MILO_COORDINATOR", "MILO_NUM_PROCESSES", "MILO_PROCESS_ID"):
        env.pop(var, None)
    env.update(CHILD_ENV)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    r = subprocess.run(
        [sys.executable, "-c", BENCH_SCRIPT, out, str(n), str(k), str(reps)],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT,
        timeout=timeout,
    )
    if r.returncode != 0:  # pragma: no cover
        raise RuntimeError(f"single-process reference failed: {r.stderr[-2000:]}")
    with open(f"{out}.0.json") as f:
        return json.load(f)


def _bench_two_process_selection(rows: list[str], verbose: bool, fast: bool) -> None:
    import tempfile

    n = 256 if fast else 1024
    k = 24 if fast else 64
    reps = 2 if fast else 5
    tmp = tempfile.mkdtemp()
    out2 = os.path.join(tmp, "two")

    t0 = time.perf_counter()
    results = launch_hosts(
        BENCH_SCRIPT, [out2, n, k, reps], num_processes=2,
        env=CHILD_ENV, cwd=REPO_ROOT, timeout=600.0)
    wall = time.perf_counter() - t0
    for r in results:
        if r.returncode != 0:  # pragma: no cover
            raise RuntimeError(
                f"process {r.process_id} failed: {r.stderr[-2000:]}")

    with open(f"{out2}.0.json") as f:
        p0 = json.load(f)
    with open(f"{out2}.1.json") as f:
        p1 = json.load(f)
    hosts_agree = (p0["indices"] == p1["indices"]
                   and p0["gains_bits"] == p1["gains_bits"])

    single = _run_single(os.path.join(tmp, "one"), n, k, reps, 600.0)
    identical = (p0["indices"] == single["indices"]
                 and p0["gains_bits"] == single["gains_bits"])

    rows.append(csv_row(
        f"selection/multihost_fl_n{n}_p2", p0["us"],
        f"k={k} reps={reps} single_us={single['us']:.1f} "
        f"hosts_agree={hosts_agree} bit_identical_vs_single={identical} "
        f"launch_wall_s={wall:.1f}"))
    if verbose:
        print(rows[-1])


def run(verbose: bool = True) -> list[str]:
    fast = os.environ.get("BENCH_FAST") == "1"
    rows: list[str] = []
    _bench_two_process_selection(rows, verbose, fast)
    return rows


if __name__ == "__main__":
    run()
