"""Tests for the trip-count-aware HLO cost model and roofline terms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_analysis as ha
from repro.launch import roofline


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_xla_cost_analysis_undercounts_scans():
    """Documents the motivating bug: XLA counts a scan body once."""

    def make(n):
        def f(x, w):
            def body(c, wi):
                return jnp.tanh(c @ wi), None

            y, _ = jax.lax.scan(body, x, w)
            return y.sum()

        return f

    x = jax.ShapeDtypeStruct((128, 64), jnp.float32)
    # n=4 and n=8 both compile to a while loop with an identical body; XLA
    # reports the same FLOPs for both — i.e. trip count is ignored.
    f4 = _compile(make(4), x, jax.ShapeDtypeStruct((4, 64, 64), jnp.float32))
    f8 = _compile(make(8), x, jax.ShapeDtypeStruct((8, 64, 64), jnp.float32))
    assert f4.cost_analysis()["flops"] == f8.cost_analysis()["flops"]


@pytest.mark.parametrize("n", [1, 4, 16])
def test_analyzer_counts_scan_flops_exactly(n):
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None

        y, _ = jax.lax.scan(body, x, w)
        return y.sum()

    x = jax.ShapeDtypeStruct((256, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((n, 128, 128), jnp.float32)
    t = ha.analyze(_compile(f, x, w).as_text())
    assert t["flops"] == pytest.approx(2 * 256 * 128 * 128 * n, rel=1e-6)
    if n > 1:
        assert n in t["while_trips"]


def test_analyzer_nested_scans():
    def f(x, w):
        def outer(c, wi):
            def inner(c2, _):
                return jnp.tanh(c2 @ wi), None

            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None

        y, _ = jax.lax.scan(outer, x, w)
        return y.sum()

    x = jax.ShapeDtypeStruct((128, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)
    t = ha.analyze(_compile(f, x, w).as_text())
    assert t["flops"] == pytest.approx(2 * 128 * 64 * 64 * 12, rel=1e-6)
    assert sorted(t["while_trips"]) == [3, 4]


def test_analyzer_bytes_are_positive_and_bounded():
    def f(a, b):
        return (a @ b).sum()

    a = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    t = ha.analyze(_compile(f, a, a).as_text())
    min_traffic = 2 * 512 * 512 * 4          # reading both operands once
    max_traffic = 40 * 512 * 512 * 4         # generous slack for temps
    assert min_traffic <= t["bytes"] <= max_traffic


def test_shape_bytes_parsing():
    assert ha.shape_bytes("f32[4,8]{1,0}") == 128
    assert ha.shape_bytes("bf16[10]") == 20
    assert ha.shape_bytes("(f32[2,2]{1,0}, s32[])") == 20
    assert ha.shape_bytes("pred[]") == 1


def test_roofline_terms_and_bound():
    class Cfg:
        num_experts = 0

        @staticmethod
        def active_param_count():
            return 1_000_000

        @staticmethod
        def param_count():
            return 1_000_000

    class Shp:
        kind = "train"
        global_batch = 8
        seq_len = 128

    totals = {
        "flops": 1e12,
        "bytes": 1e12,
        "collective_bytes": {},
        "collective_total_bytes": 1e9,
    }
    t = roofline.roofline_terms_from_hlo(Cfg, Shp, totals, multi_pod=False)
    assert t["chips"] == 256
    assert t["bound"] == "memory"
    assert t["compute_s"] == pytest.approx(1e12 / 197e12)
    assert t["memory_s"] == pytest.approx(1e12 / 819e9)
    assert t["collective_s"] == pytest.approx(1e9 / 50e9)
    mf = 6.0 * 1e6 * 8 * 128
    assert t["model_flops"] == pytest.approx(mf)
    assert 0 < t["roofline_fraction"] < 1


def test_collective_parsing_on_sharded_program():
    """An explicitly sharded matmul must show collectives in the analysis."""
    import subprocess
    import sys

    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch import hlo_analysis as ha
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("model",))
x = jax.ShapeDtypeStruct((64, 256), jnp.float32, sharding=NamedSharding(mesh, P(None, "model")))
w = jax.ShapeDtypeStruct((256, 64), jnp.float32, sharding=NamedSharding(mesh, P("model", None)))
with mesh:
    c = jax.jit(lambda a, b: a @ b).lower(x, w).compile()
t = ha.analyze(c.as_text())
assert t["collective_total_bytes"] > 0, t
print("COLL_OK", t["collective_total_bytes"])
"""
    import os

    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=dict(os.environ, PYTHONPATH="src"),
                       cwd=".", timeout=180)
    assert "COLL_OK" in r.stdout, r.stderr[-1500:]
