"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler is installed, and it compiles for a v5e
that is described and not attached.  Each test compiles one kernel with
``interpret=False`` and asserts that the program holds a ``tpu_custom_call``
(the compiled kernel).  A kernel the v5e compiler refuses (a block that
breaks the 8x128 tiling, more VMEM than a kernel may use) fails here.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the installed libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32 = jnp.float32
N, D = 8192, 768  # one gram-free FL partition of 768-wide embeddings


def test_fl_gains_gram_free_compiles(one_chip):
    from repro.kernels.fl_gains import ops

    text = _compiled_text(
        lambda z, zc, c: ops.fl_gains_gram_free(z, zc, c, interpret=False),
        one_chip, ((N, D), F32), ((N, D), F32), ((N,), F32))
    assert "tpu_custom_call" in text


def test_fl_gains_gram_free_delta_compiles(one_chip):
    from repro.kernels.fl_gains import ops

    b = N // 8  # the lazy engine's touched-rows budget at threshold 0.125
    text = _compiled_text(
        lambda z, zc, co, cn: ops.fl_gains_gram_free_delta(
            z, zc, co, cn, interpret=False),
        one_chip, ((b, D), F32), ((N, D), F32), ((b,), F32), ((b,), F32))
    assert "tpu_custom_call" in text


def test_gram_route_fl_gains_compiles(one_chip):
    from repro.kernels.fl_gains import ops

    n = 4096
    text = _compiled_text(
        lambda K, c: ops.fl_gains(K, c, interpret=False),
        one_chip, ((n, n), F32), ((n,), F32))
    assert "tpu_custom_call" in text


def test_similarity_kernel_compiles(one_chip):
    from repro.kernels.similarity import ops

    n = 4096
    text = _compiled_text(
        lambda zq, zk: ops.similarity(zq, zk, normalized=True, interpret=False),
        one_chip, ((n, D), F32), ((n, D), F32))
    assert "tpu_custom_call" in text


def test_flash_attention_forward_compiles_at_internlm2_widths(one_chip):
    from repro.configs import registry
    from repro.kernels.flash_attention import ops

    cfg = registry.get("internlm2-1.8b")
    s = 2048
    q = ((1, cfg.num_heads, s, cfg.head_dim), jnp.bfloat16)
    kv = ((1, cfg.num_kv_heads, s, cfg.head_dim), jnp.bfloat16)
    text = _compiled_text(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            interpret=False),
        one_chip, q, kv, kv)
    assert "tpu_custom_call" in text
