"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fl_gains import ops as fl_ops
from repro.kernels.fl_gains.ref import (
    fl_gains_gram_free_delta_ref,
    fl_gains_gram_free_ref,
    fl_gains_ref,
)
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import gqa_attention_ref
from repro.kernels.similarity import ops as sim_ops
from repro.kernels.similarity.ref import similarity_ref

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-4, atol=2e-4)


@pytest.mark.parametrize("mq,mk,d", [(64, 64, 16), (256, 256, 64), (300, 517, 48), (8, 1024, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_similarity_kernel_sweep(mq, mk, d, dtype):
    zq = jnp.asarray(RNG.normal(size=(mq, d)), dtype)
    zk = jnp.asarray(RNG.normal(size=(mk, d)), dtype)
    out = sim_ops.similarity(zq, zk, interpret=True)
    ref = similarity_ref(zq, zk)
    np.testing.assert_allclose(out, ref, **_tol(dtype))
    assert out.dtype == jnp.float32
    assert float(jnp.min(out)) >= -1e-3 and float(jnp.max(out)) <= 1.0 + 1e-3


@pytest.mark.parametrize("n,ncand", [(128, 128), (700, 321), (1024, 64), (65, 1000)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fl_gains_kernel_sweep(n, ncand, dtype):
    K = jnp.asarray(RNG.uniform(size=(n, ncand)), dtype)
    c = jnp.asarray(RNG.uniform(size=(n,)), dtype)
    out = fl_ops.fl_gains(K, c, interpret=True)
    ref = fl_gains_ref(K, c)
    np.testing.assert_allclose(out, ref, **_tol(dtype))
    assert np.all(np.asarray(out) >= -1e-3), "gains are nonnegative"


@pytest.mark.parametrize("n,ncand,d", [(128, 128, 32), (300, 130, 48), (64, 512, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fl_gains_gram_free_kernel_sweep(n, ncand, d, dtype):
    """Fused-similarity gains (no materialized Gram) vs the jnp oracle."""
    z = jnp.asarray(RNG.normal(size=(n, d)), dtype)
    z = z / jnp.maximum(jnp.linalg.norm(z.astype(jnp.float32), axis=-1,
                                        keepdims=True), 1e-8).astype(dtype)
    zc = z[:ncand] if ncand <= n else jnp.concatenate([z] * (ncand // n + 1))[:ncand]
    c = jnp.asarray(RNG.uniform(size=(n,)), dtype)
    out = fl_ops.fl_gains_gram_free(z, zc, c, block_i=128, block_j=128,
                                    interpret=True)
    ref = fl_gains_gram_free_ref(z, zc, c)
    np.testing.assert_allclose(out, ref, **_tol(dtype))
    assert out.dtype == jnp.float32


@pytest.mark.parametrize("b,ncand,d", [(32, 256, 16), (100, 130, 48), (1, 64, 8)])
def test_fl_gains_gram_free_delta_kernel_sweep(b, ncand, d):
    """Fused lazy-gain delta kernel vs oracle, incl. the inf-padding contract
    (rows with c_old = c_new = +inf contribute exact zeros) and the algebraic
    identity delta == restricted_gains(c_new) - restricted_gains(c_old)."""
    z = jnp.asarray(RNG.normal(size=(ncand, d)).astype(np.float32))
    z = z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-8)
    zr = z[:b] if b <= ncand else jnp.concatenate([z] * (b // ncand + 1))[:b]
    c_old = jnp.asarray(RNG.uniform(size=(b,)).astype(np.float32))
    c_new = jnp.minimum(c_old + RNG.uniform(size=(b,)).astype(np.float32), 1.0)
    # mark a few rows as padding (both covers infinite)
    pad = jnp.arange(b) % 5 == 3
    c_old = jnp.where(pad, jnp.inf, c_old)
    c_new = jnp.where(pad, jnp.inf, c_new)
    out = fl_ops.fl_gains_gram_free_delta(zr, z, c_old, c_new,
                                          block_i=64, block_j=64,
                                          interpret=True)
    ref = fl_gains_gram_free_delta_ref(zr, z, c_old, c_new)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    split = (fl_gains_gram_free_ref(zr, z, c_new)
             - fl_gains_gram_free_ref(zr, z, c_old))
    np.testing.assert_allclose(np.asarray(out), np.asarray(split),
                               rtol=1e-4, atol=1e-4)
    assert np.all(np.asarray(out) <= 1e-5), "cover only grows: delta <= 0"


@pytest.mark.parametrize(
    "b,hq,hkv,sq,sk,d",
    [
        (1, 4, 4, 64, 64, 32),      # MHA
        (2, 8, 2, 128, 128, 32),    # GQA
        (2, 8, 2, 200, 200, 32),    # ragged seq (padding path)
        (1, 4, 1, 64, 256, 64),     # cross-length causal (prefix)
        (4, 8, 4, 1, 333, 32),      # decode: 1 query vs long KV
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel_sweep(b, hq, hkv, sq, sk, d, dtype):
    q = jnp.asarray(RNG.normal(size=(b, hq, sq, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, hkv, sk, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, hkv, sk, d)), dtype)
    out = fa_ops.flash_attention(q, k, v, causal=True, interpret=True)
    ref = gqa_attention_ref(q, k, v, causal=True).astype(dtype)
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), **_tol(dtype)
    )


def test_flash_attention_noncausal():
    q = jnp.asarray(RNG.normal(size=(1, 2, 100, 16)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 2, 150, 16)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 2, 150, 16)), jnp.float32)
    out = fa_ops.flash_attention(q, k, v, causal=False, interpret=True)
    ref = gqa_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-4)


def test_similarity_matches_core_gram():
    """The Pallas path must agree with core.similarity.gram_matrix."""
    from repro.core.similarity import gram_matrix

    z = jnp.asarray(RNG.normal(size=(120, 24)), jnp.float32)
    a = sim_ops.similarity(z, z, interpret=True)
    b = gram_matrix(z)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_fl_gains_drives_greedy_equivalently():
    """Greedy with the Pallas gains == greedy with the analytic gains."""
    from repro.core.similarity import gram_matrix

    z = jnp.asarray(RNG.normal(size=(96, 16)), jnp.float32)
    K = gram_matrix(z)
    c = jnp.zeros((96,))
    sel = []
    for _ in range(5):
        gains = fl_ops.fl_gains(K, c, interpret=True)
        gains = gains.at[jnp.asarray(sel, jnp.int32)].set(-1e30) if sel else gains
        j = int(jnp.argmax(gains))
        sel.append(j)
        c = jnp.maximum(c, K[:, j])
    from repro.core import facility_location, greedy

    ref = np.asarray(greedy(facility_location, K, 5).indices).tolist()
    assert sel == ref


def test_pallas_facility_location_setfunction_in_greedy():
    """The Pallas-gains SetFunction drives the jit'd greedy engine to the
    identical selection trajectory as the analytic one."""
    from repro.core import greedy
    from repro.core.similarity import gram_matrix
    from repro.core.submodular import facility_location, make_facility_location_pallas

    z = jnp.asarray(RNG.normal(size=(64, 12)), jnp.float32)
    K = gram_matrix(z)
    fn_p = make_facility_location_pallas(interpret=True, block_i=64, block_j=64)
    a = np.asarray(greedy(facility_location, K, 6).indices)
    b = np.asarray(greedy(fn_p, K, 6).indices)
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# interpret-mode decision and the forward-only attention kernel
# ---------------------------------------------------------------------------

def test_resolve_interpret_follows_backend(monkeypatch):
    from repro.kernels import resolve_interpret

    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert resolve_interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False
    assert resolve_interpret(True) is True  # an explicit request still wins
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        resolve_interpret()


def test_kernels_default_to_interpret_on_cpu():
    """No interpret argument: the CPU interprets, so the kernel runs here
    and matches its oracle (a compiled TPU call could not run on the CPU)."""
    rng = np.random.default_rng(5)
    z = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    zn = z / jnp.linalg.norm(z, axis=1, keepdims=True)
    c = jnp.zeros((64,), jnp.float32)
    np.testing.assert_allclose(fl_ops.fl_gains_gram_free(zn, zn, c),
                               fl_gains_gram_free_ref(zn, zn, c),
                               rtol=1e-5, atol=1e-4)
