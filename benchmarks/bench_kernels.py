"""Kernel microbenchmarks: oracle-path throughput on CPU plus interpret-mode
validation timing.  (Pallas compiled timings require a TPU; the roofline
terms for the kernels' target shapes come from launch/roofline.py.)"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row
from repro.kernels.fl_gains import ops as fl_ops
from repro.kernels.fl_gains.ref import fl_gains_ref
from repro.kernels.flash_attention.ref import gqa_attention_ref
from repro.kernels.similarity.ref import similarity_ref


def _time(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def run(verbose: bool = True) -> list[str]:
    rows = []
    rng = np.random.default_rng(0)

    # similarity: 2048x2048 Gram, d=768 (DINO CLS width)
    z = jnp.asarray(rng.normal(size=(2048, 768)).astype(np.float32))
    f = jax.jit(lambda a: similarity_ref(a, a))
    dt = _time(f, z)
    flops = 2 * 2048 * 2048 * 768
    rows.append(csv_row("kernel/similarity_ref_2048x768", dt * 1e6,
                        f"gflops={flops/dt/1e9:.1f}"))

    # fl gains: n=4096 candidates=4096
    K = jnp.asarray(rng.uniform(size=(4096, 4096)).astype(np.float32))
    c = jnp.asarray(rng.uniform(size=(4096,)).astype(np.float32))
    f = jax.jit(fl_gains_ref)
    dt = _time(f, K, c)
    rows.append(csv_row("kernel/fl_gains_ref_4096", dt * 1e6,
                        f"gbps={(K.size*4/dt)/1e9:.1f}"))

    # flash attention oracle: B2 H8 S512 D64 GQA2
    q = jnp.asarray(rng.normal(size=(2, 8, 512, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 2, 512, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 2, 512, 64)).astype(np.float32))
    f = jax.jit(lambda a, b, cc: gqa_attention_ref(a, b, cc))
    dt = _time(f, q, k, v)
    attn_flops = 4 * 2 * 8 * 512 * 512 * 64
    rows.append(csv_row("kernel/flash_attention_ref_b2h8s512", dt * 1e6,
                        f"gflops={attn_flops/dt/1e9:.1f}"))

    # SSD chunked scan, one chunk (jamba hot-spot): H16 L256 P64 N128
    from repro.models.ssm import _ssd_chunk_scan
    x2 = jnp.asarray(rng.normal(size=(1, 256, 16, 64)).astype(np.float32))
    a2 = jnp.asarray(rng.uniform(0.8, 1.0, size=(1, 256, 16)).astype(np.float32))
    b2 = jnp.asarray(rng.normal(size=(1, 256, 128)).astype(np.float32))
    c2 = jnp.asarray(rng.normal(size=(1, 256, 128)).astype(np.float32))
    f = jax.jit(lambda x, a, b, c: _ssd_chunk_scan(x, a, b, c, chunk=256))
    dt = _time(f, x2, a2, b2, c2)
    ssd_flops = 2 * 256 * 256 * (128 + 16 * 64)  # scores + weighted sum approx
    rows.append(csv_row("kernel/ssd_chunk_scan_L256", dt * 1e6,
                        f"gflops={ssd_flops/dt/1e9:.1f}"))

    # kernel-free landmark selection vs exact kernel selection (future-work impl)
    import time as _time_mod
    from repro.core import facility_location, gram_matrix, greedy
    from repro.core.feature_submodular import feature_greedy_select
    z2 = jnp.asarray(rng.normal(size=(2048, 64)).astype(np.float32))
    t0 = _time_mod.perf_counter()
    Kz = gram_matrix(z2); greedy(facility_location, Kz, 128).indices.block_until_ready()
    t_exact = _time_mod.perf_counter() - t0
    t0 = _time_mod.perf_counter()
    feature_greedy_select(jax.random.PRNGKey(0), z2, 128).indices.block_until_ready()
    t_feat = _time_mod.perf_counter() - t0
    rows.append(csv_row("kernel/feature_vs_kernel_selection_n2048_k128",
                        t_feat * 1e6, f"exact_s={t_exact:.2f} feature_s={t_feat:.2f} "
                        f"mem_ratio={2048/512}"))

    # interpret-mode Pallas correctness-path timing (not a perf number; shows
    # the validation path stays usable in CI)
    Ksmall = K[:512, :512]
    csmall = c[:512]
    dt = _time(lambda a, b: fl_ops.fl_gains(a, b, interpret=True), Ksmall, csmall, reps=2)
    rows.append(csv_row("kernel/fl_gains_pallas_interpret_512", dt * 1e6, "validation-path"))
    if verbose:
        for r in rows:
            print(r)
    return rows


if __name__ == "__main__":
    run()
