"""State-space / recurrent blocks: Mamba (SSD form), mLSTM, sLSTM.

TPU adaptation (DESIGN.md §2): the selective-scan recurrences are computed in
the Mamba-2 *SSD* chunked form — per-head scalar decay, intra-chunk (L, L)
decay matmuls on the MXU, inter-chunk state carried through a ``lax.scan`` —
instead of the channel-diagonal Mamba-1 CUDA scan (which would materialize a
(B, S, d_inner, N) tensor; hopeless on any hardware without a fused kernel).
mLSTM's matrix memory C_t = f_t C + i_t v kᵀ is the same algebra with N = P,
so it shares the chunked engine.  sLSTM is inherently sequential (scalar
memory mixing) and runs as a ``lax.scan`` over time.

Recurrence (per head h, chunk length L):
    h_t = a_t h_{t-1} + (dt_t b_t) x_tᵀ        a_t = exp(-softplus(A) dt_t)
    y_t = c_tᵀ h_t
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import init_dense


def init_mamba(key, d_model: int, *, expand: int = 2, head_dim: int = 64, d_state: int = 128, dtype=jnp.bfloat16) -> dict:
    d_inner = expand * d_model
    n_heads = d_inner // head_dim
    k_in, k_bc, k_dt, k_out, k_a = jax.random.split(key, 5)
    return {
        "w_in": init_dense(k_in, d_model, 2 * d_inner, dtype),       # x and gate z
        "w_bc": init_dense(k_bc, d_model, 2 * d_state, dtype),       # B and C
        "w_dt": init_dense(k_dt, d_model, n_heads, dtype),
        "a_log": jnp.zeros((n_heads,), jnp.float32),                 # A = -softplus-ish
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "w_out": init_dense(k_out, d_inner, d_model, dtype),
        "norm": jnp.ones((d_inner,), jnp.float32),
    }


def _ssd_chunk_scan(x, a, b, c, *, chunk: int, return_state: bool = False):
    """Chunked linear recurrence.

    Args:
      x: (B, S, H, P) values;  a: (B, S, H) decay in (0,1];
      b: (B, S, N) input proj; c: (B, S, N) output proj (shared across heads).
    Returns y: (B, S, H, P), and the final state (B, H, N, P) if requested.

    Note on padding + final state: padded positions use a=1, b=0, so they do
    not perturb the carried state.
    """
    B, S, H, P = x.shape
    N = b.shape[-1]
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    nc = x.shape[1] // chunk
    # (nc, B, L, ...) chunk-major for scan
    xc = x.reshape(B, nc, chunk, H, P).swapaxes(0, 1)
    ac = a.reshape(B, nc, chunk, H).swapaxes(0, 1)
    bc_ = b.reshape(B, nc, chunk, N).swapaxes(0, 1)
    cc = c.reshape(B, nc, chunk, N).swapaxes(0, 1)

    def step(h, xs):
        xb, ab, bb, cb = xs          # (B,L,H,P) (B,L,H) (B,L,N) (B,L,N)
        la = jnp.log(jnp.maximum(ab, 1e-20))          # (B,L,H)
        cum = jnp.cumsum(la, axis=1)                  # log prod a_{1..t}
        # intra-chunk: decay(s->t) = exp(cum_t - cum_s) for s <= t
        dt_mat = cum[:, :, None, :] - cum[:, None, :, :]        # (B,L,L,H) t,s
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.where(tri[None, :, :, None], jnp.exp(dt_mat), 0.0)
        scores = jnp.einsum("btn,bsn->bts", cb, bb)             # (B,L,L)
        w = scores[..., None] * decay                           # (B,L,L,H)
        y_intra = jnp.einsum("btsh,bshp->bthp", w, xb)
        # contribution of the carried state (decayed to each position t)
        y_inter = jnp.einsum("btn,bhnp,bth->bthp", cb, h, jnp.exp(cum))
        # state update: h' = (prod a) h + sum_s (prod_{s< .. end}) b_s x_s
        tot = cum[:, -1, :]                                     # (B,H)
        rem = jnp.exp(tot[:, None, :] - cum)                    # decay from s to end
        h_new = jnp.exp(tot)[..., None, None] * h + jnp.einsum(
            "bsn,bshp,bsh->bhnp", bb, xb, rem
        )
        return h_new, y_intra + y_inter

    h0 = jnp.zeros((B, H, N, P), jnp.float32)
    # §Perf iter-4: checkpoint each chunk step — backward otherwise saves the
    # (chunks, B, L, L, H) decay/score residuals stacked across the scan
    # (~12% of jamba's HBM traffic); recomputing them per chunk is free
    # against the memory roof.
    h_fin, ys = jax.lax.scan(jax.checkpoint(step), h0,
                             (xc.astype(jnp.float32), ac.astype(jnp.float32),
                              bc_.astype(jnp.float32), cc.astype(jnp.float32)))
    y = ys.swapaxes(0, 1).reshape(B, nc * chunk, H, P)[:, :S]
    if return_state:
        return y, h_fin
    return y


def mamba(params: dict, x: jax.Array, *, chunk: int = 256,
          state: jax.Array | None = None, mode: str = "train") -> tuple[jax.Array, jax.Array | None]:
    """Mamba/SSD mixer.  x: (B, S, D).

    ``mode='decode'``: S==1, sequential state update against ``state``
    (B, H, N, P); returns (y, new_state).  Other modes return (y, final_state
    is None) — training does not thread state across calls.
    """
    B, S, D = x.shape
    d_inner2 = params["w_in"].shape[-1]
    d_inner = d_inner2 // 2
    n_heads = params["w_dt"].shape[-1]
    P = d_inner // n_heads
    N = params["w_bc"].shape[-1] // 2

    xz = jnp.einsum("bsd,de->bse", x, params["w_in"])
    xi, z = jnp.split(xz, 2, axis=-1)
    bc = jnp.einsum("bsd,dn->bsn", x, params["w_bc"]).astype(jnp.float32)
    b_proj, c_proj = jnp.split(bc, 2, axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", x, params["w_dt"]).astype(jnp.float32) + params["dt_bias"]
    )
    a = jnp.exp(-jax.nn.softplus(params["a_log"])[None, None, :] * dt)    # (B,S,H)
    xh = xi.reshape(B, S, n_heads, P).astype(jnp.float32) * dt[..., None]

    if mode == "decode":
        assert state is not None and S == 1
        h_new = a[:, 0, :, None, None] * state + jnp.einsum(
            "bn,bhp->bhnp", b_proj[:, 0], xh[:, 0]
        )
        y = jnp.einsum("bn,bhnp->bhp", c_proj[:, 0], h_new)[:, None]       # (B,1,H,P)
        new_state = h_new
    elif mode == "prefill":
        y, new_state = _ssd_chunk_scan(xh, a, b_proj, c_proj, chunk=chunk, return_state=True)
    else:
        y = _ssd_chunk_scan(xh, a, b_proj, c_proj, chunk=chunk)
        new_state = None

    y = y.reshape(B, S, d_inner)
    # gated RMS norm (Mamba-2 style)
    var = jnp.mean(y * y, axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + 1e-6) * params["norm"]
    y = y * jax.nn.silu(z.astype(jnp.float32))
    out = jnp.einsum("bse,ed->bsd", y.astype(x.dtype), params["w_out"],
                     preferred_element_type=x.dtype)  # §Perf iter-6
    return out, new_state


# --- xLSTM ------------------------------------------------------------------

def init_mlstm(key, d_model: int, *, expand: int = 2, head_dim: int = 64, dtype=jnp.bfloat16) -> dict:
    d_inner = expand * d_model
    n_heads = d_inner // head_dim
    kq, kk, kv, kf, ki, ko, kz = jax.random.split(key, 7)
    return {
        "wq": init_dense(kq, d_model, d_inner, dtype),
        "wk": init_dense(kk, d_model, d_inner, dtype),
        "wv": init_dense(kv, d_model, d_inner, dtype),
        "w_fgate": init_dense(kf, d_model, n_heads, jnp.float32),
        "w_igate": init_dense(ki, d_model, n_heads, jnp.float32),
        "w_z": init_dense(kz, d_model, d_inner, dtype),   # output gate source
        "w_out": init_dense(ko, d_inner, d_model, dtype),
        "norm": jnp.ones((d_inner,), jnp.float32),
    }


def mlstm(params: dict, x: jax.Array, *, chunk: int = 256,
          state: jax.Array | None = None, mode: str = "train") -> tuple[jax.Array, jax.Array | None]:
    """mLSTM matrix-memory block via the shared SSD engine (N == P == head_dim).

    C_t = f_t C_{t-1} + i_t v_t k_tᵀ;  y_t = C_t q_t  — i.e. the linear
    recurrence with a = sigmoid(fgate), x-values = i_t * v_t, b = k, c = q.
    """
    B, S, D = x.shape
    d_inner = params["wq"].shape[-1]
    n_heads = params["w_fgate"].shape[-1]
    P = d_inner // n_heads
    q = jnp.einsum("bsd,de->bse", x, params["wq"]).reshape(B, S, n_heads, P)
    k = jnp.einsum("bsd,de->bse", x, params["wk"]).reshape(B, S, n_heads, P) / (P ** 0.5)
    v = jnp.einsum("bsd,de->bse", x, params["wv"]).reshape(B, S, n_heads, P)
    f = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", x.astype(jnp.float32), params["w_fgate"]))
    i = jnp.exp(-jax.nn.softplus(-jnp.einsum("bsd,dh->bsh", x.astype(jnp.float32), params["w_igate"])))

    vals = v.astype(jnp.float32) * i[..., None]
    if mode == "decode":
        assert state is not None and S == 1
        # per-head state (B, H, P, P): b=k, c=q per head
        h_new = f[:, 0, :, None, None] * state + jnp.einsum(
            "bhn,bhp->bhnp", k[:, 0].astype(jnp.float32), vals[:, 0]
        )
        y = jnp.einsum("bhn,bhnp->bhp", q[:, 0].astype(jnp.float32), h_new)[:, None]
        new_state = h_new
    else:
        # per-head keys/queries: reuse _ssd_chunk_scan per head via vmap on H
        def per_head(xh, ah, bh, ch):
            y, st = _ssd_chunk_scan(
                xh[..., None, :], ah[..., None], bh, ch, chunk=chunk, return_state=True
            )
            return y[..., 0, :], st[:, 0]  # (B,S,P), (B,N,P)

        y, st = jax.vmap(per_head, in_axes=(2, 2, 2, 2), out_axes=(2, 1))(
            vals, f, k.astype(jnp.float32), q.astype(jnp.float32)
        )
        new_state = st if mode == "prefill" else None
    y = y.reshape(B, S, d_inner)
    var = jnp.mean(y * y, axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + 1e-6) * params["norm"]
    y = y * jax.nn.silu(jnp.einsum("bsd,de->bse", x, params["w_z"]).astype(jnp.float32))
    return jnp.einsum("bse,ed->bsd", y.astype(x.dtype), params["w_out"],
                      preferred_element_type=x.dtype), new_state  # §Perf iter-6


def init_slstm(key, d_model: int, *, n_heads: int = 4, dtype=jnp.bfloat16) -> dict:
    kz, ki, kf, ko, kr = jax.random.split(key, 5)
    return {
        "w_z": init_dense(kz, d_model, d_model, dtype),
        "w_i": init_dense(ki, d_model, d_model, jnp.float32),
        "w_f": init_dense(kf, d_model, d_model, jnp.float32),
        "w_o": init_dense(ko, d_model, d_model, jnp.float32),
        "w_out": init_dense(kr, d_model, d_model, dtype),
    }


def slstm(params: dict, x: jax.Array, *, state=None, mode: str = "train") -> tuple[jax.Array, tuple | None]:
    """sLSTM: sequential scalar-memory LSTM with exponential gating.

    State (c, n, m): cell, normalizer, log-max stabilizer — each (B, D).
    """
    B, S, D = x.shape
    z = jnp.tanh(jnp.einsum("bsd,de->bse", x, params["w_z"]).astype(jnp.float32))
    ig = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), params["w_i"])
    fg = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), params["w_f"])
    og = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x.astype(jnp.float32), params["w_o"]))

    def step(carry, t):
        c, n, m = carry
        zt, it, ft, ot = t
        m_new = jnp.maximum(ft + m, it)           # log-space stabilization
        i_s = jnp.exp(it - m_new)
        f_s = jnp.exp(ft + m - m_new)
        c_new = f_s * c + i_s * zt
        n_new = f_s * n + i_s
        h = ot * c_new / jnp.maximum(n_new, 1e-6)
        return (c_new, n_new, m_new), h

    if mode == "decode":
        assert state is not None and S == 1
        carry, h = step(state, (z[:, 0], ig[:, 0], fg[:, 0], og[:, 0]))
        y = h[:, None]
        new_state = carry
    else:
        init = (
            jnp.zeros((B, D), jnp.float32),
            jnp.zeros((B, D), jnp.float32),
            jnp.full((B, D), -1e30, jnp.float32),
        )
        carry, hs = jax.lax.scan(
            step, init, (z.swapaxes(0, 1), ig.swapaxes(0, 1), fg.swapaxes(0, 1), og.swapaxes(0, 1))
        )
        y = hs.swapaxes(0, 1)
        new_state = carry if mode == "prefill" else None
    return jnp.einsum("bse,ed->bsd", y.astype(x.dtype), params["w_out"]), new_state


def mamba_state_shape(d_model: int, *, expand: int = 2, head_dim: int = 64, d_state: int = 128, batch: int = 1):
    d_inner = expand * d_model
    h = d_inner // head_dim
    return (batch, h, d_state, head_dim)


def mlstm_state_shape(d_model: int, *, expand: int = 2, head_dim: int = 64, batch: int = 1):
    d_inner = expand * d_model
    h = d_inner // head_dim
    return (batch, h, head_dim, head_dim)
