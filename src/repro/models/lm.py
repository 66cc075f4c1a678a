"""Full language model: embed -> scanned block groups -> norm -> logits.

Covers all assigned families behind one interface:
  * decoder-only dense / MoE / SSM / hybrid,
  * enc-dec (whisper): encoder stack over stubbed frame embeddings, decoder
    pattern interleaves self- and cross-attention,
  * VLM (llama-3.2-vision): cross-attention layers against stubbed patch
    embeddings.

Entry points:
  init_lm(key, cfg, dtype)                      -> params
  forward(params, cfg, tokens, ...)             -> logits           (train)
  loss_fn(params, cfg, batch)                   -> (loss, metrics)
  prefill(params, cfg, tokens, caches, ...)     -> (logits, caches)
  decode_step(params, cfg, token, caches, pos)  -> (logits, caches)
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.blocks import apply_block, init_block, init_block_cache
from repro.models.layers import embed, init_dense, init_embedding, rms_norm, unembed


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def init_lm(key: jax.Array, cfg: ModelConfig) -> dict:
    dtype = _dtype(cfg)
    k_emb, k_groups, k_enc, k_ctx = jax.random.split(key, 4)

    def init_group(gkey):
        keys = jax.random.split(gkey, len(cfg.pattern))
        return {
            f"b{i}": init_block(keys[i], cfg, mixer, ffn, dtype)
            for i, (mixer, ffn) in enumerate(cfg.pattern)
        }

    params: dict[str, Any] = {
        "embed": init_embedding(k_emb, cfg.vocab_size, cfg.d_model, dtype),
        "groups": jax.vmap(init_group)(jax.random.split(k_groups, cfg.n_groups)),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if cfg.is_encdec:
        def init_enc_layer(lkey):
            return init_block(lkey, cfg, "attn_nc", "dense", dtype)

        params["encoder"] = {
            "layers": jax.vmap(init_enc_layer)(jax.random.split(k_enc, cfg.encoder_layers)),
            "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        }
    return params


# --------------------------------------------------------------------------
# layer-stack execution
# --------------------------------------------------------------------------

def _run_stack(params, cfg: ModelConfig, x, positions, context, caches, mode):
    pattern = cfg.pattern

    def group_fn(x, gparams, gcaches):
        new_caches = []
        for i, (mixer, ffn) in enumerate(pattern):
            cache_i = () if gcaches is None else gcaches[i]
            x, nc = apply_block(
                gparams[f"b{i}"], x, cfg=cfg, mixer=mixer, ffn=ffn,
                positions=positions, context=context, cache=cache_i,
                mode=mode,
            )
            new_caches.append(nc)
        return x, tuple(new_caches)

    if cfg.remat:
        group_fn = jax.checkpoint(group_fn)

    if caches is None:
        def body(x, gp):
            x, _ = group_fn(x, gp, None)
            return x, None

        x, _ = jax.lax.scan(body, x, params["groups"])
        return x, None

    def body(x, xs):
        gp, gc = xs
        return group_fn(x, gp, gc)

    x, new_caches = jax.lax.scan(body, x, (params["groups"], caches))
    return x, new_caches


def _run_encoder(params, cfg: ModelConfig, frames):
    """Encoder over precomputed frame embeddings (conv frontend stub)."""
    enc = params["encoder"]
    pos = jnp.arange(frames.shape[1])[None, :]

    def body(x, lp):
        x, _ = apply_block(
            lp, x, cfg=cfg, mixer="attn_nc", ffn="dense", positions=pos,
            context=None, cache=(), mode="train",
        )
        return x, None

    x, _ = jax.lax.scan(body, frames, enc["layers"])
    return rms_norm(x, enc["final_norm"], cfg.norm_eps)


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,                    # (B, S) int32
    *,
    context: jax.Array | None = None,     # (B, Nctx, D) patch/frame embeddings
    mode: str = "train",
    caches=None,
    pos0: jax.Array | int = 0,
) -> tuple[jax.Array, Any]:
    b, s = tokens.shape
    x = constrain(embed(tokens, params["embed"]), "batch", None, None)
    if cfg.is_encdec:
        assert context is not None, "enc-dec model needs frame embeddings"
        context = _run_encoder(params, cfg, context.astype(x.dtype))
    p0 = jnp.asarray(pos0)
    p0 = p0[:, None] if p0.ndim == 1 else p0  # per-slot decode positions (B,)
    positions = p0 + jnp.arange(s)[None, :]
    x, new_caches = _run_stack(params, cfg, x, positions, context, caches, mode)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = constrain(unembed(x, params["embed"]), "batch", None, "model")
    return logits, new_caches


# --------------------------------------------------------------------------
# losses
# --------------------------------------------------------------------------

def loss_fn(
    params: dict,
    cfg: ModelConfig,
    batch: dict,
) -> tuple[jax.Array, dict]:
    """Next-token cross entropy.  batch: tokens (B,S), labels (B,S),
    optional loss_mask (B,S), optional example weights w (B,) (MILO WRE),
    optional context (B,Nctx,D)."""
    logits, _ = forward(
        params, cfg, batch["tokens"], context=batch.get("context"),
        mode="train",
    )
    labels = batch["labels"]
    # Vocab-sharding-friendly CE: the vocab axis of ``logits`` is sharded over
    # the model mesh axis (tied to the embedding table), so we avoid any
    # gather along vocab.  logsumexp reduces over the sharded axis (GSPMD
    # inserts a tiny (B,S) all-reduce) and the label logit comes from a
    # one-hot contraction (psum) instead of take_along_axis (all-gather).
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = (logits - m).astype(jnp.float32)  # upcast per element at use
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0].astype(jnp.float32)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    label_logit = jnp.einsum("bsv,bsv->bs", logits, onehot,
                             preferred_element_type=jnp.float32)
    nll = lse - label_logit                                               # (B,S)
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(nll)
    w = batch.get("weights")
    if w is not None:
        mask = mask * w[:, None]
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss, {"loss": loss}


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, cache_len: int):
    """Stacked (over groups) cache pytree matching the pattern."""
    dtype = _dtype(cfg)

    def one_group():
        return tuple(
            init_block_cache(cfg, mixer, batch, cache_len, dtype)
            for mixer, _ in cfg.pattern
        )

    g = one_group()
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (cfg.n_groups,) + a.shape).copy(), g)


def prefill(params, cfg: ModelConfig, tokens, caches, *, context=None):
    return forward(params, cfg, tokens, context=context, mode="prefill",
                   caches=caches)


def decode_step(params, cfg: ModelConfig, token, caches, pos, *, context=None):
    """One decode step.  token: (B, 1); pos: scalar int32 current position."""
    logits, caches = forward(
        params, cfg, token, context=context, mode="decode", caches=caches,
        pos0=pos,
    )
    return logits, caches
