"""Unified model: decoder LM / encoder / hybrid, built from blocks and scanned
over the layer stack.

Heterogeneous stacks (jamba's 1-attn : 7-mamba interleave) scan over *periods*
— the repeating unit of `attn_period` layers — with the period body unrolled.
Homogeneous stacks have period length 1.  Parameters therefore live in
`params["periods"]["sub{j}"]`, stacked with a leading `n_periods` axis, which
keeps XLA compile time flat in depth.

Batch formats:
    text  {"tokens":  [B, S] int32}
    audio {"features": [B, S, FEAT], "labels": [B, S] int32}
    vlm   {"tokens":  [B, S_text] int32, "vision": [B, N_VIS, VISDIM]}
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig
from . import blocks, layers
from ..parallel.sharding import shard

AUDIO_FEAT_DIM = 512     # stubbed conv-feature-extractor output (w2v2/HuBERT)
VISION_EMB_DIM = 1024    # stubbed InternViT patch-embedding output


def period_structure(cfg: ModelConfig):
    plen = cfg.attn_period if cfg.family == "hybrid" else 1
    assert cfg.num_layers % plen == 0
    kinds = tuple(cfg.layer_kind(j) for j in range(plen))
    mlp_kinds = tuple(cfg.mlp_kind(j) for j in range(plen))
    return cfg.num_layers // plen, plen, kinds, mlp_kinds


# ----------------------------------------------------------------------------
# init


def init(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.dtype)
    n_periods, plen, kinds, mlp_kinds = period_structure(cfg)
    keys = jax.random.split(key, n_periods * plen + 3)

    def one_period(i):
        return {f"sub{j}": blocks.init_block(keys[i * plen + j], cfg,
                                             kinds[j], mlp_kinds[j], dtype)
                for j in range(plen)}

    periods = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[one_period(i) for i in range(n_periods)])
    p = {
        "periods": periods,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if cfg.family != "audio":
        p["embed"] = layers.kaiming(keys[-1], (cfg.vocab_size, cfg.d_model),
                                    dtype, fan_in=cfg.d_model)
    if not cfg.tie_embeddings or cfg.family == "audio":
        p["lm_head"] = layers.kaiming(keys[-2], (cfg.d_model, cfg.vocab_size), dtype)
    if cfg.frontend == "audio":
        p["frontend"] = {"proj": layers.kaiming(keys[-3], (AUDIO_FEAT_DIM, cfg.d_model), dtype)}
    elif cfg.frontend == "vision":
        p["frontend"] = {"proj": layers.kaiming(keys[-3], (VISION_EMB_DIM, cfg.d_model), dtype)}
    return p


def param_axes(params, cfg: ModelConfig):
    """Logical-axes pytree matching params (leading scan axis on periods)."""
    n_periods, plen, kinds, mlp_kinds = period_structure(cfg)
    paxes = {}
    for j in range(plen):
        # block_axes only inspects dict keys, so stacked params work directly
        ax = blocks.block_axes(params["periods"][f"sub{j}"])
        # prepend the scan axis
        paxes[f"sub{j}"] = jax.tree.map(
            lambda a: (None,) + a, ax, is_leaf=lambda v: isinstance(v, tuple))
    out = {"periods": paxes, "final_norm": (None,)}
    if "embed" in params:
        out["embed"] = ("vocab", "fsdp")
    if "lm_head" in params:
        out["lm_head"] = ("fsdp", "vocab")
    if "frontend" in params:
        out["frontend"] = {"proj": (None, "fsdp")}
    return out


# ----------------------------------------------------------------------------
# embedding / input handling


def _lookup(embed, tokens, vocab_size):
    """Embedding lookup; one-hot matmul inside partial-manual shard_map
    regions (XLA's SPMD partitioner cannot partition a gather under manual
    subaxes — the matmul form is the classic TPU embedding layout anyway)."""
    from ..parallel.sharding import flag
    if flag("embed_onehot"):
        oh = jax.nn.one_hot(tokens, vocab_size, dtype=embed.dtype)
        return jnp.einsum("...v,vd->...d", oh, embed)
    return jnp.take(embed, tokens, axis=0)


def embed_inputs(params, batch, cfg: ModelConfig):
    """Returns (x [B,S,D], labels [B,S] or None, loss_mask [B,S] or None)."""
    if cfg.family == "audio":
        x = jnp.einsum("bsf,fd->bsd", batch["features"], params["frontend"]["proj"])
        return x, batch["labels"], jnp.ones(batch["labels"].shape, jnp.float32)
    if cfg.family == "vlm":
        vis = jnp.einsum("bnf,fd->bnd", batch["vision"].astype(params["embed"].dtype),
                         params["frontend"]["proj"])
        txt = _lookup(params["embed"], batch["tokens"], cfg.vocab_size)
        x = jnp.concatenate([vis, txt], axis=1)
        B, S_text = batch["tokens"].shape
        n_vis = vis.shape[1]
        # next-token labels exist only for text positions
        labels = jnp.concatenate(
            [jnp.zeros((B, n_vis), jnp.int32), batch["tokens"]], axis=1)
        mask = jnp.concatenate(
            [jnp.zeros((B, n_vis), jnp.float32), jnp.ones((B, S_text), jnp.float32)],
            axis=1)
        return x, labels, mask
    tok = batch["tokens"]
    x = _lookup(params["embed"], tok, cfg.vocab_size)
    return x, tok, jnp.ones(tok.shape, jnp.float32)


def unembed(params, x, cfg: ModelConfig):
    if "lm_head" in params:
        return jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return jnp.einsum("bsd,vd->bsv", x, params["embed"])


# ----------------------------------------------------------------------------
# forward


def forward(params, batch, cfg: ModelConfig):
    """Returns (logits [B,S,V], aux_loss scalar)."""
    n_periods, plen, kinds, mlp_kinds = period_structure(cfg)
    x, _, _ = embed_inputs(params, batch, cfg)
    x = shard(x, "batch", None, None)
    S = x.shape[1]
    positions = jnp.arange(S)

    def period_body(carry, pparams):
        x, aux = carry
        for j in range(plen):
            x, a = blocks.run_block(pparams[f"sub{j}"], x, cfg,
                                    kinds[j], mlp_kinds[j], positions)
            aux = aux + a
        return (x, aux), None

    if cfg.remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)
        body = jax.checkpoint(period_body, policy=policy)
    else:
        body = period_body
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               params["periods"])
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, x, cfg)
    return shard(logits, "batch", None, "vocab"), aux


def loss_fn(params, batch, cfg: ModelConfig):
    """Scalar training loss (CE + router aux). Returns (loss, metrics)."""
    logits, aux = forward(params, batch, cfg)
    _, labels, mask = embed_inputs(params, batch, cfg)  # cheap: embeds are DCE'd
    if cfg.causal:
        logits_ = logits[:, :-1]
        labels_ = labels[:, 1:]
        mask_ = mask[:, 1:]
    else:
        logits_, labels_, mask_ = logits, labels, mask
    logp = jax.nn.log_softmax(logits_.astype(jnp.float32), axis=-1)
    from ..parallel.sharding import flag
    if flag("embed_onehot"):
        # gather-free NLL for partial-manual shard_map regions: XLA's SPMD
        # partitioner cannot partition take_along_axis (fwd gather / bwd
        # scatter) under manual subaxes, same constraint as _lookup above
        oh = jax.nn.one_hot(labels_, logp.shape[-1], dtype=logp.dtype)
        nll = -(oh * logp).sum(axis=-1)
    else:
        nll = -jnp.take_along_axis(logp, labels_[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask_.sum(), 1.0)
    ce = (nll * mask_).sum() / denom
    loss = ce + cfg.router_aux_coef * aux
    return loss, {"ce": ce, "aux": aux}


# ----------------------------------------------------------------------------
# serving: prefill + decode


def decode_window(cfg: ModelConfig, context_len: int) -> int:
    return min(context_len, cfg.sliding_window or context_len)


def init_cache(cfg: ModelConfig, batch: int, context_len: int):
    """Zero cache; `pos` counts tokens already processed."""
    dtype = jnp.dtype(cfg.dtype)
    n_periods, plen, kinds, _ = period_structure(cfg)
    W = decode_window(cfg, context_len)

    def one(kind):
        c = blocks.init_block_cache(batch, cfg, kind, W, dtype)
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (n_periods,) + x.shape), c)

    return {
        "pos": jnp.zeros((), jnp.int32),
        "blocks": {f"sub{j}": one(kinds[j]) for j in range(plen)},
    }


def cache_logical_axes(cfg: ModelConfig):
    n_periods, plen, kinds, _ = period_structure(cfg)
    return {
        "pos": (),
        "blocks": {f"sub{j}": jax.tree.map(
            lambda a: (None,) + a, blocks.cache_axes(kinds[j]),
            is_leaf=lambda v: isinstance(v, tuple)) for j in range(plen)},
    }


def decode_step(params, tokens, cache, cfg: ModelConfig):
    """One decode step. tokens [B,1] int32 (text-only decode).

    Returns (logits [B,1,V], new_cache).
    """
    n_periods, plen, kinds, mlp_kinds = period_structure(cfg)
    x = _lookup(params["embed"], tokens, cfg.vocab_size)
    x = shard(x, "batch", None, None)
    pos = cache["pos"]

    def period_body(x, scanned):
        pparams, pcache = scanned
        new_cache = {}
        for j in range(plen):
            x, c = blocks.run_block_decode(pparams[f"sub{j}"], x,
                                           pcache[f"sub{j}"], pos, cfg,
                                           kinds[j], mlp_kinds[j])
            new_cache[f"sub{j}"] = c
        return x, new_cache

    x, new_blocks = jax.lax.scan(period_body, x,
                                 (params["periods"], cache["blocks"]))
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, x, cfg)
    return logits, {"pos": pos + 1, "blocks": new_blocks}


def prefill(params, batch, cfg: ModelConfig, context_len: Optional[int] = None,
            last_logits_only: bool = False):
    """Run the full prompt, building the decode cache.

    Returns (logits [B,S,V] — or [B,1,V] with last_logits_only, the serving
    fast path that avoids materializing/gathering the full-sequence logits —
    and the cache).  Attention caches are written at positions pos % W so a
    subsequent decode continues the ring buffer.
    """
    n_periods, plen, kinds, mlp_kinds = period_structure(cfg)
    x, _, _ = embed_inputs(params, batch, cfg)
    x = shard(x, "batch", None, None)
    B, S, _ = x.shape
    W = decode_window(cfg, context_len or S)
    positions = jnp.arange(S)

    def period_body(x, pparams):
        new_cache = {}
        for j in range(plen):
            p_blk = pparams[f"sub{j}"]
            h = layers.rms_norm(x, p_blk["ln1"], cfg.norm_eps)
            if kinds[j] == "attn":
                h, k, v = layers.run_attention_with_kv(p_blk["attn"], h, cfg,
                                                       positions)
                # last min(W,S) tokens -> ring-buffer slots (pos % W)
                take = min(W, S)
                kw, vw = k[:, -take:], v[:, -take:]
                if take < W:             # cold cache: slots S..W-1 stay empty
                    pad = ((0, 0), (0, W - take), (0, 0), (0, 0))
                    kw, vw = jnp.pad(kw, pad), jnp.pad(vw, pad)
                else:
                    roll = S % W         # rotate so slot = pos % W
                    kw = jnp.roll(kw, roll, axis=1)
                    vw = jnp.roll(vw, roll, axis=1)
                new_cache[f"sub{j}"] = {"k": kw, "v": vw}
                x = x + h
            else:
                # rerun the ssm keeping final state: decode cache = last conv
                # window + final state; cheap second pass is avoided by
                # computing state from the chunked scan (future work) — here
                # we use the sequential tail trick: state after S tokens.
                import repro.models.ssm as ssm_lib
                h2, cache_j = _ssm_prefill(p_blk["ssm"], h, cfg)
                new_cache[f"sub{j}"] = cache_j
                x = x + h2
            if mlp_kinds[j] != "none":
                h = layers.rms_norm(x, p_blk["ln2"], cfg.norm_eps)
                if mlp_kinds[j] == "moe":
                    from . import moe as moe_lib
                    h, _ = moe_lib.run_moe(p_blk["moe"], h, cfg)
                else:
                    h = layers.run_mlp(p_blk["mlp"], h)
                x = x + h
        return x, new_cache

    x, cache_blocks = jax.lax.scan(period_body, x, params["periods"])
    if last_logits_only:
        x = x[:, -1:]
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, x, cfg)
    return logits, {"pos": jnp.asarray(S, jnp.int32), "blocks": cache_blocks}


def _ssm_prefill(p, x, cfg: ModelConfig):
    """Mamba block forward that also returns the decode cache.

    Uses the chunked SSD path with final-state output — the per-token
    sequential scan it replaced emitted ~S tiny HLO steps per layer (1.5M
    all-gathers at 32k prefill; see EXPERIMENTS.md §Perf iteration M1).
    """
    from . import ssm as ssm_lib
    from .layers import rms_norm
    B, S, D = x.shape
    di, N, H, P = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    z, xin, Bc, Cc, dt = ssm_lib._split_proj(p, x, cfg)
    xbc_raw = jnp.concatenate([xin, Bc, Cc], axis=-1)
    xbc = ssm_lib._causal_conv(xbc_raw, p["conv_w"], p["conv_b"])
    xin, Bc, Cc = jnp.split(xbc, [di, di + N], axis=-1)
    dtp = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = xin.reshape(B, S, H, P)

    y, state = ssm_lib.ssd_chunked_with_state(xh, dtp, A, Bc, Cc,
                                              cfg.ssm_chunk)
    y = y + xh * p["D"][None, None, :, None].astype(x.dtype)
    y = y.reshape(B, S, di)
    y = rms_norm(y * jax.nn.silu(z), p["gnorm"], cfg.norm_eps)
    out = jnp.einsum("bsf,fd->bsd", y, p["out_proj"])
    cache = {"state": state, "conv": xbc_raw[:, -(cfg.ssm_conv - 1):, :]}
    return out, cache
