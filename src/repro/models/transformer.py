"""Decoder-stack assembly for every assigned architecture.

One generic stack covers the whole pool via the config's ``layer_pattern``
(attention / mamba interleave), ``moe`` placement, and family-specific
frontends (text embeddings, VLM patch embeddings, audio codebooks).

Structure
---------
* **prefix layers** — layers that break the periodic pattern (deepseek's
  dense layer 0), unrolled with individual params.
* **body** — the remaining ``n_periods × period`` layers.  Params are stacked
  along a leading ``(n_periods,)`` axis and the stack runs under
  ``jax.lax.scan`` (small HLO, fast SPMD partitioning, MaxText-style), with
  ``jax.checkpoint`` on the period body for training remat.  ``period`` is
  ``lcm(len(layer_pattern), moe.every_k_layers)`` so every scan step sees an
  identical layer-kind sequence.

Sharding (see DESIGN.md §5): params FSDP over ``data`` × TP/EP over
``model``; inter-block activations sequence-sharded over ``model`` (Megatron
SP) so the per-device live set stays O(B·S·D/model); the LM-head loss is
computed in sequence chunks against the vocab-parallel embedding, inside a
rematerialized scan — full (B, S, V) logits never exist.
"""

from __future__ import annotations

import dataclasses
import math
import os
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import flags
from .attention import (gqa_attention, gqa_decode, gqa_init, gqa_specs,
                        mla_attention, mla_decode, mla_init, mla_specs)
from .config import ModelConfig
from .layers import (NO_SHARDING, Params, ShardingRules, constrain,
                     dense_init, embed_init, mlp, mlp_init, mlp_specs,
                     rmsnorm, rmsnorm_init)
from .mamba2 import (_dims as mamba_dims, mamba_decode, mamba_forward,
                     mamba_init, mamba_specs)
from .moe import moe_apply, moe_init, moe_specs


# ---------------------------------------------------------------------- #
# Layer layout: prefix + periodic body
# ---------------------------------------------------------------------- #
def layer_layout(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_prefix, period, n_periods)."""
    n_prefix = 1 if (cfg.moe and cfg.moe.first_dense_d_ff) else 0
    period = len(cfg.layer_pattern)
    if cfg.moe and cfg.moe.every_k_layers > 1:
        period = math.lcm(period, cfg.moe.every_k_layers)
    body = cfg.num_layers - n_prefix
    if body % period:
        raise ValueError(
            f"{cfg.name}: body layers {body} not divisible by period {period}")
    return n_prefix, period, body // period


def _layer_ff(cfg: ModelConfig, i: int) -> Optional[int]:
    """d_ff of the dense FF at layer ``i`` (None if the layer has no FF)."""
    if cfg.is_moe_layer(i):
        return None  # MoE instead
    if cfg.moe and cfg.moe.first_dense_d_ff and i == 0:
        return cfg.moe.first_dense_d_ff
    return cfg.d_ff if cfg.d_ff else None


# ---------------------------------------------------------------------- #
# One block: (attention | mamba) + optional (mlp | moe), pre-norm residual
# ---------------------------------------------------------------------- #
def block_init(key, cfg: ModelConfig, i: int, dtype=jnp.bfloat16) -> Params:
    k1, k2 = jax.random.split(key)
    kind = cfg.layer_kind(i)
    p: Params = {"norm1": rmsnorm_init(cfg.d_model, dtype)}
    if kind == "a":
        p["attn"] = (mla_init(k1, cfg, dtype) if cfg.mla
                     else gqa_init(k1, cfg, dtype))
    else:
        p["mixer"] = mamba_init(k1, cfg, dtype)
    ff = _layer_ff(cfg, i)
    if cfg.is_moe_layer(i):
        p["norm2"] = rmsnorm_init(cfg.d_model, dtype)
        p["moe"] = moe_init(k2, cfg, dtype)
    elif ff:
        p["norm2"] = rmsnorm_init(cfg.d_model, dtype)
        p["mlp"] = mlp_init(k2, cfg.d_model, ff, dtype)
    return p


def block_specs(cfg: ModelConfig, i: int, rules: ShardingRules) -> Params:
    kind = cfg.layer_kind(i)
    s: Params = {"norm1": {"scale": rules.logical(None)}}
    if kind == "a":
        s["attn"] = (mla_specs(cfg, rules) if cfg.mla
                     else gqa_specs(cfg, rules))
    else:
        s["mixer"] = mamba_specs(cfg, rules)
    if cfg.is_moe_layer(i):
        s["norm2"] = {"scale": rules.logical(None)}
        s["moe"] = moe_specs(cfg, rules)
    elif _layer_ff(cfg, i):
        s["norm2"] = {"scale": rules.logical(None)}
        s["mlp"] = mlp_specs(rules)
    return s


def block_apply(params: Params, x: jax.Array, cfg: ModelConfig, i: int,
                positions: jax.Array, rules: ShardingRules, impl: str,
                collect_cache: bool = False, cache_len: Optional[int] = None):
    """Full-sequence block.  Returns (x, aux, cache_entry|None)."""
    kind = cfg.layer_kind(i)
    h = rmsnorm(params["norm1"], x, cfg.norm_eps)
    cache = None
    if kind == "a":
        with jax.named_scope("attn"):
            if cfg.mla:
                a = mla_attention(params["attn"], h, cfg, positions, rules,
                                  impl)
            else:
                a = gqa_attention(params["attn"], h, cfg, positions, rules,
                                  impl)
        if collect_cache:
            cache = _attn_cache_from_seq(params["attn"], h, cfg, positions,
                                         cache_len, rules)
    else:
        with jax.named_scope("mixer"):
            if collect_cache:
                a, ssm, conv = mamba_forward(params["mixer"], h, cfg, rules,
                                             impl, return_state=True)
                cache = {"ssm": ssm, "conv": conv}
            else:
                a = mamba_forward(params["mixer"], h, cfg, rules, impl)
    x = x + a
    aux = jnp.zeros((), jnp.float32)
    if "moe" in params:
        h2 = rmsnorm(params["norm2"], x, cfg.norm_eps)
        with jax.named_scope("moe"):
            y, aux = moe_apply(params["moe"], h2, cfg, rules)
        x = x + y
    elif "mlp" in params:
        h2 = rmsnorm(params["norm2"], x, cfg.norm_eps)
        with jax.named_scope("mlp"):
            x = x + mlp(params["mlp"], h2, act=cfg.act, rules=rules)
    x = constrain(x, rules, "batch", "model", None)  # SP between blocks
    return x, aux, cache


def _attn_cache_from_seq(attn_p: Params, h: jax.Array, cfg: ModelConfig,
                         positions: jax.Array, cache_len: int,
                         rules: ShardingRules) -> Params:
    """Recompute the K/V (or MLA latent) of a full sequence into a cache."""
    from .layers import apply_rope
    b, s, _ = h.shape
    pad = cache_len - s
    if cfg.mla:
        m = cfg.mla
        ckv = h @ attn_p["w_dkv"]
        c_kv = rmsnorm(attn_p["kv_norm"], ckv[..., :m.kv_lora_rank],
                       cfg.norm_eps)
        k_rope = apply_rope(ckv[..., m.kv_lora_rank:], positions,
                            cfg.rope_theta)
        entry = jnp.concatenate([c_kv, k_rope], axis=-1)
        entry = jnp.pad(entry, ((0, 0), (0, pad), (0, 0)))
        return {"ckv": constrain(entry, rules, "batch", "model", None)}
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    k = (h @ attn_p["wk"]).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
    v = (h @ attn_p["wv"]).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        k = rmsnorm(attn_p["k_norm"], k, cfg.norm_eps)
    k = apply_rope(k, positions[:, None, :], cfg.rope_theta)
    k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return {"k": constrain(k, rules, "batch", None, "model", None),
            "v": constrain(v, rules, "batch", None, "model", None)}


def block_decode(params: Params, x: jax.Array, cache: Params,
                 cfg: ModelConfig, i: int, pos: jax.Array,
                 rules: ShardingRules):
    """One-token block step.  x: (B, 1, D).  Returns (x, new_cache)."""
    kind = cfg.layer_kind(i)
    h = rmsnorm(params["norm1"], x, cfg.norm_eps)
    if kind == "a":
        if cfg.mla:
            a, ckv = mla_decode(params["attn"], h, cache["ckv"], pos, cfg,
                                rules)
            new_cache = {"ckv": ckv}
        else:
            a, kc, vc = gqa_decode(params["attn"], h, cache["k"], cache["v"],
                                   pos, cfg, rules)
            new_cache = {"k": kc, "v": vc}
    else:
        a, ssm, conv = mamba_decode(params["mixer"], h, cache["ssm"],
                                    cache["conv"], cfg, rules)
        new_cache = {"ssm": ssm, "conv": conv}
    x = x + a
    if "moe" in params:
        h2 = rmsnorm(params["norm2"], x, cfg.norm_eps)
        y, _ = moe_apply(params["moe"], h2, cfg, rules)
        x = x + y
    elif "mlp" in params:
        h2 = rmsnorm(params["norm2"], x, cfg.norm_eps)
        x = x + mlp(params["mlp"], h2, act=cfg.act, rules=rules)
    return constrain(x, rules, "batch", None, None), new_cache


def block_cache_init(cfg: ModelConfig, i: int, batch: int, cache_len: int,
                     dtype=jnp.bfloat16) -> Params:
    kind = cfg.layer_kind(i)
    if kind == "a":
        if cfg.mla:
            m = cfg.mla
            return {"ckv": jnp.zeros(
                (batch, cache_len, m.kv_lora_rank + m.qk_rope_head_dim),
                dtype)}
        hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        shape = (batch, hkv, cache_len, hd)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    s, d_in, nh = mamba_dims(cfg)
    return {"ssm": jnp.zeros((batch, nh, s.d_state, s.head_dim), jnp.float32),
            "conv": jnp.zeros((batch, s.d_conv - 1, d_in), dtype)}


def block_cache_specs(cfg: ModelConfig, i: int, rules: ShardingRules) -> Params:
    """Decode caches: KV sequence-sharded over 'model' (head-count agnostic)."""
    kind = cfg.layer_kind(i)
    if kind == "a":
        if cfg.mla:
            return {"ckv": rules.logical("batch", "model", None)}
        kv = rules.logical("batch", None, "model", None)
        return {"k": kv, "v": kv}
    return {"ssm": rules.logical("batch", "model", None, None),
            "conv": rules.logical("batch", None, "model")}


# ---------------------------------------------------------------------- #
# Full model params
# ---------------------------------------------------------------------- #
def init_params(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    n_prefix, period, n_periods = layer_layout(cfg)
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    params: Params = {}
    vp = cfg.padded_vocab
    if cfg.family == "audio":
        params["embed"] = embed_init(
            k_embed, (cfg.num_codebooks, vp, cfg.d_model), dtype)
    else:
        params["embed"] = embed_init(k_embed, (vp, cfg.d_model), dtype)
    if not cfg.tie_embeddings:
        if cfg.family == "audio":
            params["lm_head"] = embed_init(
                k_head, (cfg.num_codebooks, vp, cfg.d_model), dtype)
        else:
            params["lm_head"] = embed_init(k_head, (vp, cfg.d_model), dtype)

    keys = jax.random.split(k_layers, cfg.num_layers)
    params["prefix"] = [block_init(keys[i], cfg, i, dtype)
                        for i in range(n_prefix)]

    def one_period(p_idx):
        return {"layers": [
            block_init(keys[n_prefix + p_idx * period + j],
                       cfg, n_prefix + p_idx * period + j, dtype)
            for j in range(period)]}
    periods = [one_period(p) for p in range(n_periods)]
    params["body"] = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *periods)
    params["final_norm"] = rmsnorm_init(cfg.d_model, dtype)
    return params


def param_specs(cfg: ModelConfig, rules: ShardingRules) -> Params:
    n_prefix, period, n_periods = layer_layout(cfg)
    specs: Params = {}
    # vocab-parallel embedding/unembedding: vocab over 'model', d replicated
    # (a d-over-'data' shard would fight the batch sharding and un-shard the
    # whole residual stream — measured in EXPERIMENTS.md §Perf iter 3)
    if cfg.family == "audio":
        specs["embed"] = rules.logical(None, "model", None)
    else:
        specs["embed"] = rules.logical("model", None)
    if not cfg.tie_embeddings:
        specs["lm_head"] = (rules.logical(None, "model", None)
                            if cfg.family == "audio"
                            else rules.logical("model", None))
    specs["prefix"] = [block_specs(cfg, i, rules) for i in range(n_prefix)]
    one = {"layers": [block_specs(cfg, n_prefix + j, rules)
                      for j in range(period)]}
    # body params have a leading (n_periods,) stack axis
    from jax.sharding import PartitionSpec as P
    specs["body"] = jax.tree_util.tree_map(
        lambda sp: P(*((None,) + tuple(sp))), one,
        is_leaf=lambda x: isinstance(x, P))
    specs["final_norm"] = {"scale": rules.logical(None)}
    return specs


# ---------------------------------------------------------------------- #
# Frontends: tokens -> embeddings
# ---------------------------------------------------------------------- #
def _vp_gather(table: jax.Array, toks: jax.Array,
               rules: ShardingRules) -> jax.Array:
    """Vocab-parallel embedding lookup, Megatron-style.

    GSPMD's gather partitioner replicates the table (a full-table
    all-gather every step, and full-table grad all-reduces in reverse), so
    the masked-local-gather + psum_scatter schedule is written explicitly
    under ``shard_map``: each model rank gathers from its vocab shard,
    out-of-range rows contribute zero, and the reduction lands already
    sequence-sharded (SP).  Reverse-mode gives scatter-add into the local
    shard + all-gather — no table-sized collectives anywhere.
    """
    ms = rules.model_size
    vp, d = table.shape
    b, s = toks.shape
    if (rules.model is None or ms <= 1 or vp % ms or s % ms):
        return jnp.take(table, toks, axis=0)

    def local(tab, tk):
        r = jax.lax.axis_index(rules.model)
        vshard = tab.shape[0]
        lo = r * vshard
        loc = jnp.clip(tk - lo, 0, vshard - 1)
        x = jnp.where(((tk >= lo) & (tk < lo + vshard))[..., None],
                      jnp.take(tab, loc, axis=0), 0)
        # reduce + scatter onto the sequence axis: arrives SP-sharded
        return jax.lax.psum_scatter(x, rules.model, scatter_dimension=1,
                                    tiled=True)

    from jax.sharding import PartitionSpec as P
    return jax.shard_map(
        local,
        in_specs=(P(rules.model, None), P(rules.batch, None)),
        out_specs=P(rules.batch, rules.model, None))(table, toks)


def embed_tokens(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array],
                 rules: ShardingRules) -> Tuple[jax.Array, jax.Array]:
    """Returns (x (B, S, D), positions (B, S))."""
    if cfg.family == "audio":
        toks = batch["tokens"]                     # (B, S, K)
        b, s, k = toks.shape
        # sum of per-codebook embeddings (MusicGen delay pattern is applied
        # by the data stub; the backbone just sums)
        x = sum(_vp_gather(params["embed"][i], toks[..., i], rules)
                for i in range(cfg.num_codebooks))
    elif cfg.family == "vlm":
        patches = batch["patch_embeds"].astype(params["embed"].dtype)
        toks = batch["tokens"]                     # (B, S_text)
        text = _vp_gather(params["embed"], toks, rules)
        x = jnp.concatenate([patches, text], axis=1)
        b, s = x.shape[0], x.shape[1]
    else:
        toks = batch["tokens"]                     # (B, S)
        x = _vp_gather(params["embed"], toks, rules)
        b, s = toks.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return constrain(x, rules, "batch", "model", None), positions


# ---------------------------------------------------------------------- #
# Forward over the stack
# ---------------------------------------------------------------------- #
def forward(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            rules: ShardingRules = NO_SHARDING, impl: str = "auto",
            remat: bool = True, collect_cache: bool = False,
            cache_len: Optional[int] = None):
    """Full-sequence forward.  Returns (h, aux[, caches])."""
    n_prefix, period, n_periods = layer_layout(cfg)
    x, positions = embed_tokens(params, cfg, batch, rules)
    aux_total = jnp.zeros((), jnp.float32)
    prefix_caches = []
    for i, lp in enumerate(params["prefix"]):
        x, aux, cache = block_apply(lp, x, cfg, i, positions, rules, impl,
                                    collect_cache, cache_len)
        aux_total = aux_total + aux
        prefix_caches.append(cache)

    def period_body(x, period_params):
        aux_p = jnp.zeros((), jnp.float32)
        caches = []
        for j in range(period):
            blk = partial(block_apply, cfg=cfg, i=n_prefix + j,
                          positions=positions, rules=rules, impl=impl,
                          collect_cache=collect_cache, cache_len=cache_len)
            if remat and not collect_cache and period > 1 and not os.environ.get('REPRO_NO_NESTED_REMAT'):
                # nested remat: with multi-layer periods (jamba: 8) the
                # period-level checkpoint alone keeps a whole period of
                # activations live — re-checkpoint each block so the peak
                # is one layer (72 GB -> ~15 GB/device on jamba train_4k)
                blk = jax.checkpoint(blk, prevent_cse=False)
            x, aux, cache = blk(period_params["layers"][j], x)
            aux_p = aux_p + aux
            caches.append(cache)
        if collect_cache:
            return x, (aux_p, {"layers": caches})
        return x, aux_p

    body = period_body
    if remat and not collect_cache:
        body = jax.checkpoint(period_body, prevent_cse=False)
    x, scanned = jax.lax.scan(body, x, params["body"],
                              unroll=flags.scan_unroll_layers())
    if collect_cache:
        aux_scan, body_caches = scanned
        caches = {"prefix": prefix_caches, "body": body_caches}
        aux_total = aux_total + jnp.sum(aux_scan)
        h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return h, aux_total, caches
    aux_total = aux_total + jnp.sum(scanned)
    h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return h, aux_total


# ---------------------------------------------------------------------- #
# Vocab-parallel chunked cross-entropy
# ---------------------------------------------------------------------- #
def _unembed(params: Params, cfg: ModelConfig) -> jax.Array:
    w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return w  # (Vp, D) or (K, Vp, D)


def _mask_pad_logits(logits: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                    logits.ndim - 1)
    return jnp.where(iota < cfg.vocab_size, logits, -1e30)


def chunked_ce_loss(params: Params, cfg: ModelConfig, h: jax.Array,
                    labels: jax.Array, rules: ShardingRules = NO_SHARDING,
                    chunk: int = 512, z_loss: float = 1e-4) -> jax.Array:
    """Mean CE over labels >= 0.  h: (B, S, D); labels: (B, S[, K]).

    The sequence is processed in chunks inside a rematerialized scan so the
    full (B, S, V) logits are never resident; the vocab dimension stays
    sharded over ``model`` end-to-end (lse/gather via masked reductions,
    which GSPMD turns into partial-reduce + psum — no logits all-gather).
    """
    w = _unembed(params, cfg).astype(jnp.bfloat16)
    b, s, d = h.shape
    audio = cfg.family == "audio"
    chunk = min(chunk, s)
    n_chunks = -(-s // chunk)
    pad = n_chunks * chunk - s
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        lab_pad = ((0, 0), (0, pad)) + (((0, 0),) if audio else ())
        labels = jnp.pad(labels, lab_pad, constant_values=-1)
    # keep h sequence-sharded: the CE cotangent then re-enters the backward
    # layer scan seq-sharded instead of replicated (per-layer AG otherwise)
    h = constrain(h, rules, "batch", "model", None)

    def step(carry, i):
        loss_sum, count = carry
        hs = jax.lax.dynamic_slice_in_dim(h, i * chunk, chunk, 1)
        ls = jax.lax.dynamic_slice_in_dim(labels, i * chunk, chunk, 1)
        if audio:
            logits = jnp.einsum("bsd,kvd->bskv", hs, w).astype(jnp.float32)
            logits = constrain(logits, rules, "batch", None, None, "model")
        else:
            logits = jnp.einsum("bsd,vd->bsv", hs, w).astype(jnp.float32)
            logits = constrain(logits, rules, "batch", None, "model")
        if cfg.padded_vocab != cfg.vocab_size:  # mask pad rows out of softmax
            pad_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                                logits.ndim - 1)
            logits = jnp.where(pad_iota < cfg.vocab_size, logits, -1e30)
        m = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
        lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
        v = logits.shape[-1]
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                        logits.ndim - 1)
        ll = jnp.sum(jnp.where(iota == ls[..., None], logits, 0.0), axis=-1)
        valid = ls >= 0
        tok_loss = lse - ll + z_loss * lse ** 2
        loss_sum = loss_sum + jnp.sum(jnp.where(valid, tok_loss, 0.0))
        count = count + jnp.sum(valid)
        return (loss_sum, count), None

    init = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
    (loss_sum, count), _ = jax.lax.scan(
        jax.checkpoint(step, prevent_cse=False), init, jnp.arange(n_chunks),
        unroll=flags.scan_unroll_inner())
    return loss_sum / jnp.maximum(count, 1).astype(jnp.float32)


def loss_fn(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            rules: ShardingRules = NO_SHARDING, impl: str = "auto",
            remat: bool = True, ce_chunk: int = 512) -> Tuple[jax.Array, Dict]:
    """Training loss = chunked CE + MoE aux.  batch must carry 'labels'."""
    h, aux = forward(params, cfg, batch, rules, impl, remat)
    if cfg.family == "vlm":
        n_patch = batch["patch_embeds"].shape[1]
        h = h[:, n_patch:]
    ce = chunked_ce_loss(params, cfg, h, batch["labels"], rules, ce_chunk)
    return ce + aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------- #
# Serving: prefill + decode
# ---------------------------------------------------------------------- #
def init_caches(cfg: ModelConfig, batch_size: int, cache_len: int,
                dtype=jnp.bfloat16) -> Params:
    n_prefix, period, n_periods = layer_layout(cfg)
    prefix = [block_cache_init(cfg, i, batch_size, cache_len, dtype)
              for i in range(n_prefix)]
    one = {"layers": [block_cache_init(cfg, n_prefix + j, batch_size,
                                       cache_len, dtype)
                      for j in range(period)]}
    body = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n_periods,) + x.shape), one)
    return {"prefix": prefix, "body": body}


def cache_specs(cfg: ModelConfig, rules: ShardingRules) -> Params:
    from jax.sharding import PartitionSpec as P
    n_prefix, period, n_periods = layer_layout(cfg)
    prefix = [block_cache_specs(cfg, i, rules) for i in range(n_prefix)]
    one = {"layers": [block_cache_specs(cfg, n_prefix + j, rules)
                      for j in range(period)]}
    body = jax.tree_util.tree_map(
        lambda sp: P(*((None,) + tuple(sp))), one,
        is_leaf=lambda x: isinstance(x, P))
    return {"prefix": prefix, "body": body}


def decode_step(params: Params, cfg: ModelConfig, caches: Params,
                tokens: jax.Array, pos: jax.Array,
                rules: ShardingRules = NO_SHARDING
                ) -> Tuple[jax.Array, Params]:
    """One decode step.  tokens: (B, 1) or (B, 1, K) audio; pos: (B,).

    Returns (logits (B, V) or (B, K, V), new caches).
    """
    n_prefix, period, n_periods = layer_layout(cfg)
    if cfg.family == "audio":
        x = sum(jnp.take(params["embed"][i], tokens[..., i], axis=0)
                for i in range(cfg.num_codebooks))
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
    x = constrain(x, rules, "batch", None, None)

    new_prefix = []
    for i, lp in enumerate(params["prefix"]):
        x, nc = block_decode(lp, x, caches["prefix"][i], cfg, i, pos, rules)
        new_prefix.append(nc)

    def step(x, inp):
        pp, cc = inp
        new_cc = []
        for j in range(period):
            x, ncj = block_decode(pp["layers"][j], x, cc["layers"][j], cfg,
                                  n_prefix + j, pos, rules)
            new_cc.append(ncj)
        return x, {"layers": new_cc}

    x, new_body = jax.lax.scan(step, x, (params["body"], caches["body"]),
                               unroll=flags.scan_unroll_layers())
    h = rmsnorm(params["final_norm"], x, cfg.norm_eps)[:, 0]   # (B, D)
    w = _unembed(params, cfg).astype(jnp.bfloat16)
    if cfg.family == "audio":
        logits = jnp.einsum("bd,kvd->bkv", h, w).astype(jnp.float32)
        logits = constrain(logits, rules, "batch", None, "model")
    else:
        logits = jnp.einsum("bd,vd->bv", h, w).astype(jnp.float32)
        logits = constrain(logits, rules, "batch", "model")
    return _mask_pad_logits(logits, cfg), {"prefix": new_prefix, "body": new_body}


def prefill(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            cache_len: int, rules: ShardingRules = NO_SHARDING,
            impl: str = "auto") -> Tuple[jax.Array, Params]:
    """Process a full prompt; returns (last-position logits (B, ...), caches)."""
    h, _, caches = forward(params, cfg, batch, rules, impl, remat=False,
                           collect_cache=True, cache_len=cache_len)
    last = h[:, -1]                                            # (B, D)
    w = _unembed(params, cfg).astype(jnp.bfloat16)
    if cfg.family == "audio":
        logits = jnp.einsum("bd,kvd->bkv", last, w).astype(jnp.float32)
        logits = constrain(logits, rules, "batch", None, "model")
    else:
        logits = jnp.einsum("bd,vd->bv", last, w).astype(jnp.float32)
        logits = constrain(logits, rules, "batch", "model")
    return _mask_pad_logits(logits, cfg), caches
