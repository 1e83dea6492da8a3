"""Mixture-of-Experts with expert parallelism — the paper's shuffle on the
model critical path.

Token→expert dispatch is a distributed hash-partition-with-capacity exactly
like ``repro.dataframe.shuffle``: rows (tokens) are routed to destination
partitions (experts) under a static per-destination capacity, overflow is
dropped-and-counted, and the data movement is one all-to-all over the mesh.

Two implementations:

* ``moe_apply`` (production) — **sort-based grouped dispatch**, the same
  algorithm as the dataframe shuffle's bucketize step (stable sort by
  destination + rank-within-bucket + capacity drop), vectorized per token
  group.  Peak memory is the (G, E, C, D) expert buffer — the actual data —
  instead of GShard's (T, E, C) one-hot dispatch tensors, which are O(T²)
  per group and unusable at 4k×256 batch.  With the expert axis sharded over
  ``model``, GSPMD lowers the group→expert layout change to the same
  all-to-all collective the dataframe engine issues explicitly.
* ``moe_apply_einsum`` (oracle) — the classic GShard one-hot einsum
  formulation, kept for small-shape parity tests.

Router: softmax top-k with renormalization, load-balance auxiliary loss
(Switch-style), shared experts always-on (DeepSeek-MoE).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import (NO_SHARDING, Params, ShardingRules, constrain,
                     dense_init, mlp, mlp_init, mlp_specs)


def moe_init(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    m = cfg.moe
    d, ff = cfg.d_model, m.d_ff_expert
    k_r, k_e, k_s = jax.random.split(key, 3)
    ke = jax.random.split(k_e, 3)
    p = {
        "router": dense_init(k_r, (d, m.num_experts), 0, jnp.float32),
        "experts": {
            "w_gate": dense_init(ke[0], (m.num_experts, d, ff), 1, dtype),
            "w_up": dense_init(ke[1], (m.num_experts, d, ff), 1, dtype),
            "w_down": dense_init(ke[2], (m.num_experts, ff, d), 1, dtype),
        },
    }
    if m.num_shared:
        p["shared"] = mlp_init(k_s, d, ff * m.num_shared, dtype)
    return p


def moe_specs(cfg: ModelConfig, rules: ShardingRules) -> Params:
    m = cfg.moe
    s = {
        "router": rules.logical("fsdp", None),
        "experts": {
            # EP: experts over 'model', other dims replicated — the shuffle
            # dispatch runs under shard_map with these exact in_specs, and
            # the fp32 optimizer moments regain a 'data' dim via
            # ``train.step state_specs`` (2-D ZeRO) so big MoE archs fit.
            "w_gate": rules.logical("model", None, None),
            "w_up": rules.logical("model", None, None),
            "w_down": rules.logical("model", None, None),
        },
    }
    if m.num_shared:
        s["shared"] = mlp_specs(rules)
    return s


def _route(params: Params, x: jax.Array, cfg: ModelConfig
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Router: (topv, topi, aux_loss).  x: (..., D)."""
    m = cfg.moe
    e, k = m.num_experts, m.top_k
    logits = x.astype(jnp.float32) @ params["router"]          # (..., E)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)                       # (..., k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch): E * mean(frac_tokens * frac_probs)
    flat_i = topi.reshape(-1, k)
    flat_p = probs.reshape(-1, e)
    onehot_all = jax.nn.one_hot(flat_i, e, dtype=jnp.float32)  # (T, k, E)
    frac_tokens = onehot_all.sum(1).mean(0)
    frac_probs = flat_p.mean(0)
    aux = m.router_aux_weight * e * jnp.sum(frac_tokens * frac_probs)
    return topv, topi, aux


def expert_capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    m = cfg.moe
    cap = int(m.capacity_factor * tokens_per_group * m.top_k / m.num_experts)
    return max(8, -(-max(cap, m.top_k) // 8) * 8)


def moe_apply(params: Params, x: jax.Array, cfg: ModelConfig,
              rules: ShardingRules = NO_SHARDING
              ) -> Tuple[jax.Array, jax.Array]:
    """MoE layer dispatcher.  x: (B, S, D) -> (y, aux).

    Under SP training rules the token→expert trip runs through the
    dataframe-engine shuffle inside shard_map (``moe_apply_shuffle``) —
    explicit all-to-alls instead of GSPMD-inferred collectives, which
    otherwise psum a full (B, S·k, D) f32 tensor over 'model' at the
    combine gather (measured 64× the minimal wire bytes; EXPERIMENTS.md
    §Perf cell 2).  Elsewhere (single device, TP decode) the grouped
    GSPMD formulation below is used.
    """
    m = cfg.moe
    b, s, d = x.shape
    if (rules.model is not None and not rules.tp_weights
            and m.num_experts % rules.model_size == 0
            and s % rules.model_size == 0):
        return moe_apply_shuffle(params, x, cfg, rules)
    return moe_apply_grouped(params, x, cfg, rules)


def moe_apply_grouped(params: Params, x: jax.Array, cfg: ModelConfig,
                      rules: ShardingRules = NO_SHARDING
                      ) -> Tuple[jax.Array, jax.Array]:
    """Sort-based grouped capacity dispatch (GSPMD global view).

    Each batch row is a dispatch group (G = B, Tg = S); the shuffle runs
    group-locally so all gathers/scatters stay on the data-sharded batch
    axis, and the only cross-device movement is the (G, E, C, D) buffer's
    group→expert resharding — the MoE all-to-all.
    """
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.num_experts, m.top_k
    cap = expert_capacity(cfg, s)

    x = constrain(x, rules, "batch", None, None)
    topv, topi, aux = _route(params, x, cfg)                   # (B, S, k)

    # --- bucketize (the dataframe-shuffle algorithm, per group) --------- #
    flat_e = topi.reshape(b, s * k)
    order = jnp.argsort(flat_e, axis=1, stable=True)           # (B, S*k)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    # stable rank within expert bucket
    start = jax.vmap(lambda row: jnp.searchsorted(row, row, side="left"))(
        sorted_e)
    rank = jnp.arange(s * k, dtype=jnp.int32)[None] - start.astype(jnp.int32)
    slot = jnp.where(rank < cap, sorted_e * cap + rank, e * cap)
    token_of = (order // k).astype(jnp.int32)                  # source token

    # send buffer: buf_src[slot] = source token index (sentinel s => zeros)
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    buf_src = jnp.full((b, e * cap), s, jnp.int32)
    buf_src = buf_src.at[rows, slot].set(token_of, mode="drop")

    x_pad = jnp.concatenate([x, jnp.zeros((b, 1, d), x.dtype)], axis=1)
    ex_in = jnp.take_along_axis(x_pad, buf_src[..., None], axis=1)
    ex_in = ex_in.reshape(b, e, cap, d)
    # group→expert resharding: THE all-to-all (experts sharded over 'model')
    ex_in = constrain(ex_in, rules, "batch", "model", None, None)

    w = params["experts"]
    h_g = jnp.einsum("becd,edf->becf", ex_in, w["w_gate"])
    h_u = jnp.einsum("becd,edf->becf", ex_in, w["w_up"])
    h = jax.nn.silu(h_g.astype(jnp.float32)).astype(x.dtype) * h_u
    ex_out = jnp.einsum("becf,efd->becd", h, w["w_down"])
    ex_out = constrain(ex_out, rules, "batch", "model", None, None)

    # --- combine: expert→group return trip ------------------------------ #
    inv = jnp.argsort(order, axis=1)                           # flat -> sorted
    my_slot = jnp.take_along_axis(slot, inv, axis=1)           # (B, S*k)
    out_pad = jnp.concatenate(
        [ex_out.reshape(b, e * cap, d),
         jnp.zeros((b, 1, d), ex_out.dtype)], axis=1)
    idx = jnp.minimum(my_slot, e * cap)                        # dropped -> 0row
    vals = jnp.take_along_axis(out_pad, idx[..., None], axis=1)  # (B, S*k, D)
    y = (vals.reshape(b, s, k, d)
         * topv.reshape(b, s, k, 1).astype(vals.dtype)).sum(axis=2)
    y = constrain(y, rules, "batch", None, None)

    if m.num_shared:
        y = y + mlp(params["shared"], x, act="silu", rules=rules)
    return y, aux


def moe_apply_shuffle(params: Params, x: jax.Array, cfg: ModelConfig,
                      rules: ShardingRules) -> Tuple[jax.Array, jax.Array]:
    """Token dispatch through the dataframe-engine shuffle (shard_map).

    This IS the paper's mechanism on the model's critical path: each
    (data, model) shard owns its sequence slice of tokens (SP), routes
    (token-vector, local-expert-id, provenance) rows to expert-owning ranks
    with the capacity-based all-to-all ``repro.dataframe.shuffle``, runs the
    expert FFN as the *core local operator*, and shuffles results back by
    provenance — two explicit all-to-alls of exactly the dispatched rows,
    instead of GSPMD-inferred full-tensor all-reduces.
    """
    from ..comm import get_communicator
    from ..dataframe.shuffle import shuffle as df_shuffle
    from ..dataframe.table import Table
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    b, s, d = x.shape
    e, k = m.num_experts, m.top_k
    ms = rules.model_size
    e_loc = e // ms
    axis = rules.model
    b_axes = rules.batch if isinstance(rules.batch, tuple) else (rules.batch,)
    all_axes = tuple(a for a in b_axes if a) + (axis,)
    x = constrain(x, rules, "batch", "model", None)

    def body(xl, router, wg, wu, wd):
        # xl: (b_l, s_l, d); router: (d, E); wg/wu/wd: (e_loc, d|f, ...)
        # the paper's modular communicator, on the model's critical path:
        # the dispatch all-to-alls run on whichever collective schedule the
        # config selects (xla = native, ring = Gloo-analogue, bruck = UCC)
        comm = get_communicator(m.communicator, axis)
        r = comm.rank()
        b_l, s_l, _ = xl.shape
        t = b_l * s_l
        xt = xl.reshape(t, d)

        # --- route (local tokens) ---------------------------------------- #
        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(probs, k)                  # (t, k)
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
        # global load-balance aux (partials psummed over every sharded axis)
        onehot = jax.nn.one_hot(topi.reshape(-1), e, dtype=jnp.float32)
        tok_sum = jax.lax.psum(onehot.sum(0), all_axes)
        prob_sum = jax.lax.psum(probs.sum(0), all_axes)
        n_tok = jax.lax.psum(jnp.float32(t), all_axes)
        aux = m.router_aux_weight * e * jnp.sum(
            (tok_sum / (n_tok * k)) * (prob_sum / n_tok)) * k

        # --- outbound shuffle: rows = (x-vector, local expert, provenance) #
        tk = t * k
        flat_e = topi.reshape(tk)
        dest = (flat_e // e_loc).astype(jnp.int32)            # owning rank
        rows = Table({
            "x": jnp.repeat(xt, k, axis=0),                   # (t*k, d)
            "eloc": (flat_e % e_loc).astype(jnp.int32),
            "srcslot": jnp.arange(tk, dtype=jnp.int32),
            "src": jnp.full((tk,), r, jnp.int32),
        }, jnp.asarray(tk, jnp.int32))
        cap_send = max(8, -(-int(m.capacity_factor * tk) // (8 * ms)) * 8)
        recv, stats = df_shuffle(rows, comm, dest=dest,
                                 bucket_capacity=cap_send,
                                 out_capacity=ms * cap_send)

        # --- core local operator: group by local expert, batched FFN ----- #
        rcap = ms * cap_send
        valid = recv.valid_mask()
        eloc = jnp.where(valid, recv.col("eloc"), e_loc)
        order = jnp.argsort(eloc, stable=True)
        sorted_e = jnp.take(eloc, order)
        start = jnp.searchsorted(sorted_e, sorted_e, side="left")
        rank = jnp.arange(rcap, dtype=jnp.int32) - start.astype(jnp.int32)
        # per-local-expert capacity: 2x the balanced share, never more than
        # the total rows that can arrive (tight when e_loc == 1)
        cap2 = min(max(8, -(-int(rcap * 2) // (8 * e_loc)) * 8),
                   -(-rcap // 8) * 8)
        slot = jnp.where((sorted_e < e_loc) & (rank < cap2),
                         sorted_e * cap2 + rank, e_loc * cap2)
        xs = jnp.take(recv.col("x"), order, axis=0)           # (rcap, d)
        buf = jnp.zeros((e_loc * cap2, d), xs.dtype)
        buf = buf.at[slot].set(xs, mode="drop")
        ex_in = buf.reshape(e_loc, cap2, d)
        h_g = jnp.einsum("ecd,edf->ecf", ex_in, wg)
        h_u = jnp.einsum("ecd,edf->ecf", ex_in, wu)
        h = jax.nn.silu(h_g.astype(jnp.float32)).astype(xs.dtype) * h_u
        ex_out = jnp.einsum("ecf,efd->ecd", h, wd).reshape(e_loc * cap2, d)

        # un-group: value for each received row (dropped-by-cap2 -> zero)
        out_pad = jnp.concatenate(
            [ex_out, jnp.zeros((1, d), ex_out.dtype)], axis=0)
        vals_sorted = jnp.take(out_pad, jnp.minimum(slot, e_loc * cap2),
                               axis=0)
        inv = jnp.argsort(order)
        vals = jnp.take(vals_sorted, inv, axis=0)             # recv order

        # --- return shuffle by provenance -------------------------------- #
        back_tbl = Table({
            "y": vals,
            "srcslot": recv.col("srcslot"),
        }, recv.row_count)
        back_dest = jnp.where(valid, recv.col("src"), ms)
        back, _ = df_shuffle(back_tbl, comm, dest=back_dest,
                             bucket_capacity=cap_send,
                             out_capacity=tk)

        # --- combine at the source ---------------------------------------#
        y_rows = jnp.zeros((tk + 1, d), xl.dtype)
        bslot = jnp.where(back.valid_mask(), back.col("srcslot"), tk)
        y_rows = y_rows.at[bslot].set(
            back.col("y").astype(xl.dtype), mode="drop")[:tk]
        y = (y_rows.reshape(t, k, d)
             * topv.reshape(t, k, 1).astype(xl.dtype)).sum(axis=1)
        return y.reshape(b_l, s_l, d), aux[None]

    bspec = rules.batch
    y, aux = jax.shard_map(
        body,
        in_specs=(P(bspec, axis, None), P(), P(axis, None, None),
                  P(axis, None, None), P(axis, None, None)),
        out_specs=(P(bspec, axis, None), P(None)),
        check_vma=False,
    )(x, params["router"], params["experts"]["w_gate"],
      params["experts"]["w_up"], params["experts"]["w_down"])
    aux = aux.reshape(-1)[0]

    if m.num_shared:
        y = y + mlp(params["shared"], x, act="silu", rules=rules)
    return y, aux


def moe_apply_einsum(params: Params, x: jax.Array, cfg: ModelConfig,
                     rules: ShardingRules = NO_SHARDING
                     ) -> Tuple[jax.Array, jax.Array]:
    """GShard one-hot einsum dispatch (oracle for small shapes).

    Capacity ranks are computed per batch-row group so drop behaviour
    matches ``moe_apply`` exactly.
    """
    m = cfg.moe
    b, s, d = x.shape
    t = s                                        # tokens per group
    e, k = m.num_experts, m.top_k
    cap = expert_capacity(cfg, s)

    topv, topi, aux = _route(params, x, cfg)     # (B, S, k)

    flat_e = topi.reshape(b, t * k)
    oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)            # (B, T*k, E)
    # stable rank of each (token, choice) within its expert queue.  Ties
    # between the k choices of one token resolve by expert id (sort order in
    # moe_apply), which one_hot cumsum reproduces since each row has one hit.
    rank = (jnp.cumsum(oh, axis=1) - oh)[
        rows_b := jnp.arange(b)[:, None], jnp.arange(t * k)[None], flat_e]
    keep = rank < cap
    slot_oh = jax.nn.one_hot(jnp.where(keep, rank, cap), cap, dtype=x.dtype)
    exp_oh = jax.nn.one_hot(flat_e, e, dtype=x.dtype)
    disp_tk = exp_oh[..., None] * slot_oh[..., None, :]        # (B,T*k,E,C)
    disp = disp_tk.reshape(b, t, k, e, cap).sum(2)             # (B,T,E,C)
    comb = (disp_tk * topv.reshape(b, t * k)[..., None, None]
            ).reshape(b, t, k, e, cap).sum(2)

    ex_in = jnp.einsum("btec,btd->becd", disp, x)
    ex_in = constrain(ex_in, rules, "batch", "model", None, None)
    w = params["experts"]
    h_g = jnp.einsum("becd,edf->becf", ex_in, w["w_gate"])
    h_u = jnp.einsum("becd,edf->becf", ex_in, w["w_up"])
    h = jax.nn.silu(h_g.astype(jnp.float32)).astype(x.dtype) * h_u
    ex_out = jnp.einsum("becf,efd->becd", h, w["w_down"])
    y = jnp.einsum("btec,becd->btd", comb, ex_out)

    if m.num_shared:
        y = y + mlp(params["shared"], x, act="silu", rules=rules)
    return y, aux
