"""Shared transformer building blocks (pure-jnp, shard-friendly).

Everything here is written against *stacked* per-layer parameter trees so
model bodies can ``lax.scan`` over layers (small HLO, fast 512-device
compiles — the same trick MaxText uses).

Attention uses a flash-style *chunked* path by default (``lax.scan`` over
query chunks) so that the 32k prefill cells never materialise an
``S x S`` score tensor.  The Pallas kernels in ``repro.kernels`` are
drop-in replacements for the TPU target; the chunked jnp path is the
portable oracle that the dry-run lowers.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

PyTree = Any

NEG_INF = -2.0e38


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: Optional[float] = None):
    """Truncated-normal fan-in init."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms / rotary
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(dt)


def gated_rms_norm(x, gate, w, eps: float = 1e-5):
    """Mamba2-style norm(x * silu(gate))."""
    dt = x.dtype
    x = x.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(dt)


def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, D]; positions: [..., S] int32."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta))  # [D/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]  # [..., S, 1, D/2]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attn(key, cfg: ModelConfig, pdt) -> Dict[str, jax.Array]:
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, hq * dh), pdt),
        "wk": dense_init(ks[1], (d, hkv * dh), pdt),
        "wv": dense_init(ks[2], (d, hkv * dh), pdt),
        "wo": dense_init(ks[3], (hq * dh, d), pdt, scale=1.0 / math.sqrt(hq * dh)),
    }


def _gqa_scores(q, k):
    """q: [B,Sq,Hkv,G,D]  k: [B,Sk,Hkv,D] -> [B,Hkv,G,Sq,Sk] (fp32)."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32)


def _gqa_out(p, v):
    """p: [B,Hkv,G,Sq,Sk]  v: [B,Sk,Hkv,D] -> [B,Sq,Hkv,G,D]."""
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)


def attention_naive(q, k, v, *, causal: bool, q_offset=0):
    """Reference attention.  q: [B,Sq,Hq,D], k/v: [B,Sk,Hkv,D]."""
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, dh) * (dh ** -0.5)
    s = _gqa_scores(qg, k)
    if causal:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return _gqa_out(p, v).reshape(b, sq, hq, dh)


def attention_chunked(q, k, v, *, causal: bool, chunk: int, q_offset=0):
    """Flash-style memory-efficient attention: scan over query chunks.

    Never materialises more than [B,Hkv,G,chunk,Sk] scores at once.
    """
    b, sq, hq, dh = q.shape
    if sq % chunk != 0 or sq <= chunk:
        return attention_naive(q, k, v, causal=causal, q_offset=q_offset)
    hkv = k.shape[2]
    g = hq // hkv
    nq = sq // chunk
    qg = (q * (dh ** -0.5)).reshape(b, nq, chunk, hkv, g, dh)
    kpos = jnp.arange(k.shape[1])

    def body(_, xs):
        qc, idx = xs  # qc: [B,chunk,Hkv,G,D]
        s = _gqa_scores(qc, k)  # [B,Hkv,G,chunk,Sk]
        if causal:
            qpos = idx * chunk + jnp.arange(chunk) + q_offset
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return None, _gqa_out(p, v)  # [B,chunk,Hkv,G,D]

    _, out = jax.lax.scan(body, None, (jnp.moveaxis(qg, 1, 0), jnp.arange(nq)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq, hq, dh)
    return out


def attention_decode(q, k_cache, v_cache, cache_len):
    """Single-step decode.  q: [B,1,Hq,D]; caches: [B,Smax,Hkv,D]."""
    b, _, hq, dh = q.shape
    hkv = k_cache.shape[2]
    g = hq // hkv
    qg = q.reshape(b, 1, hkv, g, dh) * (dh ** -0.5)
    s = _gqa_scores(qg, k_cache)  # [B,Hkv,G,1,Smax]
    valid = jnp.arange(k_cache.shape[1]) < cache_len
    s = jnp.where(valid[None, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return _gqa_out(p, v_cache).reshape(b, 1, hq, dh)


def attn_forward(p, x, cfg: ModelConfig, positions, *, causal=True, kv_override=None):
    """Full-sequence attention block body.  x: [B,S,d]."""
    b, s, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cdt = x.dtype
    q = (x @ p["wq"].astype(cdt)).reshape(b, s, hq, dh)
    if kv_override is None:
        k = (x @ p["wk"].astype(cdt)).reshape(b, s, hkv, dh)
        v = (x @ p["wv"].astype(cdt)).reshape(b, s, hkv, dh)
        k = apply_rope(k, positions, cfg.rope_theta)
    else:  # cross attention: kv from encoder states
        enc = kv_override
        k = (enc @ p["wk"].astype(cdt)).reshape(b, enc.shape[1], hkv, dh)
        v = (enc @ p["wv"].astype(cdt)).reshape(b, enc.shape[1], hkv, dh)
    q = apply_rope(q, positions, cfg.rope_theta) if kv_override is None else q
    if cfg.attn_mode == "naive":
        o = attention_naive(q, k, v, causal=causal)
    else:
        o = attention_chunked(q, k, v, causal=causal, chunk=cfg.attn_chunk)
    return o.reshape(b, s, hq * dh) @ p["wo"].astype(cdt)


def attn_decode_forward(p, x, cfg: ModelConfig, cache_k, cache_v, cache_len):
    """One-token attention with KV cache update.

    x: [B,1,d].  Returns (out [B,1,d], new_k, new_v).
    """
    b = x.shape[0]
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cdt = x.dtype
    q = (x @ p["wq"].astype(cdt)).reshape(b, 1, hq, dh)
    k = (x @ p["wk"].astype(cdt)).reshape(b, 1, hkv, dh)
    v = (x @ p["wv"].astype(cdt)).reshape(b, 1, hkv, dh)
    pos = jnp.full((b, 1), cache_len, dtype=jnp.int32)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    new_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype), (0, cache_len, 0, 0))
    new_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype), (0, cache_len, 0, 0))
    o = attention_decode(q, new_k.astype(cdt), new_v.astype(cdt), cache_len + 1)
    return o.reshape(b, 1, hq * dh) @ p["wo"].astype(cdt), new_k, new_v


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, pdt, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "wg": dense_init(ks[0], (d, f), pdt),
        "wi": dense_init(ks[1], (d, f), pdt),
        "wo": dense_init(ks[2], (f, d), pdt),
    }


def mlp_forward(p, x):
    cdt = x.dtype
    h = jax.nn.silu(x @ p["wg"].astype(cdt)) * (x @ p["wi"].astype(cdt))
    return h @ p["wo"].astype(cdt)


def init_moe(key, cfg: ModelConfig, pdt):
    # Experts padded to a TP-friendly count (padded experts are masked out
    # of the router and never receive tokens).
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts_padded
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "wg": dense_init(ks[1], (e, d, f), pdt),
        "wi": dense_init(ks[2], (e, d, f), pdt),
        "wo": dense_init(ks[3], (e, f, d), pdt),
    }


def moe_forward(p, x, cfg: ModelConfig):
    """Top-k MoE FFN.  x: [B,S,d] -> [B,S,d].

    ``cfg.moe_mode``:
      * ``dense``    – every expert computes every token; combine with
                       (sparse) gate weights.  Correctness oracle; used by
                       smoke tests and as the *paper-faithful framework
                       baseline* in the dry-run.
      * ``dispatch`` – sort-based capacity dispatch (dropless up to
                       ``capacity_factor``): gather token rows per expert,
                       batched expert matmuls, scatter-add back.  The
                       hillclimbed production path.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts_padded, cfg.topk
    cdt = x.dtype
    logits = (x.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # [B,S,E]
    if e > cfg.n_experts:  # mask padded experts out of routing
        pad_mask = jnp.arange(e) >= cfg.n_experts
        logits = jnp.where(pad_mask, -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)  # [B,S,K]
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    if cfg.moe_mode == "dense":
        h = jnp.einsum("bsd,edf->bsef", x, p["wg"].astype(cdt))
        u = jnp.einsum("bsd,edf->bsef", x, p["wi"].astype(cdt))
        y = jnp.einsum("bsef,efd->bsed", jax.nn.silu(h) * u, p["wo"].astype(cdt))
        dense_w = jnp.sum(
            jax.nn.one_hot(topi, e, dtype=jnp.float32) * topw[..., None], axis=2
        )  # [B,S,E]
        return jnp.einsum("bsed,bse->bsd", y, dense_w.astype(cdt))

    # ---- dispatch mode: sort-based capacity dispatch over token groups ----
    # Under a mesh (production) and a full sequence, use the EXPLICIT
    # shard_map expert-parallel path: local bucketing, all-to-all to the
    # expert shards, local expert matmuls (weight grads stay local — each
    # shard owns its experts), all-to-all back.  Otherwise (single device /
    # decode) the pure-jit gather-based path below.
    from repro.distributed.sharding import current_rules, moe_constraint

    rules = current_rules()
    if rules is not None and s > 1:
        out = _moe_shardmap(p, x, topi, topw.astype(cdt), cfg, rules)
        if out is not None:
            return out

    g = cfg.moe_groups if s % cfg.moe_groups == 0 and s >= cfg.moe_groups else 1
    tg = s // g  # tokens per group
    xf = x.reshape(b * g, tg, d)
    ti = topi.reshape(b * g, tg, k)
    tw = topw.reshape(b * g, tg, k).astype(cdt)
    out = _moe_dispatch_batched(xf, ti, tw, p, cfg, groups_per_row=g,
                                constraint=moe_constraint)
    return out.reshape(b, s, d)


def _moe_shardmap(p, x, topi, topw, cfg: ModelConfig, rules):
    """Explicit EP: shard_map over (dp x model); returns None if shapes
    don't tile the mesh (caller falls back to the pure-jit path)."""
    import math as _math
    from functools import partial as _partial

    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e, k = cfg.n_experts_padded, cfg.topk
    mesh = rules.mesh
    maxis = rules.model_axis
    m = mesh.shape[maxis]
    dp = rules.dp
    dp_size = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        dp_size *= mesh.shape[a]
    if b % dp_size or s % m or e % m:
        return None
    t_loc = (b // dp_size) * (s // m)
    cap = max(4, int(_math.ceil(t_loc * k / e * cfg.capacity_factor)))

    def local_fn(xl, ti_l, tw_l, wg_l, wi_l, wo_l):
        # xl: [B_loc, S_loc, d]; ti/tw: [B_loc, S_loc, K]
        bl, sl, _ = xl.shape
        xf = xl.reshape(1, bl * sl, d)
        ti_f = ti_l.reshape(1, bl * sl, k)
        tw_f = tw_l.reshape(1, bl * sl, k)

        def expert_fn(xg):
            # xg: [1, E, cap, d] local buffer for ALL experts ->
            # a2a so each shard keeps its E_loc experts from all peers
            xg = xg.reshape(e, cap, d)
            recv = jax.lax.all_to_all(xg, maxis, split_axis=0, concat_axis=1,
                                      tiled=True)              # [E_loc, M*cap, d]
            h = jnp.einsum("ecd,edf->ecf", recv, wg_l.astype(xg.dtype))
            u = jnp.einsum("ecd,edf->ecf", recv, wi_l.astype(xg.dtype))
            yg = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u,
                            wo_l.astype(xg.dtype))             # [E_loc, M*cap, d]
            back = jax.lax.all_to_all(yg, maxis, split_axis=1, concat_axis=0,
                                      tiled=True)              # [E, cap, d]
            return back.reshape(1, e, cap, d)

        out = _moe_dispatch_batched(xf, ti_f, tw_f, p, cfg, groups_per_row=1,
                                    constraint=None, expert_fn=expert_fn,
                                    cap_override=cap)
        return out.reshape(bl, sl, d)

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dp, maxis, None), P(dp, maxis, None), P(dp, maxis, None),
                  P(maxis, None, None), P(maxis, None, None), P(maxis, None, None)),
        out_specs=P(dp, maxis, None),
        check_vma=False,
    )
    cdt = x.dtype
    return fn(x, topi, topw, p["wg"].astype(cdt), p["wi"].astype(cdt),
              p["wo"].astype(cdt))


def _moe_dispatch_batched(xf, ti, tw, p, cfg: ModelConfig, *, groups_per_row: int,
                          constraint=None, expert_fn=None, cap_override=None):
    """Batched capacity dispatch, SCATTER-FREE.  xf: [G,T,d]; ti/tw: [G,T,K].

    Both the token->expert-buffer build and the combine are expressed as
    batched GATHERS (take_along_axis with a leading group batch dim), which
    GSPMD partitions along G without the partial-result all-reduces that a
    generic scatter triggers (the scatter formulation cost a full
    [G, T, d] fp32 all-reduce per layer — see EXPERIMENTS.md §Perf).
    """
    gdim, t, d = xf.shape
    e, k = cfg.n_experts_padded, cfg.topk
    cdt = xf.dtype
    if cap_override is not None:
        cap = cap_override
    else:
        cap = int(math.ceil(t * k / e * cfg.capacity_factor))
        cap = max(4, min(cap, t))
    tk = t * k
    ar = jnp.arange(tk)
    flat_e = ti.reshape(gdim, tk)
    flat_row = jnp.tile(jnp.repeat(jnp.arange(t), k)[None], (gdim, 1))
    order = jnp.argsort(flat_e, axis=1, stable=True)            # [G, TK]
    e_sorted = jnp.take_along_axis(flat_e, order, axis=1)
    row_sorted = jnp.take_along_axis(flat_row, order, axis=1)
    # per-expert slot counts and exclusive starts (gather-only bookkeeping)
    counts = jnp.sum(flat_e[:, :, None] == jnp.arange(e)[None, None, :],
                     axis=1, dtype=jnp.int32)                    # [G, E]
    start = jnp.cumsum(counts, axis=1) - counts                  # [G, E]
    # expert buffer of token-row indices: position (e, c) holds the c-th
    # sorted slot of expert e (sentinel t = zero-pad row when overflowing)
    s_pos = start[:, :, None] + jnp.arange(cap)[None, None, :]   # [G, E, cap]
    valid = jnp.arange(cap)[None, None, :] < jnp.minimum(counts[:, :, None], cap)
    s_clip = jnp.clip(s_pos, 0, tk - 1).reshape(gdim, e * cap)
    buf_idx = jnp.where(valid.reshape(gdim, e * cap),
                        jnp.take_along_axis(row_sorted, s_clip, axis=1),
                        t).astype(jnp.int32)                     # [G, E*cap]
    x_pad = jnp.concatenate([xf, jnp.zeros((gdim, 1, d), cdt)], axis=1)
    xg = jnp.take_along_axis(x_pad, buf_idx[..., None], axis=1)  # [G, E*cap, d]
    xg = xg.reshape(gdim, e, cap, d)
    if expert_fn is not None:   # shard_map EP path supplies the expert block
        yg = expert_fn(xg)
    else:
        if constraint is not None:  # group->expert reshard (the EP a2a)
            xg = constraint(xg, "expert_in", groups_per_row)
        h = jnp.einsum("gecd,edf->gecf", xg, p["wg"].astype(cdt))
        u = jnp.einsum("gecd,edf->gecf", xg, p["wi"].astype(cdt))
        yg = jnp.einsum("gecf,efd->gecd", jax.nn.silu(h) * u, p["wo"].astype(cdt))
        if constraint is not None:  # expert-sharded -> back to group-sharded
            yg = constraint(yg, "expert_out", groups_per_row)
    # combine via the INVERSE mapping: for each original (token, slot), the
    # buffer position it landed in (or the zero sentinel if dropped)
    inv_perm = jnp.argsort(order, axis=1)                        # [G, TK]
    start_g = jnp.take_along_axis(start, e_sorted, axis=1)       # [G, TK]
    pos_in_e = ar[None] - start_g
    bp_sorted = jnp.where(pos_in_e < cap, e_sorted * cap + pos_in_e, e * cap)
    bp = jnp.take_along_axis(bp_sorted, inv_perm, axis=1)        # [G, TK]
    yg_pad = jnp.concatenate(
        [yg.reshape(gdim, e * cap, d), jnp.zeros((gdim, 1, d), cdt)], axis=1)
    contrib = jnp.take_along_axis(yg_pad, bp[..., None], axis=1)  # [G, TK, d]
    out = jnp.einsum("gtkd,gtk->gtd", contrib.reshape(gdim, t, k, d),
                     tw.astype(cdt))
    return out
