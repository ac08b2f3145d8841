"""Block library: norms, RoPE, Megatron-style sharded attention/MLP/embed/CE.

Everything here operates on LOCAL shards inside a fully-manual
``jax.shard_map`` and emits explicit collectives over ``ctx.tp_axis``
(no GSPMD): column-parallel projections need no comm; row-parallel
projections psum; vocab-sharded embedding/cross-entropy use masked
lookup + psum/pmax. With ``ctx.tp_axis=None`` all collectives are no-ops
(single-device smoke-test path).

Attention is blockwise (flash-style online softmax over KV chunks, chunk
body remat'd) so the 32k-prefill and 4k-train cells never materialize the
(S, S) score matrix. Decode attends one query against the KV cache.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig, HeadGeom, ShardCtx, head_geometry

Array = jax.Array

_NEG_INF = -1e30


def rmsnorm(x: Array, delta: Array, eps: float) -> Array:
    """RMSNorm with gain stored as a delta around 1 (zero-init friendly)."""
    xf = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return ((1.0 + delta.astype(jnp.float32)) * xf * rms).astype(x.dtype)


def rope(x: Array, pos: Array, theta: float) -> Array:
    """Rotary embedding. x: (B, S, H, hd), pos: (B, S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def rope_interleaved(x: Array, pos: Array, theta: float) -> Array:
    """Rotary embedding on interleaved pairs (2i, 2i+1), each turned by
    pos * theta^(-2i/hd) (``rope_interleave``). x: (B, S, H, hd)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def per_row(fn, *rows):
    """``fn`` over one batch row at a time, each given as a batch of one,
    its body remat'd: the activations of one sequence are live at once.
    Every output of ``fn`` carries that leading axis of one; the rows'
    outputs come back concatenated on it."""
    out = jax.lax.map(jax.checkpoint(lambda r: fn(*(a[None] for a in r))),
                      rows)
    return jax.tree_util.tree_map(
        lambda o: o.reshape((-1,) + o.shape[2:]), out)


def linear_row(x: Array, w: Array, ctx: ShardCtx) -> Array:
    """Row-parallel matmul: local contraction + psum over the model axis."""
    return ctx.psum_tp(x @ w.astype(x.dtype))


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _kv_slice(w: Array, geom: HeadGeom, hd: int, ctx: ShardCtx) -> Array:
    """Select this shard's KV-head columns from replicated KV storage."""
    if not geom.kv_replicated or ctx.tp_axis is None:
        return w
    kv_head = ctx.tp_rank() * geom.nkv // ctx.tp  # floor(s*kv/tp)
    return jax.lax.dynamic_slice_in_dim(w, kv_head * hd, hd, axis=1)


@functools.partial(jax.checkpoint, static_argnums=(4, 5))
def _attn_chunk(q, k_c, v_c, bias_c, scale, dtype):
    """One online-softmax step over a KV chunk (grouped GQA heads).

    q: (B,G,R,S,hd) — G kv groups x R q-heads-per-group; k_c/v_c: (B,G,Ck,hd).
    """
    s = jnp.einsum("bgrqd,bgkd->bgrqk", q, k_c).astype(jnp.float32) * scale
    s = s + bias_c  # (B,1,1,S,Ck) additive mask
    m = jnp.max(s, axis=-1)                       # (B,G,R,S)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bgrqk,bgkd->bgrqd", p.astype(dtype), v_c)
    return m, l, o


def blockwise_attention(q: Array, k: Array, v: Array, *, causal: bool,
                        q_pos: Array, kv_pos: Array, chunk: int = 1024) -> Array:
    """Flash-style attention. q, k: (B,S,Hq,hd), (B,T,Hkv,hd); v: (B,T,Hkv,dv)
    -> (B,S,Hq,dv)

    GQA is computed in grouped form — KV heads are never replicated in
    memory. Online softmax over KV chunks keeps live memory O(S*chunk); each
    chunk body is remat'd so backward recomputes scores instead of storing
    the (S, T) matrix.
    """
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    rep = Hq // Hkv
    scale = hd ** -0.5
    qt = q.transpose(0, 2, 1, 3).reshape(B, Hkv, rep, S, hd)
    kt = k.transpose(0, 2, 1, 3)                       # (B,Hkv,T,hd)
    vt = v.transpose(0, 2, 1, 3)

    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
    n_chunks = (T + pad) // chunk
    kt = kt.reshape(B, Hkv, n_chunks, chunk, hd).transpose(2, 0, 1, 3, 4)
    vt = vt.reshape(B, Hkv, n_chunks, chunk, -1).transpose(2, 0, 1, 3, 4)
    kv_pos_c = kv_pos.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        m_run, l_run, o_run = carry
        k_c, v_c, kp = xs
        valid = (kp >= 0)[:, None, None, None, :]      # (B,1,1,1,Ck)
        if causal:
            ok = q_pos[:, None, None, :, None] >= kp[:, None, None, None, :]
            bias = jnp.where(valid & ok, 0.0, _NEG_INF)
        else:
            bias = jnp.where(valid, 0.0, _NEG_INF)
        m_c, l_c, o_c = _attn_chunk(qt, k_c, v_c, bias, scale, q.dtype)
        m_new = jnp.maximum(m_run, m_c)
        a = jnp.exp(m_run - m_new)
        b = jnp.exp(m_c - m_new)
        l_new = a * l_run + b * l_c
        o_new = (o_run * a[..., None].astype(q.dtype)
                 + o_c * b[..., None].astype(q.dtype))
        return (m_new, l_new, o_new), None

    dv = v.shape[-1]
    m0 = jnp.full((B, Hkv, rep, S), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, rep, S), jnp.float32)
    o0 = jnp.zeros((B, Hkv, rep, S, dv), q.dtype)
    (m, l, o), _ = jax.lax.scan(body, (m0, l0, o0), (kt, vt, kv_pos_c))
    out = o / jnp.maximum(l, 1e-20)[..., None].astype(q.dtype)
    return out.reshape(B, Hq, S, dv).transpose(0, 2, 1, 3)


def causal_prefix_attention(q: Array, k: Array, v: Array, pos: Array, *,
                            block: int = 1024) -> Array:
    """Causal self-attention with the queries in blocks: block i attends
    to the keys of blocks 0..i only (``blockwise_attention`` over that
    prefix), so neither an (S, S) score matrix nor a (block, S) one over
    keys a causal mask would void is formed. q, k: (B, S, H, dqk);
    v: (B, S, H, dv) -> (B, S, H, dv)."""
    S = q.shape[1]

    # remat'd per block: its backward recomputes one block's scan at a
    # time and keeps only the shared q, k, v
    @functools.partial(jax.checkpoint, static_argnums=(4, 5))
    def one(q, k, v, pos, a, b):
        return blockwise_attention(
            q[:, a:b], k[:, :b], v[:, :b], causal=True, q_pos=pos[:, a:b],
            kv_pos=pos[:, :b], chunk=block)

    return jnp.concatenate([one(q, k, v, pos, a, min(a + block, S))
                            for a in range(0, S, block)], axis=1)


def decode_attention(q: Array, k_cache: Array, v_cache: Array,
                     kv_len: Array) -> Array:
    """One-token attention against a cache. q: (B,1,Hq,hd);
    caches: (B,T,Hkv,hd); kv_len: () — or (B,) per-row, for continuous
    batching where slots sit at different positions — current valid
    length (incl. new token). Positions >= kv_len are masked to a finite
    -inf whose softmax weight underflows to exactly 0, so cache contents
    past the valid length (pad K/V, reused paged blocks) cannot perturb
    the output bitwise.
    """
    B, S, Hq, hd = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    rep = Hq // Hkv
    qg = q.transpose(0, 2, 1, 3).reshape(B, Hkv, rep, S, hd)
    kg = k_cache.transpose(0, 2, 1, 3)                 # (B,Hkv,T,hd)
    vg = v_cache.transpose(0, 2, 1, 3)
    s = jnp.einsum("bgrqd,bgtd->bgrqt", qg, kg).astype(jnp.float32) * hd**-0.5
    lens = jnp.asarray(kv_len)
    if lens.ndim:                                      # per-row valid lengths
        lens = lens.reshape(B, 1, 1, 1, 1)
    mask = jnp.arange(T)[None, None, None, None, :] < lens
    s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bgrqt,bgtd->bgrqd", p, vg)
    return o.reshape(B, Hq, S, hd).transpose(0, 2, 1, 3)


def _cache_append(cache: dict, k: Array, v: Array,
                  kv_len: Array) -> tuple[Array, Array]:
    """Write this step's K/V at ``kv_len`` into the cache time axis.

    Scalar ``kv_len`` keeps the original whole-batch dynamic-update (the
    single-position demo path, byte-identical lowering); a (B,) vector
    writes each row at its own position (continuous batching), via a
    vmapped per-row dynamic update.
    """
    lens = jnp.asarray(kv_len)
    if lens.ndim:
        upd = jax.vmap(lambda c, u, i:
                       jax.lax.dynamic_update_slice_in_dim(c, u, i, 0))
        return (upd(cache["k"], k.astype(cache["k"].dtype), lens),
                upd(cache["v"], v.astype(cache["v"].dtype), lens))
    return (jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), kv_len, 1),
            jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), kv_len, 1))


def attention_block(p: dict, cfg: ArchConfig, ctx: ShardCtx, x: Array,
                    pos: Array, *, mode: str = "train",
                    cache: dict | None = None, kv_len: Array | None = None,
                    cross_kv: Array | None = None) -> tuple[Array, dict | None]:
    """Pre-norm (cross-)attention block. x: (B,S,d) local-batch activations.

    mode:
      'train'   — causal blockwise attention, no cache.
      'prefill' — causal blockwise attention over the S new tokens AND the
                  k/v are written into ``cache`` at [0:S] (len-0 start).
      'decode'  — S==1 token appended at ``kv_len``, attends to [0, kv_len].
    cache: {'k': (B,T,Hkv_loc,hd), 'v': ...}; kv_len: () int32 valid length
    BEFORE this call. cross_kv (vlm): (B, n_cross, d) precomputed patch
    embeddings (stub frontend); cross KV is static — never cached.
    """
    g = head_geometry(cfg, ctx.tp)
    hd = cfg.hd
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    B, S, _ = h.shape

    q = (h @ p["wq"].astype(h.dtype)).reshape(B, S, -1, hd)
    kv_src = h
    if cross_kv is not None:
        kv_src = rmsnorm(cross_kv, p["kv_norm"], cfg.norm_eps)
    wk = _kv_slice(p["wk"], g, hd, ctx).astype(h.dtype)
    wv = _kv_slice(p["wv"], g, hd, ctx).astype(h.dtype)
    k = (kv_src @ wk).reshape(B, kv_src.shape[1], -1, hd)
    v = (kv_src @ wv).reshape(B, kv_src.shape[1], -1, hd)

    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if cross_kv is None:  # RoPE only for self-attention
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)

    new_cache = None
    if cross_kv is not None:
        if mode == "decode":
            o = decode_attention(q, k.astype(h.dtype), v.astype(h.dtype),
                                 jnp.int32(k.shape[1]))
        else:
            kv_pos = jnp.zeros((B, k.shape[1]), jnp.int32)
            o = blockwise_attention(q, k, v, causal=False, q_pos=pos,
                                    kv_pos=kv_pos)
        new_cache = cache  # cross KV is static; pass cache through unchanged
    elif mode == "decode":
        k_cache, v_cache = _cache_append(cache, k, v, kv_len)
        o = decode_attention(q, k_cache.astype(h.dtype),
                             v_cache.astype(h.dtype), kv_len + S)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        o = blockwise_attention(q, k, v, causal=True, q_pos=pos, kv_pos=pos)
        if mode == "prefill":
            T = cache["k"].shape[1]
            kp = jnp.pad(k, ((0, 0), (0, T - S), (0, 0), (0, 0)))
            vp = jnp.pad(v, ((0, 0), (0, T - S), (0, 0), (0, 0)))
            new_cache = {"k": kp.astype(cache["k"].dtype),
                         "v": vp.astype(cache["v"].dtype)}

    y = linear_row(o.reshape(B, S, -1), p["wo"], ctx)
    return x + y.astype(x.dtype), new_cache


def _mla_attend(p: dict, cfg: ArchConfig, ctx: ShardCtx, h: Array,
                pos: Array) -> Array:
    """Latent attention of normed rows h: (B, S, d) -> the row-parallel
    output projection (before the residual)."""
    B, S, _ = h.shape
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q = (h @ p["wq"].astype(h.dtype)).reshape(B, S, -1, dn + dr)
    H = q.shape[2]
    kva = h @ p["wkva"].astype(h.dtype)                  # [c_kv, k_pe]
    c_kv = rmsnorm(kva[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    kvb = (c_kv @ p["wkvb"].astype(h.dtype)).reshape(B, S, H, dn + dv)
    q_pe = rope_interleaved(q[..., dn:], pos, cfg.rope_theta)
    k_pe = rope_interleaved(kva[..., None, cfg.kv_lora_rank:], pos,
                            cfg.rope_theta)
    qf = jnp.concatenate([q[..., :dn], q_pe], -1)
    kf = jnp.concatenate([kvb[..., :dn],
                          jnp.broadcast_to(k_pe, (B, S, H, dr))], -1)
    o = causal_prefix_attention(qf, kf, kvb[..., dn:], pos)
    return linear_row(o.reshape(B, S, H * dv), p["wo"], ctx)


def mla_block(p: dict, cfg: ArchConfig, ctx: ShardCtx, x: Array, pos: Array,
              *, mode: str = "train") -> Array:
    """Pre-norm latent attention block (DeepSeek-V2 MLA, no query
    compression): q = W_q h split into [q_nope, q_pe]; [c_kv, k_pe] =
    W_kva h; [k_nope, v] = W_kvb RMSNorm(c_kv); rotary on q_pe and on the
    one k_pe every head shares, on interleaved pairs (DeepSeek-V3's
    ``rope_interleave``); causal softmax of [q_nope, q_pe] .
    [k_nope, k_pe] over sqrt(qk_nope + qk_rope); o = W_o. One sequence at
    a time (``per_row``); training only (no latent cache)."""
    if mode != "train":
        raise NotImplementedError(
            f"{cfg.name}: latent attention has no serving cache")
    with jax.named_scope("mla"):
        h = rmsnorm(x, p["norm"], cfg.norm_eps)
        y = per_row(lambda hr, pr: _mla_attend(p, cfg, ctx, hr, pr), h, pos)
    return x + y.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP / embedding / loss
# ---------------------------------------------------------------------------


def parallel_attn_mlp_block(p: dict, cfg: ArchConfig, ctx: ShardCtx,
                            x: Array, pos: Array, *, mode: str = "train",
                            cache: dict | None = None,
                            kv_len: Array | None = None
                            ) -> tuple[Array, dict | None]:
    """PaLM-style parallel block: attention and MLP branch from ONE norm
    and their outputs merge in ONE row-parallel psum — halving the
    per-layer TP collective count (the dominant roofline term for small-d
    archs at TP=16; beyond-paper opt-in via ``ArchConfig.parallel_block``).
    """
    g = head_geometry(cfg, ctx.tp)
    hd = cfg.hd
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    B, S, _ = h.shape

    q = (h @ p["wq"].astype(h.dtype)).reshape(B, S, -1, hd)
    wk = _kv_slice(p["wk"], g, hd, ctx).astype(h.dtype)
    wv = _kv_slice(p["wv"], g, hd, ctx).astype(h.dtype)
    k = (h @ wk).reshape(B, S, -1, hd)
    v = (h @ wv).reshape(B, S, -1, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)

    new_cache = None
    if mode == "decode":
        k_cache, v_cache = _cache_append(cache, k, v, kv_len)
        o = decode_attention(q, k_cache.astype(h.dtype),
                             v_cache.astype(h.dtype), kv_len + S)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        o = blockwise_attention(q, k, v, causal=True, q_pos=pos, kv_pos=pos)
        if mode == "prefill":
            T = cache["k"].shape[1]
            kp = jnp.pad(k, ((0, 0), (0, T - S), (0, 0), (0, 0)))
            vp = jnp.pad(v, ((0, 0), (0, T - S), (0, 0), (0, 0)))
            new_cache = {"k": kp.astype(cache["k"].dtype),
                         "v": vp.astype(cache["v"].dtype)}

    mp = p["mlp"]
    hm = rmsnorm(x, mp["norm"], cfg.norm_eps)
    act = jax.nn.silu(hm @ mp["wg"].astype(h.dtype)) \
        * (hm @ mp["wu"].astype(h.dtype))
    y_local = (o.reshape(B, S, -1) @ p["wo"].astype(h.dtype)
               + act @ mp["wo"].astype(h.dtype))
    y = ctx.psum_tp(y_local)                      # the ONE collective
    return x + y.astype(x.dtype), new_cache


def mlp_block(p: dict, cfg: ArchConfig, ctx: ShardCtx, x: Array) -> Array:
    """Pre-norm SwiGLU MLP, column->row parallel."""
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    gate = h @ p["wg"].astype(h.dtype)
    up = h @ p["wu"].astype(h.dtype)
    y = linear_row(jax.nn.silu(gate) * up, p["wo"], ctx)
    return x + y.astype(x.dtype)


def embed_lookup(table: Array, ids: Array, ctx: ShardCtx) -> Array:
    """Vocab-sharded embedding lookup: masked local take + psum."""
    v_loc = table.shape[0]
    start = ctx.tp_rank() * v_loc
    local = ids - start
    ok = (local >= 0) & (local < v_loc)
    emb = jnp.take(table, jnp.clip(local, 0, v_loc - 1), axis=0)
    emb = jnp.where(ok[..., None], emb, 0.0)
    return ctx.psum_tp(emb).astype(ctx.dtype)


def lm_loss(hidden: Array, head_w: Array, labels: Array, cfg: ArchConfig,
            ctx: ShardCtx, *, chunk: int = 1024) -> Array:
    """Mean next-token cross-entropy with vocab-sharded logits.

    hidden: (B,S,d); head_w: (d, V_loc); labels: (B,S) with -1 = ignore.
    Sequence is processed in remat'd chunks so (B,S,V_loc) logits never
    materialize for the whole sequence at once.
    """
    B, S, _ = hidden.shape
    v_loc = head_w.shape[1]
    start = ctx.tp_rank() * v_loc
    # global column ids >= real vocab are padding -> masked out of the CE
    col_valid = (jnp.arange(v_loc) + start) < cfg.vocab_size

    chunk = min(chunk, S)
    pad = (-S) % chunk
    hid = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0))) if pad else hidden
    lab = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1) if pad else labels
    n = (S + pad) // chunk
    hid = hid.reshape(B, n, chunk, -1).transpose(1, 0, 2, 3)
    lab = lab.reshape(B, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_loss(h_c, l_c):
        logits = (h_c @ head_w.astype(h_c.dtype)).astype(jnp.float32)
        logits = jnp.where(col_valid, logits, _NEG_INF)
        # logsumexp is shift-invariant: the max is stability-only, so the
        # pmax (which has no differentiation rule) sees a zero-tangent input.
        m = ctx.pmax_tp(jax.lax.stop_gradient(jnp.max(logits, -1)))
        z = ctx.psum_tp(jnp.sum(jnp.exp(logits - m[..., None]), -1))
        loc = l_c - start
        ok = (loc >= 0) & (loc < v_loc)
        picked = jnp.take_along_axis(
            logits, jnp.clip(loc, 0, v_loc - 1)[..., None], -1)[..., 0]
        label_logit = ctx.psum_tp(jnp.where(ok, picked, 0.0))
        nll = jnp.log(z) + m - label_logit
        w = (l_c >= 0).astype(jnp.float32)
        return jnp.sum(nll * w), jnp.sum(w)

    def body(carry, xs):
        tot, cnt = carry
        s, c = chunk_loss(*xs)
        return (tot + s, cnt + c), None

    (tot, cnt), _ = jax.lax.scan(body, (0.0, 0.0), (hid, lab))
    return tot / jnp.maximum(cnt, 1.0)


def lm_logits(hidden: Array, head_w: Array, cfg: ArchConfig,
              ctx: ShardCtx) -> Array:
    """Full logits for decode: (B,S,d) -> (B,S,V_local) (model-sharded)."""
    logits = (hidden @ head_w.astype(hidden.dtype)).astype(jnp.float32)
    v_loc = head_w.shape[1]
    start = ctx.tp_rank() * v_loc
    col_valid = (jnp.arange(v_loc) + start) < cfg.vocab_size
    return jnp.where(col_valid, logits, _NEG_INF)


def sharded_argmax(logits: Array, ctx: ShardCtx) -> Array:
    """Greedy token over vocab-sharded logits: (..., V_local) -> (...) int32.

    Local argmax, then a pmax over the model axis picks the global winner;
    ties broken toward the lowest global vocab id (pmin over candidates).
    """
    v_loc = logits.shape[-1]
    start = ctx.tp_rank() * v_loc
    loc_max = jnp.max(logits, axis=-1)
    loc_idx = jnp.argmax(logits, axis=-1).astype(jnp.int32) + start
    gmax = ctx.pmax_tp(loc_max)
    cand = jnp.where(loc_max >= gmax, loc_idx, jnp.int32(2**30))
    return -ctx.pmax_tp(-cand)
