"""Unified model: scan-over-cycles forward for all ten architectures.

One entry point per lowering target:

    loss_fn    — training forward -> scalar loss (train_4k)
    prefill_fn — build the KV/SSM caches from a prompt, return last logits
                 (prefill_32k)
    decode_fn  — one new token against a cache (decode_32k / long_500k)

All three run on LOCAL shards inside a fully-manual ``jax.shard_map`` (or on
one device with ``ctx.tp_axis=None``). Parameters arrive as the flat layout
of ``flatten.FlatSpec``; ``gather`` (FSDP) is a caller-supplied callable that
all-gathers a flat segment over the data axis — identity when params are
replicated. The per-cycle gather sits *inside* the scan body so the full
bf16 weights of only one cycle are ever live (ZeRO-3 style), and its autodiff
transpose (psum_scatter) delivers gradients pre-sharded in storage layout.

The cycle body dispatches on ``cfg.cycle`` — e.g. ``('attn',)*4 + ('cross',)``
for llama-vision, ``('mamba',)*6 + ('shared_attn',)`` for zamba2 — and is
remat'd per cycle during training.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import mamba as mb
from repro.models import moe as moe_lib
from repro.models import rwkv as rk
from repro.models.common import ArchConfig, ShardCtx, head_geometry
from repro.models.flatten import FlatSpec
from repro.models.layers import (attention_block, embed_lookup, lm_logits,
                                 lm_loss, mla_block, mlp_block,
                                 parallel_attn_mlp_block, rmsnorm,
                                 sharded_argmax)

Array = jax.Array
Gathers = tuple[Callable[[Array], Array], Callable[[Array], Array]] | None

MOE_AUX_COEF = 0.01


def _kind_counts(cfg: ArchConfig) -> dict[str, int]:
    counts: dict[str, int] = {}
    for kind in cfg.cycle:
        if kind != "shared_attn":
            counts[kind] = counts.get(kind, 0) + 1
    return counts


def _servable(cfg: ArchConfig) -> None:
    """Serving has no latent cache and no selection-bias state."""
    if cfg.mla or cfg.router == "sigmoid":
        raise NotImplementedError(
            f"{cfg.name}: serving latent attention (MLA) or a sigmoid "
            f"router with its selection bias is not supported; the model "
            f"is trained only")


def _self_attention(p: dict, cfg: ArchConfig, ctx: ShardCtx, x: Array,
                    pos: Array, mode: str, cache: dict | None,
                    kv_len: Array | None) -> tuple[Array, dict | None]:
    if cfg.mla:
        return mla_block(p, cfg, ctx, x, pos, mode=mode), None
    return attention_block(p, cfg, ctx, x, pos, mode=mode, cache=cache,
                           kv_len=kv_len)


def _apply_lead(cfg: ArchConfig, ctx: ShardCtx, top: dict, x: Array,
                pos: Array, mode: str, remat: bool = False) -> Array:
    """The leading dense layers (``first_dense``), before the cycles, each
    remat'd like a cycle where ``remat``."""
    def layer(p, x):
        x, _ = _self_attention(p, cfg, ctx, x, pos, mode, None, None)
        return mlp_block(p["mlp"], cfg, ctx, x)

    if remat:
        layer = jax.checkpoint(layer)
    for i in range(cfg.first_dense):
        x = layer(jax.tree_util.tree_map(lambda a: a[i], top["lead"]), x)
    return x


def _apply_cycle(cfg: ArchConfig, ctx: ShardCtx, cyc_p: dict,
                 shared_p: dict | None, x: Array, pos: Array, mode: str,
                 cross_kv: Array | None, cache: dict | None,
                 kv_len: Array | None, bias: Array | None = None
                 ) -> tuple[Array, Array, Array | None, dict | None]:
    """Apply one cycle of blocks. Returns (x, aux_loss, load, new_cache):
    load, of the cycle's MoE block, is the tokens routed to each expert
    (None without one); ``bias`` is that router's selection bias."""
    aux = jnp.float32(0.0)
    load = None
    new_cache: dict[str, Any] = {}
    occ: dict[str, int] = {}

    def sub(kind: str, j: int):
        if cache is None:
            return None
        return jax.tree_util.tree_map(lambda a: a[j], cache[kind])

    def put(kind: str, j: int, c):
        if cache is None or c is None:
            return
        cur = new_cache.get(kind)
        if cur is None:
            cur = jax.tree_util.tree_map(
                lambda a: jnp.zeros_like(a), cache[kind])
        new_cache[kind] = jax.tree_util.tree_map(
            lambda buf, leaf: buf.at[j].set(leaf.astype(buf.dtype)), cur, c)

    for kind in cfg.cycle:
        j = occ.get(kind, 0)
        occ[kind] = j + 1
        if kind == "shared_attn":
            x, c = attention_block(shared_p, cfg, ctx, x, pos, mode=mode,
                                   cache=sub(kind, j), kv_len=kv_len)
            x = mlp_block(shared_p["mlp"], cfg, ctx, x)
            put(kind, j, c)
            continue
        p = jax.tree_util.tree_map(lambda a: a[j], cyc_p[kind])
        if kind == "attn":
            if cfg.parallel_block:
                x, c = parallel_attn_mlp_block(
                    p, cfg, ctx, x, pos, mode=mode, cache=sub(kind, j),
                    kv_len=kv_len)
            else:
                x, c = attention_block(p, cfg, ctx, x, pos, mode=mode,
                                       cache=sub(kind, j), kv_len=kv_len)
                x = mlp_block(p["mlp"], cfg, ctx, x)
            put(kind, j, c)
        elif kind == "cross":
            x, _ = attention_block(p, cfg, ctx, x, pos, mode=mode,
                                   cross_kv=cross_kv)
            x = mlp_block(p["mlp"], cfg, ctx, x)
        elif kind == "moe":
            x, c = _self_attention(p, cfg, ctx, x, pos, mode, sub(kind, j),
                                   kv_len)
            x, a, load = moe_lib.moe_block(p["moe"], cfg, ctx, x, bias)
            aux = aux + a
            put(kind, j, c)
        elif kind == "rwkv":
            st = sub(kind, j)
            x, c = rk.rwkv_block(p, cfg, ctx, x, state=st)
            put(kind, j, c)
        elif kind == "mamba":
            st = sub(kind, j)
            x, c = mb.mamba_block(p, cfg, ctx, x, state=st)
            put(kind, j, c)
        else:  # pragma: no cover
            raise ValueError(f"unknown block kind {kind!r}")
    return x, aux, load, (new_cache if cache is not None else None)


def _cycle_scan_body(cfg: ArchConfig, ctx: ShardCtx, fs: FlatSpec,
                     shared_p: dict | None, pos: Array, mode: str,
                     cross_kv: Array | None, kv_len: Array | None,
                     gs_, gr_):
    """Scan body over (vs, vr, cyc_cache, bias) — the single source of
    the per-cycle step, shared by ``_backbone`` and the chunked training
    path so the two cannot drift. Its outputs are (new cache, MoE load)."""
    def body(carry, xs):
        x, aux = carry
        vs, vr, cyc_cache, bias = xs
        cyc_p = fs.cycle_params(gs_(vs), gr_(vr), ctx.dtype)
        x, a, load, new_c = _apply_cycle(cfg, ctx, cyc_p, shared_p, x, pos,
                                         mode, cross_kv, cyc_cache, kv_len,
                                         bias)
        return (x, aux + a), (new_c, load)

    return body


def _scan_cycles(cyc, carry, xs, remat: bool):
    """Scan ``cyc`` over cycle rows with the sqrt-n nested-remat structure.

    A flat scan's backward stores the carry at every cycle (n * B*S*d —
    tens of GB at 94 layers); a two-level scan with a remat'd outer body
    stores ~(n1 + n2) carries instead. Shared by the monolithic training
    scan and each chunk of ``chunked_loss_vjp`` (applied within the chunk's
    cycle range, so the chunk VJP's residual footprint stays sublinear).
    The scan nests only where that stores fewer carries than the n of a
    flat scan: at four and five cycles both store n, and nesting would
    only recompute every cycle once more.
    ``xs`` holds one row per cycle; returns (carry, per-cycle outputs).
    """
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    n2 = max(1, int(math.isqrt(n)))
    n1, rem = n // n2, n % n2
    if not (remat and n1 + n2 + rem < n):
        return jax.lax.scan(cyc, carry, xs)
    main = jax.tree_util.tree_map(
        lambda a: a[:n1 * n2].reshape((n1, n2) + a.shape[1:]), xs)
    carry, ys = jax.lax.scan(
        jax.checkpoint(lambda c, v: jax.lax.scan(cyc, c, v)), carry, main)
    ys = jax.tree_util.tree_map(
        lambda a: a.reshape((n1 * n2,) + a.shape[2:]), ys)
    if rem:
        tail = jax.tree_util.tree_map(lambda a: a[n1 * n2:], xs)
        carry, yt = jax.lax.scan(cyc, carry, tail)
        ys = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b]), ys, yt)
    return carry, ys


def _backbone(cfg: ArchConfig, ctx: ShardCtx, fs: FlatSpec, segs: dict,
              tokens: Array, pos: Array, mode: str,
              cross_kv: Array | None = None, cache: Any = None,
              kv_len: Array | None = None, gathers: Gathers = None,
              remat: bool = False, router_bias: Array | None = None
              ) -> tuple[Array, Array, Any, dict, Array | None]:
    """Embed -> leading dense layers -> scan cycles -> final norm. Returns
    (hidden, aux, cache, top, load): load (n_cycles, E) is the tokens each
    cycle's router sent to each expert (None without MoE cycles);
    ``router_bias`` (n_cycles, E) the routers' selection bias.

    segs: flat-segment dict (see flatten.py). gathers = (gather_sharded,
    gather_replicated) — identity when storage is unsharded (tp=1 smoke /
    'dp' sharded leaves), all-gather closures for 'model'/'data' otherwise.
    """
    gs_, gr_ = gathers or (lambda v: v, lambda v: v)
    top = fs.top_params(gs_(segs["top_s"]), gr_(segs["top_r"]), ctx.dtype)

    x = embed_lookup(top["embed"], tokens, ctx)
    x = _apply_lead(cfg, ctx, top, x, pos, mode, remat)
    shared_p = top.get("shared_attn")

    body = _cycle_scan_body(cfg, ctx, fs, shared_p, pos, mode, cross_kv,
                            kv_len, gs_, gr_)
    if remat:
        body = jax.checkpoint(body)
    cs, cr = segs["cycles_s"], segs["cycles_r"]
    if cache is not None:
        # Serve path: the cache rides the scan CARRY and each cycle's slice
        # is updated in place (dynamic_update_index lowers to an aliased
        # DUS inside the while loop) — scanning it as xs/ys would allocate
        # a second and third cache-sized buffer (measured in the dry-run).
        def serve_body(carry, xs):
            x, aux, cache_full, i = carry
            cyc_cache = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0,
                                                       keepdims=False),
                cache_full)
            (x, aux), (new_c, _) = body((x, aux),
                                        (xs[0], xs[1], cyc_cache, None))
            cache_full = jax.tree_util.tree_map(
                lambda full, nc: jax.lax.dynamic_update_index_in_dim(
                    full, nc.astype(full.dtype), i, 0),
                cache_full, new_c)
            return (x, aux, cache_full, i + 1), None

        (x, aux, new_cache, _), _ = jax.lax.scan(
            serve_body, (x, jnp.float32(0.0), cache, jnp.int32(0)),
            (cs, cr))
        x = rmsnorm(x, top["final_norm"], cfg.norm_eps)
        return x, aux, new_cache, top, None

    def cyc(c, v):
        c, (_, load) = body(c, (v[0], v[1], None, v[2]))
        return c, load

    (x, aux), load = _scan_cycles(cyc, (x, jnp.float32(0.0)),
                                  (cs, cr, router_bias), remat)
    x = rmsnorm(x, top["final_norm"], cfg.norm_eps)
    return x, aux, None, top, load


def _head_w(cfg: ArchConfig, top: dict) -> Array:
    return top["embed"].T if cfg.tie_embeddings else top["head"]


def _loss_head(cfg: ArchConfig, ctx: ShardCtx, hid: Array, aux: Array,
               top: dict, labels: Array) -> Array:
    """Final-norm'd hidden -> CE loss (+ MoE aux): the shared tail of
    ``loss_fn`` and the chunked epilogue."""
    loss = lm_loss(hid, _head_w(cfg, top), labels, cfg, ctx)
    if cfg.n_experts:
        loss = loss + MOE_AUX_COEF * aux / max(1, cfg.n_cycles)
    return loss


# ---------------------------------------------------------------------------
# Lowering targets
# ---------------------------------------------------------------------------


def loss_fn(cfg: ArchConfig, ctx: ShardCtx, fs: FlatSpec, segs: dict,
            batch: dict, *, gathers: Gathers = None, remat: bool = True,
            router_bias: Array | None = None, with_load: bool = False):
    """Mean next-token CE (+ MoE aux). batch: tokens/labels (B,S) [cross_kv].
    ``with_load``: (loss, load), load as ``_backbone`` gives it."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    hid, aux, _, top, load = _backbone(
        cfg, ctx, fs, segs, tokens, pos, "train",
        cross_kv=batch.get("cross_kv"), gathers=gathers, remat=remat,
        router_bias=router_bias)
    loss = _loss_head(cfg, ctx, hid, aux, top, batch["labels"])
    return (loss, load) if with_load else loss


def chunked_loss_vjp(cfg: ArchConfig, ctx: ShardCtx, fs: FlatSpec,
                     segs: dict, batch: dict, *, chunks: int,
                     gathers: Gathers = None, remat: bool = True,
                     grad_seed: float = 1.0,
                     router_bias: Array | None = None):
    """Training forward with the cycle scan split into K autodiff chunks.

    The monolithic ``loss_fn`` hands autodiff one opaque scan, so the full
    backward must finish before any gradient coordinate exists. Here the
    scan is cut at K chunk boundaries that are *visible* to autodiff
    (``jax.vjp`` per chunk), so each chunk's VJP yields its cycle-gradient
    slice as it completes — in reverse-chunk order, the order backward
    physically produces them. The caller (the readiness scheduler in
    ``core/gs_sgd.exchange_interleaved``) can then start a bucket's
    encode/all-reduce while the remaining chunks' backward is still
    pending; within each chunk the sqrt-n ``_scan_cycles`` remat structure
    is preserved.

    Returns ``(loss, bwd_steps, top_grads, load)``:

      loss       — scalar, identical to ``loss_fn`` (before grad_seed).
      bwd_steps  — K thunks to invoke STRICTLY in order. Step j runs the
                   VJP of chunk K-1-j and returns ``((a, b), d_cs, d_cr)``:
                   the chunk's cycle-row range and its cycles_s / cycles_r
                   gradient slices. Step 0 also runs the loss/head
                   epilogue's VJP; the last step also runs the embed
                   prologue's VJP.
      top_grads  — thunk, valid only after every bwd_step ran: the
                   accumulated ``(d_top_s, d_top_r)`` (embed + head +
                   shared leaves receive contributions from every chunk,
                   so they finalize last — the final emission event).
      load       — the MoE cycles' routed tokens per expert, as
                   ``loss_fn(..., with_load=True)`` gives it (or None).

    grad_seed scales the loss cotangent (the caller's 1/tp seeding).
    Gradients equal ``jax.grad(grad_seed * loss_fn)`` exactly: the chunk
    composition is the same chain rule, and per-leaf cotangent sums are
    plain commutative adds of the same terms.
    """
    from repro.models.flatten import chunk_plan

    tokens = batch["tokens"]
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cross_kv = batch.get("cross_kv")
    gs_, gr_ = gathers or (lambda v: v, lambda v: v)
    ts, tr = segs["top_s"], segs["top_r"]
    cs, cr = segs["cycles_s"], segs["cycles_r"]
    bounds = chunk_plan(fs.n_cycles, chunks)
    K = len(bounds)

    # The top segments are gathered ONCE (like _backbone) through their own
    # vjp stage; the per-stage cotangents accumulate on the GATHERED arrays
    # and the gather transpose (psum_scatter under tp/fsdp sharding) runs a
    # single time in top_grads — a K-chunk step must not multiply the
    # top-segment collectives by K+2.
    # Every stage differentiated below runs under the device scope
    # ``forward``; its VJP's ops then carry ``transpose(jvp(forward))``.
    def gather(a, b):
        with jax.named_scope("forward"):
            return gs_(a), gr_(b)

    (g_ts, g_tr), vjp_gather = jax.vjp(gather, ts, tr)

    def prologue(ts, tr):
        with jax.named_scope("forward"):
            top = fs.top_params(ts, tr, ctx.dtype)
            x = embed_lookup(top["embed"], tokens, ctx)
            return (_apply_lead(cfg, ctx, top, x, pos, "train", remat),
                    jnp.float32(0.0))

    def chunk_fn(carry, vs, vr, ts, tr, bias):
        with jax.named_scope("forward"):
            top = fs.top_params(ts, tr, ctx.dtype)
            body = _cycle_scan_body(cfg, ctx, fs, top.get("shared_attn"),
                                    pos, "train", cross_kv, None, gs_, gr_)
            if remat:
                body = jax.checkpoint(body)

            def cyc(c, v):
                c, (_, load) = body(c, (v[0], v[1], None, v[2]))
                return c, load

            return _scan_cycles(cyc, carry, (vs, vr, bias), remat)

    def epilogue(carry, ts, tr):
        with jax.named_scope("forward"):
            x, aux = carry
            top = fs.top_params(ts, tr, ctx.dtype)
            x = rmsnorm(x, top["final_norm"], cfg.norm_eps)
            return _loss_head(cfg, ctx, x, aux, top, batch["labels"])

    carry, vjp_pro = jax.vjp(prologue, g_ts, g_tr)
    chunk_vjps, loads = [], []
    for a, b in bounds:
        bias = None if router_bias is None else router_bias[a:b]
        carry, vjp_c, load = jax.vjp(
            lambda c, vs, vr, ts, tr: chunk_fn(c, vs, vr, ts, tr, bias),
            carry, cs[a:b], cr[a:b], g_ts, g_tr, has_aux=True)
        chunk_vjps.append(vjp_c)
        loads.append(load)
    loss, vjp_epi = jax.vjp(epilogue, carry, g_ts, g_tr)
    load = None if loads[0] is None else jnp.concatenate(loads)

    st: dict = {}

    def make_step(j: int):
        c = K - 1 - j
        a, b = bounds[c]

        def run():
            if j == 0:
                seed = jnp.asarray(grad_seed, loss.dtype)
                st["d_carry"], st["d_ts"], st["d_tr"] = vjp_epi(seed)
            d_carry, d_cs, d_cr, d_ts, d_tr = chunk_vjps[c](st["d_carry"])
            st["d_carry"] = d_carry
            st["d_ts"] = st["d_ts"] + d_ts
            st["d_tr"] = st["d_tr"] + d_tr
            if c == 0:  # embed transpose — the top segments' last piece
                d_ts, d_tr = vjp_pro(st["d_carry"])
                st["d_ts"] = st["d_ts"] + d_ts
                st["d_tr"] = st["d_tr"] + d_tr
            return (a, b), d_cs, d_cr

        return run

    def top_grads():
        return vjp_gather((st["d_ts"], st["d_tr"]))

    return loss, [make_step(j) for j in range(K)], top_grads, load


def prefill_fn(cfg: ArchConfig, ctx: ShardCtx, fs: FlatSpec, segs: dict,
               batch: dict, cache: Any, *,
               gathers: Gathers = None) -> tuple[Array, Any]:
    """Prompt forward; fills ``cache`` from position 0. Returns (last-token
    logits (B, V_local), new cache)."""
    _servable(cfg)
    tokens = batch["tokens"]
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    hid, _, cache, top, _ = _backbone(
        cfg, ctx, fs, segs, tokens, pos, "prefill",
        cross_kv=batch.get("cross_kv"), cache=cache, kv_len=jnp.int32(0),
        gathers=gathers)
    logits = lm_logits(hid[:, -1:, :], _head_w(cfg, top), cfg, ctx)
    return logits[:, 0, :], cache


def decode_fn(cfg: ArchConfig, ctx: ShardCtx, fs: FlatSpec, segs: dict,
              tokens: Array, kv_len: Array, cache: Any, *,
              cross_kv: Array | None = None,
              gathers: Gathers = None) -> tuple[Array, Any]:
    """One decode step: tokens (B, 1) at position ``kv_len`` -> (next-token
    ids (B,), updated cache).

    ``kv_len`` is the valid cache length BEFORE this token: a () scalar
    (whole batch at one position — the original demo path) or a (B,)
    vector (each row at its own position — continuous batching).
    """
    _servable(cfg)
    B, S = tokens.shape
    kl = jnp.asarray(kv_len).astype(jnp.int32)
    pos = jnp.broadcast_to(kl[:, None] if kl.ndim else kl, (B, S))
    hid, _, cache, top, _ = _backbone(
        cfg, ctx, fs, segs, tokens, pos, "decode", cross_kv=cross_kv,
        cache=cache, kv_len=kv_len, gathers=gathers)
    logits = lm_logits(hid, _head_w(cfg, top), cfg, ctx)
    return sharded_argmax(logits[:, 0, :], ctx), cache


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, ctx: ShardCtx, b_loc: int, t_cache: int,
               dtype=jnp.bfloat16) -> Any:
    """Concrete zeroed cache pytree, stacked (n_cycles, cnt, ...) leaves.

    Attention/moe kinds get KV caches; rwkv/mamba get recurrent states;
    cross blocks need none (static image KV).
    """
    _servable(cfg)
    n = cfg.n_cycles
    g = head_geometry(cfg, ctx.tp)
    nkv_store = 1 if g.kv_replicated else g.nkv_loc
    cache: dict[str, Any] = {}

    def kv(cnt):
        shape = (n, cnt, b_loc, t_cache, nkv_store, cfg.hd)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    for kind, cnt in _kind_counts(cfg).items():
        if kind in ("attn", "moe"):
            cache[kind] = kv(cnt)
        elif kind == "rwkv":
            st = rk.init_rwkv_state(cfg, ctx, b_loc)
            cache[kind] = jax.tree_util.tree_map(
                lambda a: jnp.zeros((n, cnt) + a.shape, a.dtype), st)
        elif kind == "mamba":
            st = mb.init_mamba_state(cfg, ctx, b_loc, dtype)
            cache[kind] = jax.tree_util.tree_map(
                lambda a: jnp.zeros((n, cnt) + a.shape, a.dtype), st)
        # 'cross': no cache
    if "shared_attn" in cfg.cycle:
        shape = (n, 1, b_loc, t_cache, nkv_store, cfg.hd)
        cache["shared_attn"] = {"k": jnp.zeros(shape, dtype),
                                "v": jnp.zeros(shape, dtype)}
    return cache


def cache_shapes(cfg: ArchConfig, ctx: ShardCtx, b_loc: int, t_cache: int,
                 dtype=jnp.bfloat16) -> Any:
    """ShapeDtypeStruct pytree of the cache (dry-run stand-in, no alloc)."""
    return jax.eval_shape(
        functools.partial(init_cache, cfg, ctx, b_loc, t_cache, dtype))


# Cache kinds whose leaves carry a time axis (axis 3 of the stacked
# (n, cnt, B, T, nkv, hd) layout) and are therefore pageable; rwkv/mamba
# kinds hold fixed-size recurrent state with batch axis 2 and no time axis.
KV_CACHE_KINDS = ("attn", "moe", "shared_attn")


def split_cache(cache: dict) -> tuple[dict, dict]:
    """Split a cache pytree into (kv_kinds, state_kinds) sub-dicts.

    The serve layer pages only the KV kinds; state kinds stay dense
    per-slot. Both returned dicts share leaves with the input (no copy).
    """
    kv = {k: v for k, v in cache.items() if k in KV_CACHE_KINDS}
    state = {k: v for k, v in cache.items() if k not in KV_CACHE_KINDS}
    return kv, state


def merge_cache(kv: dict, state: dict) -> dict:
    """Inverse of :func:`split_cache`."""
    out = dict(kv)
    out.update(state)
    return out
