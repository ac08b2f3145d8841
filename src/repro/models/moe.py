"""Mixture-of-Experts block with expert parallelism over the ``model`` axis.

Because activations are replicated across the model axis between blocks
(Megatron TP semantics — see ``layers.py``), expert parallelism needs no
all-to-all: every rank sees every token, routes it over every expert, and
computes only the part of the result its own experts give; the combine is
the same ``psum`` the row-parallel projections already use. A chip that
holds a share of a wider expert-parallel layer (``experts_held``) is the
same layer with that share stored here: it routes over all experts and
computes its own.

Routing (``route``, scope ``moe_route``):

- ``softmax``: top-k of the softmax, gates renormalised over the chosen,
  plus the Switch load-balance aux loss;
- ``sigmoid`` (DeepSeek-V3 ``noaux_tc``): scores s = sigmoid(h W_r) in
  float32; the choice is the top-k of s + b, where b is a selection-only
  bias that is training state, not a parameter; gates
  ``routed_scale * s_i / sum_chosen s_j``; no aux loss. After each step b
  moves by ``bias_rate * sign(mean load - load)`` (``update_bias``).

Dispatch is dropless (scopes ``moe_dispatch`` and ``moe_experts``): the
(token, choice) pairs of one sequence are sorted by held expert, the held
ones first, and the expert SwiGLU is a grouped matmul
(``jax.lax.ragged_dot``) over the rows actually routed here. The static
bound is every pair of the sequence, so no routing can drop a token.
Shared experts (one SwiGLU of ``n_shared_experts`` times the expert
width) see every token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig, ShardCtx, padded_experts
from repro.models.layers import per_row, rmsnorm

Array = jax.Array

HIGHEST = jax.lax.Precision.HIGHEST


def init_router_bias(cfg: ArchConfig, tp: int = 1) -> Array | None:
    """The selection bias of every cycle's router at step 0 (zeros), or
    None where the router keeps no such state."""
    if cfg.router != "sigmoid":
        return None
    return jnp.zeros((cfg.n_cycles, padded_experts(cfg, tp)), jnp.float32)


def update_bias(cfg: ArchConfig, bias: Array, load: Array) -> Array:
    """bias += bias_rate * sign(mean load - load), per cycle over the
    experts that exist. load: (n_cycles, E) tokens routed to each expert,
    summed over the data axis by the caller."""
    with jax.named_scope("moe_route"):
        valid = jnp.arange(bias.shape[-1]) < cfg.n_experts
        mean = jnp.sum(load, -1, keepdims=True) / cfg.n_experts
        return bias + jnp.where(
            valid, cfg.bias_rate * jnp.sign(mean - load), 0.0)


def route(p: dict, cfg: ArchConfig, h: Array,
          bias: Array | None) -> tuple[Array, Array, Array, Array]:
    """h: (T, d) normed tokens -> (chosen experts (T, k), gates (T, k),
    aux loss, load (E,): tokens routed to each expert)."""
    ne = p["router"].shape[-1]
    k = cfg.experts_per_tok
    valid = jnp.arange(ne) < cfg.n_experts         # mask padded experts
    with jax.named_scope("moe_route"):
        if cfg.router == "sigmoid":
            logits = jnp.matmul(h.astype(jnp.float32),
                                p["router"].astype(jnp.float32),
                                precision=HIGHEST)
            s = jax.nn.sigmoid(logits)
            b = jnp.zeros((ne,), jnp.float32) if bias is None else bias
            choice = jnp.where(valid, s + b, -jnp.inf)
            _, eidx = jax.lax.top_k(jax.lax.stop_gradient(choice), k)
            g = jnp.take_along_axis(s, eidx, -1)
            gate = cfg.routed_scale * g / jnp.sum(g, -1, keepdims=True)
            aux = jnp.float32(0.0)
        elif cfg.router == "softmax":
            logits = (h @ p["router"].astype(h.dtype)).astype(jnp.float32)
            probs = jax.nn.softmax(jnp.where(valid, logits, -1e30), -1)
            gate, eidx = jax.lax.top_k(probs, k)
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
            # Switch load balance: E * sum_e mean(route_e) * mean(p_e)
            route_frac = jnp.mean(jnp.sum(
                jax.nn.one_hot(eidx, ne, dtype=jnp.float32), axis=1), 0)
            aux = cfg.n_experts * jnp.sum(route_frac * jnp.mean(probs, 0))
        else:
            raise ValueError(f"unknown router {cfg.router!r}")
        load = jnp.sum(jax.nn.one_hot(eidx, ne, dtype=jnp.float32), (0, 1))
    return eidx, gate, aux, load


def _swiglu_rows(x: Array, wi: Array, wo: Array, sizes: Array) -> Array:
    """The SwiGLU of each held expert over its rows: x (M, d) sorted by
    expert, ``sizes[e]`` rows each from the top; rows past them are 0."""
    live = jnp.arange(x.shape[0])[:, None] < jnp.sum(sizes)

    def grouped(a, w):
        # a TPU leaves the rows past the groups unspecified, in the
        # output and in the backward's lhs cotangent: zero both
        return jnp.where(live, jax.lax.ragged_dot(
            jnp.where(live, a, 0.0), w.astype(a.dtype), sizes), 0.0)

    g, u = jnp.split(grouped(x, wi), 2, axis=-1)
    return grouped(jax.nn.silu(g) * u, wo)


def _routed(p: dict, h: Array, eidx: Array, gate: Array,
            first: Array) -> Array:
    """The held experts' part of the routed output of one sequence.
    h: (1, S, d); eidx, gate: (1, S, k) -> (1, S, d)."""
    _, S, d = h.shape
    k = eidx.shape[-1]
    n_held = p["experts"]["wi"].shape[0]
    with jax.named_scope("moe_dispatch"):
        local = eidx.reshape(S * k) - first
        held = (local >= 0) & (local < n_held)
        key = jnp.where(held, local, n_held)
        order = jnp.argsort(key, stable=True)      # held rows first
        sizes = jnp.sum(key[:, None] == jnp.arange(n_held), 0,
                        dtype=jnp.int32)
        rows = jnp.take(jnp.repeat(h[0], k, axis=0), order, axis=0,
                        unique_indices=True)
    with jax.named_scope("moe_experts"):
        y = _swiglu_rows(rows, p["experts"]["wi"], p["experts"]["wo"],
                         sizes)
    with jax.named_scope("moe_dispatch"):
        back = jnp.take(y, jnp.argsort(order), axis=0,
                        unique_indices=True).reshape(S, k, d)
        w = (gate.reshape(S, k) * held.reshape(S, k)).astype(y.dtype)
        return jnp.sum(back * w[..., None], axis=1)[None]


def _shared(p: dict, h: Array) -> Array:
    with jax.named_scope("moe_experts"):
        act = jax.nn.silu(h @ p["wg"].astype(h.dtype)) \
            * (h @ p["wu"].astype(h.dtype))
        return act @ p["wo"].astype(h.dtype)


def moe_ffn(p: dict, cfg: ArchConfig, ctx: ShardCtx, h: Array,
            bias: Array | None = None, *, first: Array | int | None = None,
            shared: bool = True) -> tuple[Array, Array, Array]:
    """This chip's part of the MoE FFN of normed h: (B, S, d), before the
    combine over 'model'. The stored experts are experts [first, first +
    stored) (default: this rank's slice). Returns (y, aux, load)."""
    B, S, d = h.shape
    eidx, gate, aux, load = route(p, cfg, h.reshape(B * S, d), bias)
    k = eidx.shape[-1]
    if first is None:
        first = ctx.tp_rank() * p["experts"]["wi"].shape[0]
    first = jnp.asarray(first, jnp.int32)

    def one(hr, er, gr):
        y = _routed(p, hr, er, gr, first)
        if shared and "shared" in p:
            y = y + _shared(p["shared"], hr)
        return y

    y = per_row(one, h, eidx.reshape(B, S, k), gate.reshape(B, S, k))
    return y, aux, load


def moe_block(p: dict, cfg: ArchConfig, ctx: ShardCtx, x: Array,
              bias: Array | None = None) -> tuple[Array, Array, Array]:
    """Pre-norm MoE FFN. x: (B, S, d) -> (residual output, aux loss,
    tokens routed to each expert (E,))."""
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    y, aux, load = moe_ffn(p, cfg, ctx, h, bias)
    with jax.named_scope("moe_dispatch"):
        y = ctx.psum_tp(y)
    return x + y.astype(x.dtype), aux, load
