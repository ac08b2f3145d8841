"""Shared model-zoo machinery: configs, TP sharding rules, param specs.

All ten assigned architectures are built from one composable block library
(attention / SwiGLU MLP / MoE / RWKV6 time-mix / Mamba2 SSD / cross-attn)
arranged by a per-arch ``cycle`` pattern that is ``jax.lax.scan``'d over
stacked parameters — compile time and HLO size are depth-independent.

Tensor parallelism is *manual* (Megatron-style): the model runs inside a
fully-manual ``jax.shard_map`` and emits its own collectives over the
``model`` axis. ``ShardCtx`` carries the axis names; ``tp=1, axis=None``
gives the single-device path used by CPU smoke tests (no collectives).

Head/vocab/expert padding for TP=16 follows DESIGN.md §5: Q heads pad up to
a multiple of tp, KV heads with kv < tp are stored replicated (grad-synced
over the model axis), vocab pads to a multiple of 128, experts pad to a
multiple of tp with router masking.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Array = jax.Array


def pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """Exact published architecture hyper-parameters (see configs/<id>.py)."""

    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // n_heads
    qk_norm: bool = False
    rope_theta: float = 10000.0
    # --- latent attention (MLA, DeepSeek-V2 §2.1; kv_lora_rank > 0) ---
    kv_lora_rank: int = 0            # width of the compressed KV latent
    qk_nope_dim: int = 0             # per-head query/key part without rotary
    qk_rope_dim: int = 0             # per-head rotary part (key shared)
    v_head_dim: int = 0              # rotary on pairs (2i, 2i+1)
    # --- MoE ---
    n_experts: int = 0               # routed experts the router scores
    experts_per_tok: int = 0
    moe_d_ff: int = 0                # expert width; 0 -> d_ff
    n_shared_experts: int = 0        # one SwiGLU of n_shared * expert width
    router: str = "softmax"          # softmax (+ aux loss) | sigmoid
    #   sigmoid: DeepSeek-V3 noaux_tc — top-k of score + a selection-only
    #   bias (state, moved by bias_rate * sign(mean load - load) a step)
    routed_scale: float = 1.0        # multiplies the normalised gates
    bias_rate: float = 0.0
    experts_held: int = 0            # experts stored here (this chip's
    #   share of an expert-parallel layer); 0 -> all, split over 'model'
    first_dense: int = 0             # leading dense layers before the cycles
    # --- SSM / hybrid ---
    ssm_state: int = 0
    ssm_head_dim: int = 64           # per-head channel dim for rwkv/mamba
    shared_attn_every: int = 0       # zamba2: weight-tied attn block period
    # --- VLM ---
    cross_attn_every: int = 0        # llama-vision: every Nth layer is cross
    n_cross_tokens: int = 0          # stub frontend: precomputed patch embeds
    # --- misc ---
    block: str = "attn"              # attn | moe | rwkv | mamba
    parallel_block: bool = False     # PaLM-style attn||mlp, 1 psum/layer
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    notes: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    # ---- cycle structure: what one scanned step applies ------------------
    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def expert_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def cycle(self) -> tuple[str, ...]:
        if self.family == "vlm" and self.cross_attn_every:
            return ("attn",) * (self.cross_attn_every - 1) + ("cross",)
        if self.family == "hybrid" and self.shared_attn_every:
            return ("mamba",) * self.shared_attn_every + ("shared_attn",)
        return (self.block,)

    @property
    def n_cycles(self) -> int:
        per = len([b for b in self.cycle if b not in ("shared_attn",)])
        if self.family == "hybrid" and self.shared_attn_every:
            per = self.shared_attn_every
        n, r = divmod(self.n_layers - self.first_dense, per)
        if r:
            raise ValueError(f"{self.name}: n_layers={self.n_layers} not a "
                             f"multiple of cycle length {per}")
        return n

    def params_count(self, tp: int = 1) -> int:
        """Exact parameter count of the *padded* model (python int)."""
        specs = param_specs(self, tp=tp)
        leaves = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, Spec))
        return sum(math.prod(s.shape) for s in leaves)

    def active_params_count(self, tp: int = 1) -> int:
        """Active-per-token params (MoE: only experts_per_tok experts)."""
        total = self.params_count(tp)
        if self.n_experts:
            specs = param_specs(self, tp=tp)

            def expert_leaves(tree):
                out = []
                if isinstance(tree, dict):
                    for k, v in tree.items():
                        if k == "experts":
                            out += jax.tree_util.tree_leaves(
                                v, is_leaf=lambda x: isinstance(x, Spec))
                        else:
                            out += expert_leaves(v)
                return out

            ex = expert_leaves(specs["layers"])
            ex_total = sum(math.prod(s.shape) for s in ex)
            n_exp = stored_experts(self, tp)
            total = total - ex_total + int(ex_total * self.experts_per_tok / n_exp)
        return total


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """How the current computation is sharded (inside manual shard_map)."""

    tp: int = 1
    tp_axis: str | None = None       # None => single-device (no collectives)
    dp_axes: tuple[str, ...] = ()    # data-parallel axes ('data'[, 'pod'])
    dtype: Any = jnp.bfloat16        # activation/weight compute dtype
    comm_dtype: Any = None           # wire dtype for activation psums
    #   (None = compute dtype). float8_e4m3fn halves the TP-collective
    #   roofline term — a beyond-paper serving optimization; numerics
    #   validated in tests/test_perf_opts.py.

    def psum_tp(self, x: Array) -> Array:
        if not self.tp_axis:
            return x
        if self.comm_dtype is not None and x.dtype != jnp.float32:
            # fp8-on-the-wire reduction: per-shard amax scaling into the
            # representable range, all-gather the fp8 payload (1 B/elem,
            # (P-1)/P of it — 4x fewer wire bytes than a bf16 all-reduce),
            # then dequantize + sum locally in f32.
            amax = jnp.maximum(jax.lax.stop_gradient(
                jnp.max(jnp.abs(x.astype(jnp.float32)))), 1e-12)
            scale = 448.0 / amax
            y8 = (x.astype(jnp.float32) * scale).astype(self.comm_dtype)
            g8 = jax.lax.all_gather(y8, self.tp_axis)          # (P, ...)
            scales = jax.lax.all_gather(scale, self.tp_axis)   # (P,)
            sh = (self.tp,) + (1,) * x.ndim
            y = jnp.sum(g8.astype(jnp.float32) / scales.reshape(sh), axis=0)
            return y.astype(x.dtype)
        return jax.lax.psum(x, self.tp_axis)

    def pmax_tp(self, x: Array) -> Array:
        return jax.lax.pmax(x, self.tp_axis) if self.tp_axis else x

    def tp_rank(self) -> Array:
        return (jax.lax.axis_index(self.tp_axis) if self.tp_axis
                else jnp.int32(0))


# ---------------------------------------------------------------------------
# Padded/sharded geometry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HeadGeom:
    nq: int          # padded global q heads (multiple of tp)
    nkv: int         # kv heads as stored (== cfg kv heads, never padded)
    nq_loc: int      # q heads per shard
    nkv_loc: int     # kv heads per shard (0 => replicated storage, 1 used)
    kv_replicated: bool

    @property
    def q_per_kv(self) -> int:
        return self.nq // max(self.nkv, 1)


def head_geometry(cfg: ArchConfig, tp: int) -> HeadGeom:
    nq = pad_to(cfg.n_heads, tp)
    nkv = cfg.n_kv_heads
    if nkv >= tp:
        if nkv % tp:
            nkv = pad_to(nkv, tp)  # pad kv heads too (e.g. minicpm MHA 36->48)
        return HeadGeom(max(nq, nkv), nkv, max(nq, nkv) // tp, nkv // tp, False)
    # kv < tp: replicated storage; each shard slices 1 kv head
    return HeadGeom(nq, nkv, nq // tp, 1, True)


def padded_vocab(cfg: ArchConfig, tp: int) -> int:
    return pad_to(cfg.vocab_size, max(128, tp))


def padded_experts(cfg: ArchConfig, tp: int) -> int:
    """Experts the router scores (padded to a multiple of tp, masked)."""
    return pad_to(cfg.n_experts, tp) if cfg.n_experts else 0


def stored_experts(cfg: ArchConfig, tp: int) -> int:
    """Experts whose weights this chip stores: its held share, or every
    (padded) expert, split over 'model'."""
    return cfg.experts_held or padded_experts(cfg, tp)


# ---------------------------------------------------------------------------
# Parameter specs: single source of truth for shapes/sharding/init-scale.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Spec:
    """One parameter leaf: GLOBAL (padded) shape + partition + init scale."""

    shape: tuple[int, ...]
    pspec: P
    scale: float = 0.02
    dtype: Any = jnp.float32  # master params are f32; compute casts to bf16

    def local_shape(self, tp: int) -> tuple[int, ...]:
        out = []
        for dim, ax in zip(self.shape, tuple(self.pspec) + (None,) * 8):
            out.append(dim // tp if ax == "model" else dim)
        return tuple(out)


def _attn_specs(cfg: ArchConfig, tp: int, cross: bool = False) -> dict:
    g = head_geometry(cfg, tp)
    d, hd = cfg.d_model, cfg.hd
    kv_pspec = P(None, None) if g.kv_replicated else P(None, "model")
    kv_cols = g.nkv * hd
    s = {
        "wq": Spec((d, g.nq * hd), P(None, "model")),
        "wk": Spec((d, kv_cols), kv_pspec),
        "wv": Spec((d, kv_cols), kv_pspec),
        "wo": Spec((g.nq * hd, d), P("model", None)),
        "norm": Spec((d,), P(None), scale=0.0),  # RMSNorm gain (1 + x)
    }
    if cfg.qk_norm:
        s["q_norm"] = Spec((hd,), P(None), scale=0.0)
        s["k_norm"] = Spec((hd,), P(None), scale=0.0)
    if cross:
        s["kv_norm"] = Spec((d,), P(None), scale=0.0)
    return s


def _mla_specs(cfg: ArchConfig, tp: int) -> dict:
    """Latent attention: per-head projections column-parallel by head; the
    shared down-projection to [c_kv, k_pe] and its norm replicated."""
    d, h = cfg.d_model, pad_to(cfg.n_heads, tp)
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "wq": Spec((d, h * qk), P(None, "model")),
        "wkva": Spec((d, cfg.kv_lora_rank + cfg.qk_rope_dim), P(None, None)),
        "kv_norm": Spec((cfg.kv_lora_rank,), P(None), scale=0.0),
        "wkvb": Spec((cfg.kv_lora_rank,
                      h * (cfg.qk_nope_dim + cfg.v_head_dim)),
                     P(None, "model")),
        "wo": Spec((h * cfg.v_head_dim, d), P("model", None)),
        "norm": Spec((d,), P(None), scale=0.0),
    }


def _self_attn_specs(cfg: ArchConfig, tp: int) -> dict:
    return _mla_specs(cfg, tp) if cfg.mla else _attn_specs(cfg, tp)


def _mlp_specs(cfg: ArchConfig, tp: int) -> dict:
    d, ff = cfg.d_model, pad_to(cfg.d_ff, tp)
    # gate/up kept as separate leaves: a fused (d, 2ff) matrix cannot be
    # column-sharded (rank 0 would hold all-gate, rank 1 all-up).
    return {
        "wg": Spec((d, ff), P(None, "model")),
        "wu": Spec((d, ff), P(None, "model")),
        "wo": Spec((ff, d), P("model", None)),
        "norm": Spec((d,), P(None), scale=0.0),
    }


def _moe_specs(cfg: ArchConfig, tp: int) -> dict:
    d, ff = cfg.d_model, cfg.expert_ff
    ne = stored_experts(cfg, tp)
    s = {
        # scores every expert; replicated, grad psum'd
        "router": Spec((d, padded_experts(cfg, tp)), P(None, None)),
        "experts": {
            # stored experts split over 'model' on the expert axis (EP)
            "wi": Spec((ne, d, 2 * ff), P("model", None, None)),
            "wo": Spec((ne, ff, d), P("model", None, None)),
        },
        "norm": Spec((d,), P(None), scale=0.0),
    }
    if cfg.n_shared_experts:
        fs = pad_to(cfg.n_shared_experts * ff, tp)
        s["shared"] = {"wg": Spec((d, fs), P(None, "model")),
                       "wu": Spec((d, fs), P(None, "model")),
                       "wo": Spec((fs, d), P("model", None))}
    return s


def _rwkv_specs(cfg: ArchConfig, tp: int) -> dict:
    d = cfg.d_model
    nh = pad_to(d // cfg.ssm_head_dim, tp)  # wkv heads
    dh = nh * cfg.ssm_head_dim              # padded inner width
    ff = pad_to(cfg.d_ff, tp)
    return {
        # time-mix: receptance/key/value/gate column-parallel by head
        "wr": Spec((d, dh), P(None, "model")),
        "wk": Spec((d, dh), P(None, "model")),
        "wv": Spec((d, dh), P(None, "model")),
        "wg": Spec((d, dh), P(None, "model")),
        "ww": Spec((d, dh), P(None, "model"), scale=0.002),  # decay lora
        "w_bias": Spec((dh,), P("model"), scale=0.0),
        "bonus": Spec((dh,), P("model"), scale=0.02),        # 'u' term
        "wo": Spec((dh, d), P("model", None)),
        "mu": Spec((4, d), P(None, None), scale=0.0),        # token-shift mix
        "norm": Spec((d,), P(None), scale=0.0),
        # channel-mix (RWKV FFN): relu^2
        "ck": Spec((d, ff), P(None, "model")),
        "cv": Spec((ff, d), P("model", None)),
        "cmu": Spec((1, d), P(None, None), scale=0.0),
        "cnorm": Spec((d,), P(None), scale=0.0),
    }


def _mamba_specs(cfg: ArchConfig, tp: int) -> dict:
    d = cfg.d_model
    nh = pad_to(max(1, d // cfg.ssm_head_dim), tp)
    dh = nh * cfg.ssm_head_dim
    ns = cfg.ssm_state
    return {
        # in_proj -> [x (dh), z (dh)] column-parallel by head
        "wx": Spec((d, dh), P(None, "model")),
        "wz": Spec((d, dh), P(None, "model")),
        # B, C projections: per-head state inputs (shared across head dim)
        "wB": Spec((d, nh * ns), P(None, "model")),
        "wC": Spec((d, nh * ns), P(None, "model")),
        "wdt": Spec((d, nh), P(None, "model")),
        "dt_bias": Spec((nh,), P("model"), scale=0.0),
        "A_log": Spec((nh,), P("model"), scale=0.0),
        "D": Spec((nh,), P("model"), scale=0.0),
        "conv": Spec((4, dh), P(None, "model"), scale=0.1),  # depthwise conv
        "wo": Spec((dh, d), P("model", None)),
        "norm": Spec((d,), P(None), scale=0.0),
        "gnorm": Spec((dh,), P("model"), scale=0.0),  # gated RMSNorm pre-out
    }


_BLOCK_SPECS = {
    "attn": lambda c, t: {**_attn_specs(c, t), **{"mlp": _mlp_specs(c, t)}},
    "cross": lambda c, t: {**_attn_specs(c, t, cross=True),
                           **{"mlp": _mlp_specs(c, t)}},
    "moe": lambda c, t: {**_self_attn_specs(c, t),
                         **{"moe": _moe_specs(c, t)}},
    "rwkv": lambda c, t: _rwkv_specs(c, t),
    "mamba": lambda c, t: _mamba_specs(c, t),
}


def _stack(tree: Any, n: int) -> Any:
    """Prefix every Spec's shape with the scan (cycle) axis."""
    def f(s: Spec) -> Spec:
        return Spec((n,) + s.shape, P(*((None,) + tuple(s.pspec))),
                    s.scale, s.dtype)
    return jax.tree_util.tree_map(f, tree,
                                  is_leaf=lambda x: isinstance(x, Spec))


def param_specs(cfg: ArchConfig, tp: int = 1) -> dict:
    """Full pytree of Spec for the padded model at the given TP degree."""
    vp = padded_vocab(cfg, tp)
    d = cfg.d_model
    specs: dict = {
        "embed": Spec((vp, d), P("model", None), scale=0.02),
        "final_norm": Spec((d,), P(None), scale=0.0),
    }
    if not cfg.tie_embeddings:
        specs["head"] = Spec((d, vp), P(None, "model"))

    # One params sub-tree per block kind in the cycle; kinds appearing
    # multiple times per cycle (e.g. vlm: 4x 'attn') get an extra stacked
    # axis, and the whole layer dict is stacked over n_cycles for lax.scan.
    layer: dict = {}
    counts: dict[str, int] = {}
    for kind in cfg.cycle:
        if kind != "shared_attn":
            counts[kind] = counts.get(kind, 0) + 1
    for kind, cnt in counts.items():
        sub = _BLOCK_SPECS[kind](cfg, tp)
        layer[kind] = _stack(sub, cnt)
    specs["layers"] = _stack(layer, cfg.n_cycles)
    if cfg.first_dense:   # leading dense layers, applied before the cycles
        specs["lead"] = _stack({**_self_attn_specs(cfg, tp),
                                "mlp": _mlp_specs(cfg, tp)}, cfg.first_dense)

    if "shared_attn" in cfg.cycle:
        specs["shared_attn"] = {**_attn_specs(cfg, tp),
                                "mlp": _mlp_specs(cfg, tp)}
    return specs


def abstract_params(cfg: ArchConfig, mesh, tp: int) -> Any:
    """ShapeDtypeStruct pytree with NamedSharding — dry-run stand-ins."""
    from jax.sharding import NamedSharding

    def f(s: Spec):
        return jax.ShapeDtypeStruct(s.shape, s.dtype,
                                    sharding=NamedSharding(mesh, s.pspec))
    return jax.tree_util.tree_map(f, param_specs(cfg, tp),
                                  is_leaf=lambda x: isinstance(x, Spec))


def init_params(cfg: ArchConfig, key: Array, tp: int = 1) -> Any:
    """Concrete (global-shape) parameter init — smoke tests / examples."""
    specs = param_specs(cfg, tp)
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, Spec))
    keys = jax.random.split(key, len(leaves))
    vals = []
    for s, k in zip(leaves, keys):
        if s.scale == 0.0:
            vals.append(jnp.zeros(s.shape, s.dtype))
        else:
            vals.append(s.scale * jax.random.normal(k, s.shape, s.dtype))
    return jax.tree_util.tree_unflatten(treedef, vals)


def pspec_tree(cfg: ArchConfig, tp: int = 1) -> Any:
    """PartitionSpec pytree (shard_map in_specs for the params argument)."""
    return jax.tree_util.tree_map(lambda s: s.pspec, param_specs(cfg, tp),
                                  is_leaf=lambda x: isinstance(x, Spec))
