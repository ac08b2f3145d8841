"""Plain float32 reference of a DeepSeek-V3-type decoder on one chip's
expert share: latent attention (MLA), leading dense layers, then MoE
layers with sigmoid routing, a selection-only bias and shared experts.

Straightforward ``jax.numpy`` with every matrix product at
``Precision.HIGHEST``. One sequence at a time; per sequence: embedding,
then per layer

- RMSNorm -> latent attention: q = h W_q split into [q_nope, q_pe];
  [c_kv, k_pe] = h W_kva; [k_nope, v] = RMSNorm(c_kv) W_kvb; rotary on
  q_pe and the one k_pe all heads share, on interleaved pairs (2i, 2i+1)
  at angle pos * theta^(-2i/rope_dim); exact causal softmax of
  [q_nope, q_pe] . [k_nope, k_pe] / sqrt(qk_nope + qk_rope), computed for
  one block of queries at a time against every key, masked; o = W_o;
  residual;
- RMSNorm -> the first ``first_k_dense_replace`` layers a SwiGLU MLP;
  the rest an MoE FFN: scores s = sigmoid(h W_r) over every routed expert
  of the published layer, the choice the top-k of s + b (b the layer's
  selection bias), gates ``routed_scaling_factor * s_i / sum_chosen s``;
  the output the gate-weighted sum of the held experts' SwiGLU, each
  computed over every token and weighted by its gate (zero where not
  chosen), plus one shared SwiGLU of ``n_shared_experts`` times the
  expert width; residual;

then a final RMSNorm, the output head and the mean next-token
cross-entropy over the vocabulary slice. No kernel, no sorting, no
grouped matmul, no cache. It imports nothing of the program: the sizes
come from the configuration file (``n_routed_experts`` is the experts held
here, experts 0 .. held-1; ``published.n_routed_experts`` the router's
width).

The selection bias is state: it starts at zero, and once a step's
gradients are taken it moves by ``bias_rate * sign(mean load - load_i)``,
the load counted over every expert and summed over the workers of the
step (DeepSeek-V3 §2.1.2). ``loss_and_grad`` keeps it with the model: a
call with parameters other than the previous call's opens a new step and
first applies the loads the previous step counted; ``init_params`` resets
it.

Departures from the published description, all shared with the program:
the held share of the experts and the vocabulary slice (the absent
experts' part of each MoE output is left out, the loss is over the slice);
RMSNorm gains stored as a delta around 1; no gate epsilon in the
normalisation.

Weights are made from the run's seed by the rule the configuration file
states under ``init`` (one key per leaf, split from ``PRNGKey(seed)`` in
the order of ``leaf_specs``; matrices normal x 0.02, gains zero).
``pack``/``unpack`` map the named leaves to the flat vector in the order
``flat_layout`` states: two top-level segments, then one row per MoE
layer of each per-layer segment, each zero-padded to ``pad_multiple``.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_NEG = -1e30
Q_BLOCK = 512


class RouterState:
    """The selection bias (moe_layers, experts) and the step it is at."""

    def __init__(self):
        self.reset(None)

    def reset(self, bias):
        self.bias = bias
        self.pending = None     # loads counted in the current step
        self.params = None      # the parameters of the current step


@dataclasses.dataclass(frozen=True)
class Decoder:
    layers: int
    first_dense: int
    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int
    d_ff: int
    moe_d_ff: int
    n_experts: int          # the router's width (the published count)
    experts_held: int
    top_k: int
    n_shared: int
    routed_scale: float
    bias_rate: float
    vocab: int
    rope_theta: float
    norm_eps: float
    pad_multiple: int = 512
    vocab_multiple: int = 128
    state: RouterState = dataclasses.field(
        default_factory=RouterState, compare=False, hash=False, repr=False)

    @classmethod
    def from_config(cls, c: dict) -> "Decoder":
        return cls(layers=c["num_hidden_layers"],
                   first_dense=c["first_k_dense_replace"],
                   d_model=c["hidden_size"],
                   n_heads=c["num_attention_heads"],
                   kv_lora_rank=c["kv_lora_rank"],
                   qk_nope=c["qk_nope_head_dim"],
                   qk_rope=c["qk_rope_head_dim"],
                   v_head=c["v_head_dim"], d_ff=c["intermediate_size"],
                   moe_d_ff=c["moe_intermediate_size"],
                   n_experts=c["published"]["n_routed_experts"],
                   experts_held=c["n_routed_experts"],
                   top_k=c["num_experts_per_tok"],
                   n_shared=c["n_shared_experts"],
                   routed_scale=float(c["routed_scaling_factor"]),
                   bias_rate=float(c["bias_update_rate"]),
                   vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   norm_eps=float(c["rms_norm_eps"]),
                   pad_multiple=c["flat_layout"]["pad_multiple"],
                   vocab_multiple=c["flat_layout"]["vocab_multiple"])

    @property
    def moe_layers(self) -> int:
        return self.layers - self.first_dense

    @property
    def vocab_padded(self) -> int:
        m = self.vocab_multiple
        return -(-self.vocab // m) * m


# (name, shape, init scale, flat segment)
def leaf_specs(m: Decoder) -> list[tuple[str, tuple[int, ...], float, str]]:
    """Every leaf in seed-key order, with its shape and flat segment.

    Per-MoE-layer leaves are stacked over those layers (a leading axis,
    then a block-count axis of 1); the leading dense layers' leaves over
    the dense layers, and they live in the top-level segments.
    """
    d, v, H = m.d_model, m.vocab_padded, m.n_heads
    qk = m.qk_nope + m.qk_rope
    ff, fs = m.moe_d_ff, m.n_shared * m.moe_d_ff
    lay = lambda *s: (m.moe_layers, 1) + s  # noqa: E731
    lead = lambda *s: (m.first_dense,) + s  # noqa: E731

    def mla(shape, seg):
        return [
            ("kv_norm", shape(m.kv_lora_rank), 0.0, seg + "_r"),
        ], [
            ("norm", shape(d), 0.0, seg + "_r"),
            ("wkva", shape(d, m.kv_lora_rank + m.qk_rope), 0.02, seg + "_r"),
            ("wkvb", shape(m.kv_lora_rank, H * (m.qk_nope + m.v_head)), 0.02,
             seg + "_s"),
            ("wo", shape(H * m.v_head, d), 0.02, seg + "_s"),
            ("wq", shape(d, H * qk), 0.02, seg + "_s"),
        ]

    c_head, c_tail = mla(lay, "cycles")
    l_head, l_tail = mla(lead, "top")
    cycles = c_head + [
        ("moe.experts.wi", lay(m.experts_held, d, 2 * ff), 0.02, "cycles_s"),
        ("moe.experts.wo", lay(m.experts_held, ff, d), 0.02, "cycles_s"),
        ("moe.norm", lay(d), 0.0, "cycles_r"),
        ("moe.router", lay(d, m.n_experts), 0.02, "cycles_r"),
        ("moe.shared.wg", lay(d, fs), 0.02, "cycles_s"),
        ("moe.shared.wo", lay(fs, d), 0.02, "cycles_s"),
        ("moe.shared.wu", lay(d, fs), 0.02, "cycles_s"),
    ] + c_tail
    leading = l_head + [
        ("mlp.norm", lead(d), 0.0, "top_r"),
        ("mlp.wg", lead(d, m.d_ff), 0.02, "top_s"),
        ("mlp.wo", lead(m.d_ff, d), 0.02, "top_s"),
        ("mlp.wu", lead(d, m.d_ff), 0.02, "top_s"),
    ] + l_tail
    return ([("embed", (v, d), 0.02, "top_s"),
             ("final_norm", (d,), 0.0, "top_r"),
             ("head", (d, v), 0.02, "top_s")]
            + [("layers." + n, *r) for n, *r in cycles]
            + [("lead." + n, *r) for n, *r in leading])


SEGMENTS = ("top_s", "top_r", "cycles_s", "cycles_r")


def init_params(m: Decoder, seed: int) -> dict:
    """Named leaves from the seed (module docstring), in one jitted call on
    the default device; the selection bias back at zero. ``PRNGKey`` keeps
    the low 32 bits of a seed."""
    m.state.reset(jnp.zeros((m.moe_layers, m.n_experts), jnp.float32))
    return _init_fn(m)(jnp.uint32(int(seed) % 2**32))


@functools.lru_cache(maxsize=None)
def _init_fn(m: Decoder):
    def make(s):
        specs = leaf_specs(m)
        keys = jax.random.split(jax.random.PRNGKey(s), len(specs))
        return {name: (jnp.zeros(shape, jnp.float32) if scale == 0.0 else
                       scale * jax.random.normal(k, shape, jnp.float32))
                for (name, shape, scale, _), k in zip(specs, keys)}
    return jax.jit(make)


# ---------------------------------------------------------------------------
# Flat layout
# ---------------------------------------------------------------------------


def _pad(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def layout(m: Decoder) -> tuple[list, dict[str, tuple[int, ...]]]:
    """(name, segment, offset in a row, size in a row) of every leaf, and
    the shape of every segment."""
    off = {s: 0 for s in SEGMENTS}
    slots = []
    for name, shape, _, seg in leaf_specs(m):
        size = math.prod(shape[1:] if seg.startswith("cycles") else shape)
        slots.append((name, seg, off[seg], size))
        off[seg] += size
    p, n = m.pad_multiple, m.moe_layers
    shapes = {"top_s": (_pad(off["top_s"], p),),
              "top_r": (_pad(off["top_r"], p),),
              "cycles_s": (n, _pad(off["cycles_s"], p)),
              "cycles_r": (n, _pad(off["cycles_r"], p))}
    return slots, shapes


def flat_size(m: Decoder) -> int:
    return sum(math.prod(s) for s in layout(m)[1].values())


def to_segments(m: Decoder, params: dict) -> dict:
    """Named leaves -> segment arrays (zero padding included)."""
    slots, shapes = layout(m)
    segs = {}
    for seg in SEGMENTS:
        rows = seg.startswith("cycles")
        parts = [params[name].reshape((m.moe_layers, -1) if rows else (-1,))
                 for name, s, _, _ in slots if s == seg]
        cat = jnp.concatenate(parts, axis=-1)
        pad = [(0, 0)] * (cat.ndim - 1) + [(0, shapes[seg][-1]
                                            - cat.shape[-1])]
        segs[seg] = jnp.pad(cat, pad)
    return segs


def leaves_of_segments(m: Decoder, segs: dict) -> dict:
    """Segment arrays -> named leaves (the inverse of ``to_segments``)."""
    slots, _ = layout(m)
    shapes = {n: s for n, s, _, _ in leaf_specs(m)}
    return {name: segs[seg][..., off:off + size].reshape(shapes[name])
            for name, seg, off, size in slots}


def pack(m: Decoder, params: dict) -> jax.Array:
    """Named leaves -> the flat vector (segments in order, rows in order)."""
    segs = to_segments(m, params)
    return jnp.concatenate([segs[k].reshape(-1) for k in SEGMENTS])


def unpack(m: Decoder, flat: jax.Array) -> dict:
    _, shapes = layout(m)
    segs, off = {}, 0
    for k in SEGMENTS:
        n = math.prod(shapes[k])
        segs[k] = flat[off:off + n].reshape(shapes[k])
        off += n
    return leaves_of_segments(m, segs)


# ---------------------------------------------------------------------------
# Forward and loss, one sequence
# ---------------------------------------------------------------------------


def _rmsnorm(x, gain_delta, eps):
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return ((1.0 + gain_delta.astype(jnp.float32)) * xf * r).astype(x.dtype)


def _rotate_pairs(x, theta):
    """x: (S, H, r); pair (2i, 2i+1) of position t turned by
    t * theta^(-2i/r)."""
    S, H, r = x.shape
    freqs = theta ** (-2.0 * jnp.arange(r // 2, dtype=jnp.float32) / r)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    pairs = x.reshape(S, H, r // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(S, H, r)


def _mm(a, b, precision):
    return jnp.matmul(a, b.astype(a.dtype), precision=precision)


def _swiglu(h, wg, wu, wo, precision):
    return _mm(jax.nn.silu(_mm(h, wg, precision)) * _mm(h, wu, precision),
               wo, precision)


def _attention(m: Decoder, lp: dict, h, precision):
    """Latent attention of one normed sequence h: (S, d) -> (S, d)."""
    S = h.shape[0]
    H, dn, dr, dv = m.n_heads, m.qk_nope, m.qk_rope, m.v_head
    q = _mm(h, lp["wq"], precision).reshape(S, H, dn + dr)
    kva = _mm(h, lp["wkva"], precision)
    c_kv = _rmsnorm(kva[:, :m.kv_lora_rank], lp["kv_norm"], m.norm_eps)
    kvb = _mm(c_kv, lp["wkvb"], precision).reshape(S, H, dn + dv)
    q = jnp.concatenate(
        [q[..., :dn], _rotate_pairs(q[..., dn:], m.rope_theta)], -1)
    k_pe = _rotate_pairs(kva[:, None, m.kv_lora_rank:], m.rope_theta)
    k = jnp.concatenate([kvb[..., :dn],
                         jnp.broadcast_to(k_pe, (S, H, dr))], -1)
    v = kvb[..., dn:]

    @jax.checkpoint
    def block(xs):          # one block of queries against every key
        qb, a = xs
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=precision)
        s = s.astype(jnp.float32) * (dn + dr) ** -0.5
        qpos = a + jnp.arange(qb.shape[0])[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= qpos, s, _NEG)
        p = jax.nn.softmax(s, axis=-1).astype(h.dtype)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=precision)

    nb = -(-S // Q_BLOCK)
    qs = jnp.pad(q, ((0, nb * Q_BLOCK - S), (0, 0), (0, 0)))
    o = jax.lax.map(block, (qs.reshape(nb, Q_BLOCK, H, dn + dr),
                            jnp.arange(nb) * Q_BLOCK))
    o = o.reshape(nb * Q_BLOCK, H, dv)[:S]
    return _mm(o.reshape(S, H * dv), lp["wo"], precision)


def _moe(m: Decoder, lp: dict, h, bias, precision):
    """The held experts' and the shared experts' output for one normed
    sequence h: (S, d), and the tokens routed to each expert (E,)."""
    s = jax.nn.sigmoid(_mm(h, lp["moe.router"], precision)
                       .astype(jnp.float32))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(s + bias), m.top_k)
    chosen = jax.nn.one_hot(idx, m.n_experts, dtype=jnp.float32)  # S,k,E
    g = jnp.sum(chosen * s[:, None, :], -1)
    gate = m.routed_scale * g / jnp.sum(g, -1, keepdims=True)
    gates = jnp.sum(chosen * gate[..., None], 1)                  # S, E
    ff = m.moe_d_ff

    def expert(y, xs):     # one held expert over every token
        wi, wo, g = xs
        out = _swiglu(h, wi[:, :ff], wi[:, ff:], wo, precision)
        return y + g[:, None].astype(h.dtype) * out, None

    y = _swiglu(h, lp["moe.shared.wg"], lp["moe.shared.wu"],
                lp["moe.shared.wo"], precision)
    y, _ = jax.lax.scan(expert, y, (lp["moe.experts.wi"],
                                    lp["moe.experts.wo"],
                                    gates[:, :m.experts_held].T))
    return y, jnp.sum(chosen, (0, 1))


def _row_forward(m: Decoder, params: dict, bias, tokens, dtype, precision):
    """Logits (S, vocab_padded; padding columns at -1e30) and the MoE
    layers' loads (moe_layers, E) of one sequence."""
    p = params
    x = p["embed"].astype(dtype)[tokens]
    for i in range(m.first_dense):
        lp = {k[5:]: v[i] for k, v in p.items() if k.startswith("lead.")}
        x = x + jax.checkpoint(_attention, static_argnums=(0, 3))(
            m, lp, _rmsnorm(x, lp["norm"], m.norm_eps), precision)
        x = x + _swiglu(_rmsnorm(x, lp["mlp.norm"], m.norm_eps),
                        lp["mlp.wg"], lp["mlp.wu"], lp["mlp.wo"], precision)
    @jax.checkpoint
    def layer(x, xs):
        lp, b = xs
        x = x + _attention(m, lp, _rmsnorm(x, lp["norm"], m.norm_eps),
                           precision)
        y, load = _moe(m, lp, _rmsnorm(x, lp["moe.norm"], m.norm_eps), b,
                       precision)
        return x + y, load

    # the MoE layers one after another (a loop, not unrolled)
    x, loads = jax.lax.scan(layer, x, ({k[7:]: v[:, 0] for k, v in p.items()
                                        if k.startswith("layers.")}, bias))
    x = _rmsnorm(x, p["final_norm"], m.norm_eps)
    logits = _mm(x, p["head"], precision).astype(jnp.float32)
    return (jnp.where(jnp.arange(m.vocab_padded) < m.vocab, logits, _NEG),
            loads)


def _row_loss(m: Decoder, params: dict, bias, tokens, labels, dtype,
              precision):
    """Loss and the MoE layers' loads of one sequence."""
    logits, loads = _row_forward(m, params, bias, tokens, dtype, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    w = (labels >= 0).astype(jnp.float32)
    loss = jnp.sum((lse - picked) * w) / jnp.maximum(jnp.sum(w), 1.0)
    return loss, loads


def logits(m: Decoder, params: dict, tokens, bias=None, *,
           precision=HIGHEST):
    """Logits (B, S, vocab_padded) and loads (B, moe_layers, E), one row
    at a time, at the given selection bias (default zero)."""
    if bias is None:
        bias = jnp.zeros((m.moe_layers, m.n_experts), jnp.float32)
    return jax.lax.map(lambda t: _row_forward(m, params, bias, t,
                                              jnp.float32, precision),
                       jnp.asarray(tokens))


def loss_and_grad(m: Decoder, params: dict, tokens, labels, *,
                  dtype=jnp.float32, precision=HIGHEST):
    """Mean loss over the rows and its gradient, one row at a time (a scan
    whose carry sums the rows' losses, gradients and loads); every row has
    the same number of labelled positions. Keeps the selection bias
    (module docstring)."""
    st = m.state
    if params is not st.params:
        if st.pending is not None:
            st.bias = _update_bias(m, st.bias, st.pending)
        st.pending, st.params = None, params
    loss, grads, load = _loss_and_grad_fn(m, jnp.dtype(dtype), precision)(
        params, st.bias, (tokens, labels))
    st.pending = load if st.pending is None else st.pending + load
    return loss, grads


def _update_bias(m: Decoder, bias, load):
    mean = jnp.sum(load, -1, keepdims=True) / m.n_experts
    return bias + m.bias_rate * jnp.sign(mean - load)


@functools.lru_cache(maxsize=None)
def _loss_and_grad_fn(m: Decoder, dtype, precision):
    vg = jax.value_and_grad(
        lambda q, b, t, lab: _row_loss(m, q, b, t, lab, dtype, precision),
        has_aux=True)

    def run(p, bias, rows):
        def body(carry, xs):
            tot, acc, ld = carry
            (l_i, load), g_i = vg(p, bias, *xs)
            return (tot + l_i, jax.tree_util.tree_map(jnp.add, acc, g_i),
                    ld + load), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, p)
        init = (jnp.float32(0.0), zeros, jnp.zeros_like(bias))
        (tot, acc, ld), _ = jax.lax.scan(body, init, rows)
        n = rows[0].shape[0]
        return tot / n, jax.tree_util.tree_map(lambda g: g / n, acc), ld

    return jax.jit(run)
