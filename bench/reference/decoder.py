"""Plain float32 reference of the pre-norm decoder the program trains.

Straightforward ``jax.numpy`` with every matrix product at
``Precision.HIGHEST``: embedding, then per layer RMSNorm -> causal
grouped-query attention with rotary positions -> residual, RMSNorm ->
SwiGLU MLP -> residual, then a final RMSNorm, the output head and the mean
next-token cross-entropy. No kernel, no chunking, no remat, no cache. It
imports nothing of the program: the sizes come from the configuration file.

Weights are made from the run's seed by the rule the configuration file
states under ``init``: one key per leaf, split from ``PRNGKey(seed)`` in
the order of ``leaf_specs``, a normal draw of the leaf's whole stacked
shape times 0.02 for every matrix, zeros for every RMSNorm gain (stored as
a delta around 1).

``pack`` and ``unpack`` map the named leaves to one flat vector in the
order the configuration file states under ``flat_layout`` (two top-level
segments, then one row per layer of each per-layer segment, each segment
zero-padded to a multiple of ``pad_multiple``). The exchange hashes flat
coordinates, so the reference needs the same numbering to sketch the same
vector; the harness reads the program's flat state through the same map.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_NEG = -1e30


@dataclasses.dataclass(frozen=True)
class Decoder:
    layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    pad_multiple: int = 512
    vocab_multiple: int = 128

    @classmethod
    def from_config(cls, c: dict) -> "Decoder":
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   norm_eps=float(c["rms_norm_eps"]),
                   pad_multiple=c["flat_layout"]["pad_multiple"],
                   vocab_multiple=c["flat_layout"]["vocab_multiple"])

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def vocab_padded(self) -> int:
        m = self.vocab_multiple
        return -(-self.vocab // m) * m


# (name, per-layer shape or None for top level, init scale, flat segment)
def leaf_specs(m: Decoder) -> list[tuple[str, tuple[int, ...], float, str]]:
    """Every leaf in seed-key order, with its shape and flat segment.

    Top-level shapes are whole; per-layer leaves are stacked over layers
    (a leading ``layers`` axis, then a block-count axis of 1).
    """
    d, f, v, hd = m.d_model, m.d_ff, m.vocab_padded, m.head_dim
    L = m.layers
    lay = lambda *s: (L, 1) + s  # noqa: E731
    return [
        ("embed", (v, d), 0.02, "top_s"),
        ("final_norm", (d,), 0.0, "top_r"),
        ("head", (d, v), 0.02, "top_s"),
        ("mlp_norm", lay(d), 0.0, "cycles_r"),
        ("mlp_wg", lay(d, f), 0.02, "cycles_s"),
        ("mlp_wo", lay(f, d), 0.02, "cycles_s"),
        ("mlp_wu", lay(d, f), 0.02, "cycles_s"),
        ("attn_norm", lay(d), 0.0, "cycles_r"),
        ("wk", lay(d, m.n_kv_heads * hd), 0.02, "cycles_s"),
        ("wo", lay(m.n_heads * hd, d), 0.02, "cycles_s"),
        ("wq", lay(d, m.n_heads * hd), 0.02, "cycles_s"),
        ("wv", lay(d, m.n_kv_heads * hd), 0.02, "cycles_s"),
    ]


SEGMENTS = ("top_s", "top_r", "cycles_s", "cycles_r")


def init_params(m: Decoder, seed: int) -> dict:
    """Named leaves from the seed (module docstring), in one jitted call on
    the default device. ``PRNGKey`` keeps the low 32 bits of a seed."""
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


@dataclasses.dataclass(frozen=True)
class Slot:
    name: str
    segment: str
    offset: int      # within one row of the segment
    size: int        # per row (per layer for per-layer leaves)


def _pad(x: int, m: int) -> int:
    return -(-x // m) * m


def layout(m: Decoder) -> tuple[list[Slot], dict[str, tuple[int, ...]]]:
    """Slots of every leaf and the shape of every segment."""
    off = {s: 0 for s in SEGMENTS}
    slots = []
    for name, shape, _, seg in leaf_specs(m):
        size = math.prod(shape[1:]) if seg.startswith("cycles") \
            else math.prod(shape)
        slots.append(Slot(name, seg, off[seg], size))
        off[seg] += size
    p = m.pad_multiple
    shapes = {"top_s": (_pad(off["top_s"], p),),
              "top_r": (_pad(off["top_r"], p),),
              "cycles_s": (m.layers, _pad(off["cycles_s"], p)),
              "cycles_r": (m.layers, _pad(off["cycles_r"], p))}
    return slots, shapes


def flat_size(m: Decoder) -> int:
    return sum(math.prod(s) for s in layout(m)[1].values())


def to_segments(m: Decoder, params: dict) -> dict:
    """Named leaves -> segment arrays (zero padding included)."""
    slots, shapes = layout(m)
    segs = {}
    for seg in SEGMENTS:
        parts = []
        for s in slots:
            if s.segment != seg:
                continue
            a = params[s.name]
            parts.append(a.reshape(m.layers, -1) if seg.startswith("cycles")
                         else a.reshape(-1))
        width = shapes[seg][-1]
        if seg.startswith("cycles"):
            cat = jnp.concatenate(parts, axis=1)
            segs[seg] = jnp.pad(cat, ((0, 0), (0, width - cat.shape[1])))
        else:
            cat = jnp.concatenate(parts)
            segs[seg] = jnp.pad(cat, (0, width - cat.shape[0]))
    return segs


def leaves_of_segments(m: Decoder, segs: dict) -> dict:
    """Segment arrays -> named leaves (the inverse of ``to_segments``)."""
    slots, _ = layout(m)
    shapes = {n: s for n, s, _, _ in leaf_specs(m)}
    out = {}
    for s in slots:
        a = segs[s.segment]
        if s.segment.startswith("cycles"):
            out[s.name] = a[:, s.offset:s.offset + s.size].reshape(
                shapes[s.name])
        else:
            out[s.name] = a[s.offset:s.offset + s.size].reshape(
                shapes[s.name])
    return out


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
# Forward and loss
# ---------------------------------------------------------------------------


def _rmsnorm(x, gain_delta, eps):
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return ((1.0 + gain_delta.astype(jnp.float32)) * xf * r).astype(x.dtype)


def _rope(x, theta):
    """x: (B, S, H, hd); rotate the two halves of each head."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mm(a, b, precision):
    return jnp.matmul(a, b.astype(a.dtype), precision=precision)


def loss(m: Decoder, params: dict, tokens, labels, *, dtype=jnp.float32,
         precision=HIGHEST):
    """Mean next-token cross-entropy over every labelled position."""
    B, S = tokens.shape
    hd, H, G = m.head_dim, m.n_heads, m.n_kv_heads
    x = params["embed"].astype(dtype)[tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))
    for i in range(m.layers):
        lp = {k: params[k][i, 0] for k in ("attn_norm", "wq", "wk", "wv",
                                            "wo", "mlp_norm", "mlp_wg",
                                            "mlp_wu", "mlp_wo")}
        h = _rmsnorm(x, lp["attn_norm"], m.norm_eps)
        q = _mm(h, lp["wq"], precision).reshape(B, S, H, hd)
        k = _mm(h, lp["wk"], precision).reshape(B, S, G, hd)
        v = _mm(h, lp["wv"], precision).reshape(B, S, G, hd)
        q, k = _rope(q, m.rope_theta), _rope(k, m.rope_theta)
        q = q.reshape(B, S, G, H // G, hd)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k, precision=precision)
        s = s.astype(jnp.float32) * hd ** -0.5
        s = jnp.where(causal, s, _NEG)
        p = jax.nn.softmax(s, axis=-1).astype(dtype)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v, precision=precision)
        x = x + _mm(o.reshape(B, S, H * hd), lp["wo"], precision)
        h = _rmsnorm(x, lp["mlp_norm"], m.norm_eps)
        act = jax.nn.silu(_mm(h, lp["mlp_wg"], precision)) \
            * _mm(h, lp["mlp_wu"], precision)
        x = x + _mm(act, lp["mlp_wo"], precision)
    x = _rmsnorm(x, params["final_norm"], m.norm_eps)
    logits = _mm(x, params["head"], precision).astype(jnp.float32)
    cols = jnp.arange(m.vocab_padded) < m.vocab
    logits = jnp.where(cols, logits, _NEG)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    w = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - picked) * w) / jnp.maximum(jnp.sum(w), 1.0)


def loss_and_grad(m: Decoder, params: dict, tokens, labels, *,
                  dtype=jnp.float32, precision=HIGHEST):
    """Loss and gradient of ``loss`` over all rows, one row at a time (a
    scan whose carry sums the rows), so that the (S, S) scores of only one
    row are live. Every row has the same number of labelled positions, so
    the mean of the row means is the mean over all positions."""
    rows = (tokens[:, None], labels[:, None])
    return _loss_and_grad_fn(m, jnp.dtype(dtype), precision)(params, rows)


@functools.lru_cache(maxsize=None)
def _loss_and_grad_fn(m: Decoder, dtype, precision):
    vg = jax.checkpoint(jax.value_and_grad(
        lambda q, t, lab: loss(m, q, t, lab, dtype=dtype,
                               precision=precision)))

    def run(p, rows):
        def body(carry, xs):
            tot, acc = carry
            l_i, g_i = vg(p, *xs)
            return (tot + l_i, jax.tree_util.tree_map(jnp.add, acc, g_i)), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, p)
        (tot, acc), _ = jax.lax.scan(body, (jnp.float32(0.0), zeros), rows)
        n = rows[0].shape[0]
        return tot / n, jax.tree_util.tree_map(lambda g: g / n, acc)

    return jax.jit(run)
