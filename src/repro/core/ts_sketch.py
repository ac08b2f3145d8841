"""TS-sketch: a TPU-native O(d·R) Count-Sketch variant (beyond-paper).

The exact multiply-shift Count-Sketch needs either scatter-add (no TPU
atomics, slow lowering) or the one-hot-matmul kernel (exact, but 3·d·W·R
MACs — the price quantified in EXPERIMENTS.md §Roofline). This variant
keeps the multiply-shift SIGN hash per coordinate but replaces the bucket
hash with a per-row *digit transpose*:

    p_r(i)      = (i mod m_r) * n_r + i div m_r      (m_r * n_r = d_pad,
                                                      both powers of two)
    bucket_r(i) = p_r(i) mod W

Encode row r is then sign-flip -> reshape(m_r, n_r).T -> reshape(-1, W)
.sum(0): elementwise ops, one real transpose, and a regular reduction —
no gather, no scatter, no matmul. Choosing n_r <= W/2 makes consecutive
coordinates land n_r buckets apart (never merged — the failure mode of a
naive shifted-window hash on weight-row-structured gradients), and
spreading m_r across rows de-correlates collision pairs between rows.

Estimates remain **unbiased** (collisions are sign-randomized; signs carry
the randomness) and the structure is linear/mergeable, so Alg. 1
aggregation and HEAVYMIX (via precomputed estimates) compose unchanged:
``compression.GsSGD(encoder="ts")``. What is traded away is the
pairwise-independent worst-case variance bound; measured estimator
quality vs the exact sketch is in tests/test_ts_sketch.py.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

_CHUNK = 1 << 20


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class TSketchConfig:
    """Static geometry. d must be known to fix the per-row factorizations."""

    d: int
    rows: int = 5
    width: int = 16384
    seed: int = 0

    def __post_init__(self):
        object.__setattr__(self, "width",
                           1 << max(1, (int(self.width) - 1).bit_length()))

    @property
    def log2_width(self) -> int:
        return int(self.width).bit_length() - 1

    @property
    def size(self) -> int:
        return self.rows * self.width

    @property
    def d_pad(self) -> int:
        """Power of two, >= d and >= 2W (so every row has m_r >= 2W)."""
        return max(1 << max(0, (int(self.d) - 1).bit_length()),
                   2 * self.width)

    @functools.cached_property
    def log_m(self) -> tuple[int, ...]:
        """Per-row log2(m_r), spread over [w+1, bits] (row 0 = identity)."""
        bits = (self.d_pad - 1).bit_length()
        lo = min(bits, self.log2_width + 1)
        if self.rows == 1:
            return (bits,)
        return tuple(bits - round(r * (bits - lo) / (self.rows - 1))
                     for r in range(self.rows))

    @functools.cached_property
    def offsets(self) -> tuple[int, ...]:
        """Per-row additive index offsets (multiples of W).

        All reshape-transpose bucket maps are bit-ROTATIONS, hence
        GF(2)-linear and strongly correlated across rows (a pair colliding
        in one row tends to collide in neighbors). Adding b_r before the
        rotation introduces carries — a non-GF(2)-linear mix that
        decorrelates the rows' collision pairs — and costs only a roll
        (ref) / one extra constant (kernel) because b_r is a multiple of W.
        """
        rng = np.random.RandomState(
            np.uint32((self.seed * 40503 + 7) % (2 ** 31)))
        nb = max(1, self.d_pad // self.width)
        return tuple(int(rng.randint(0, nb)) * self.width
                     for _ in range(self.rows))

    @functools.cached_property
    def sign_params(self) -> np.ndarray:
        rng = np.random.RandomState(
            np.uint32((self.seed * 2654435761 + 12345) % (2 ** 31)))
        p = rng.randint(0, 2 ** 31, size=(self.rows, 2)).astype(np.uint64)
        p = (p * 2 + rng.randint(0, 2 ** 31, (self.rows, 2)).astype(
            np.uint64)) % (2 ** 32)
        p[:, 0] |= 1
        return p.astype(np.uint32)


def signs_at(cfg: TSketchConfig, idx: Array) -> Array:
    """(R, *idx.shape) f32 in {-1, +1} — multiply-shift top bit."""
    p = jnp.asarray(cfg.sign_params)
    i = idx.astype(jnp.uint32)
    c = p[:, 0].reshape((-1,) + (1,) * i.ndim)
    dd = p[:, 1].reshape((-1,) + (1,) * i.ndim)
    return 1.0 - 2.0 * ((c * i + dd) >> jnp.uint32(31)).astype(jnp.float32)


def buckets_at(cfg: TSketchConfig, idx: Array) -> Array:
    """(R, *idx.shape) int32 in [0, W): ((i mod m)*n + i div m) mod W."""
    i = idx.astype(jnp.uint32)
    bits = (cfg.d_pad - 1).bit_length()
    wmask = jnp.uint32(cfg.width - 1)
    dmask = jnp.uint32(cfg.d_pad - 1)
    out = []
    for a, b in zip(cfg.log_m, cfg.offsets):
        n_log = bits - a
        ib = (i + jnp.uint32(b)) & dmask
        p = ((ib & jnp.uint32((1 << a) - 1)) << jnp.uint32(n_log)) \
            + (ib >> jnp.uint32(a))
        out.append((p & wmask).astype(jnp.int32))
    return jnp.stack(out)


def encode(cfg: TSketchConfig, g: Array) -> Array:
    """(d,) -> (R, W) f32 via transpose + reduction only (no scatter)."""
    g = g.reshape(-1).astype(jnp.float32)
    gp = jnp.pad(g, (0, cfg.d_pad - g.shape[0]))
    idx = jnp.arange(cfg.d_pad)
    s = signs_at(cfg, idx)
    bits = (cfg.d_pad - 1).bit_length()
    rows = []
    for r, a in enumerate(cfg.log_m):
        m, n = 1 << a, 1 << (bits - a)
        y = jnp.roll(gp * s[r], cfg.offsets[r])        # coord i -> i + b_r
        # coordinate j = b*m + a' lands at p = a'*n + b: reshape(n, m).T
        z = y.reshape(n, m).T.reshape(-1)              # digit transpose
        rows.append(z.reshape(-1, cfg.width).sum(axis=0))
    return jnp.stack(rows)


def decode(cfg: TSketchConfig, sketch: Array, d: int | None = None) -> Array:
    """(R, W) -> (d,) median-of-rows estimates (chunked over coords)."""
    d = d or cfg.d
    sk = sketch.astype(jnp.float32)

    def est_for(idx):
        b = buckets_at(cfg, idx)
        s = signs_at(cfg, idx)
        return jnp.median(jnp.take_along_axis(sk, b, axis=1) * s, axis=0)

    if d <= _CHUNK:
        return est_for(jnp.arange(d))
    pad = (-d) % _CHUNK

    def body(_, i):
        return None, est_for(jnp.arange(_CHUNK) + i * _CHUNK)

    _, chunks = jax.lax.scan(body, None, jnp.arange((d + pad) // _CHUNK))
    return chunks.reshape(-1)[:d]


def l2sq_estimate(sketch: Array) -> Array:
    return jnp.median(jnp.sum(sketch.astype(jnp.float32) ** 2, axis=1))
