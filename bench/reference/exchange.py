"""Plain reference of one data-parallel training step's exchange and update.

The step it follows, for P workers each holding a slice of the global
batch:

- every worker p computes its gradient ``g_p`` (mean loss over its rows);
- ``none``: the applied gradient is the mean of the ``g_p``;
- ``gs-sgd`` (Alg. 1 and 2 of the paper, greedy fill): with error
  feedback ``u_p = e_p + g_p``, the Count-Sketch of ``U = sum_p u_p``
  (row r adds ``sign_r(i) * U[i]`` into bucket ``h_r(i)``) is decoded to
  ``est_i = median_r sign_r(i) * S[r, h_r(i)]``; the k coordinates of
  largest ``|est_i|`` are selected, their exact sum ``U[I]`` is applied
  (divided by P), and each worker keeps ``e_p' = u_p`` with ``I`` zeroed;
- AdamW (decoupled weight decay, bias-corrected moments) on the flat
  parameter vector.

The hash family is multiply-shift over 32-bit words, with parameters drawn
from the sketch seed as the traffic file's ``sketch`` section states it
(``hash_params``). Sketching the sum of the workers' vectors instead of
summing their sketches is the same linear map. Nothing here imports the
program.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Sketch:
    rows: int
    width: int       # a power of two
    k: int
    seed: int

    @property
    def log2_width(self) -> int:
        return self.width.bit_length() - 1


def hash_params(sk: Sketch) -> np.ndarray:
    """(rows, 4) uint32 words [a, b, c, d]; a and c odd."""
    rng = np.random.RandomState(np.uint32(sk.seed * 2654435761 % (2**31)))
    p = rng.randint(0, 2**31, size=(sk.rows, 4)).astype(np.uint64)
    p = (p * 2 + rng.randint(0, 2**31, size=(sk.rows, 4)).astype(np.uint64)
         ) % (2**32)
    p[:, 0] |= 1
    p[:, 2] |= 1
    return p.astype(np.uint32)


def _hash(sk: Sketch, idx):
    """bucket (rows, n) int32 and sign (rows, n) f32 of flat indices."""
    p = jnp.asarray(hash_params(sk))[:, :, None]
    i = idx.astype(jnp.uint32)[None]
    bucket = ((p[:, 0] * i + p[:, 1]) >> (32 - sk.log2_width)
              ).astype(jnp.int32)
    sign = 1.0 - 2.0 * ((p[:, 2] * i + p[:, 3]) >> 31).astype(jnp.float32)
    return bucket, sign


_BLOCK = 1 << 22


def _block(d: int) -> int:
    """Coordinates a block: 2^22, or the next power of two above d."""
    return min(_BLOCK, 1 << max(0, d - 1).bit_length())


def _blocks(d: int):
    n = _block(d)
    return [(a, min(d, a + n)) for a in range(0, d, n)]


def sketch_of(sk: Sketch, u) -> jax.Array:
    """(d,) -> (rows, width): scatter-add, one block of coordinates at a
    time."""
    acc = jnp.zeros((sk.rows, sk.width), jnp.float32)
    n = _block(u.shape[0])
    for a, b in _blocks(u.shape[0]):
        x = jnp.pad(u[a:b], (0, n - (b - a)))
        acc = _add_block(sk, n)(acc, x, jnp.uint32(a))
    return acc


@functools.lru_cache(maxsize=None)
def _add_block(sk: Sketch, n: int):
    def add(acc, x, lo):
        b, s = _hash(sk, lo + jnp.arange(n, dtype=jnp.uint32))
        return jax.vmap(lambda a, bb, ss: a.at[bb].add(ss * x))(acc, b, s)
    return jax.jit(add)


def estimates(sk: Sketch, S, d: int) -> jax.Array:
    """Median-of-rows estimate of every coordinate, (d,)."""
    n = _block(d)
    return jnp.concatenate([_estimate_block(sk, n)(S, jnp.uint32(a))[:b - a]
                            for a, b in _blocks(d)])


@functools.lru_cache(maxsize=None)
def _estimate_block(sk: Sketch, n: int):
    def est(S, lo):
        b, s = _hash(sk, lo + jnp.arange(n, dtype=jnp.uint32))
        return jnp.median(jnp.take_along_axis(S, b, axis=1) * s, axis=0)
    return jax.jit(est)


def gs_sgd(sk: Sketch, us: list) -> tuple[jax.Array, list]:
    """Applied gradient SUM and the new error feedback of every worker,
    from the workers' error-corrected vectors ``us``."""
    U = sum(us[1:], us[0])
    est = estimates(sk, sketch_of(sk, U), U.shape[0])
    _, idx = jax.lax.top_k(jnp.abs(est), sk.k)
    applied = jnp.zeros_like(U).at[idx].set(U[idx])
    return applied, [u.at[idx].set(0.0) for u in us]


@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float

    def init(self, p):
        return jnp.zeros_like(p), jnp.zeros_like(p)

    def apply(self, p, g, m, v, step: int):
        t = step + 1
        m = self.b1 * m + (1 - self.b1) * g
        v = self.b2 * v + (1 - self.b2) * g * g
        mhat = m / (1 - self.b1 ** t)
        vhat = v / (1 - self.b2 ** t)
        return (p - self.lr * (mhat / (jnp.sqrt(vhat) + self.eps)
                               + self.weight_decay * p), m, v)
