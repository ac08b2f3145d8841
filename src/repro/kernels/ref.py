"""Pure-jnp oracles for the Count-Sketch Pallas kernels.

These are the ground truth the kernels are validated against (allclose over
shape/dtype sweeps + hypothesis-generated inputs). They implement the SAME
math as the kernels — multiply-shift hashing + signed bucket accumulation —
but with jnp scatter/gather instead of blocked one-hot MXU matmuls.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import count_sketch as cs

Array = jax.Array


def count_sketch_encode(cfg: cs.SketchConfig, g: Array,
                        offset: int = 0) -> Array:
    """(d,) -> (R, W) float32 sketch. Oracle for kernels.sketch_encode.

    ``offset`` hashes ``g[j]`` as coordinate ``offset + j`` (partial encode
    of a contiguous slice — oracle for the fused-interleave kernel path).
    """
    return cs.encode(cfg, g, offset=offset)


def count_sketch_decode(cfg: cs.SketchConfig, sketch: Array, d: int,
                        offset: int | Array = 0,
                        limit: int | None = None) -> Array:
    """(R, W) -> (d,) median-of-rows estimates. Oracle for kernels.sketch_decode.

    ``offset`` (an int or a traced scalar) estimates coordinates
    [offset, offset + d) — the gather-style partial decode matching the
    partial encode above. Coordinates at or past ``limit`` read 0.
    """
    if isinstance(offset, int) and not offset:
        est = cs.decode(cfg, sketch, d)
    else:
        est = cs.decode_at(cfg, sketch, jnp.arange(d) + offset)
    if limit is not None:
        est = jnp.where(jnp.arange(d) + offset < limit, est, 0.0)
    return est


def heavymix_recover(cfg: cs.SketchConfig, sketch: Array, k: int,
                     d: int) -> tuple[Array, Array]:
    """Greedy-fill HEAVYMIX selection (idx, est). Oracle for the fused
    Pallas decode+score recovery kernel (kernels.heavymix_topk)."""
    from repro.core import heavymix as hm
    return hm.heavymix(cfg, sketch, k, d, use_pallas=False)


def count_sketch_encode_onehot(cfg: cs.SketchConfig, g: Array) -> Array:
    """Encode via explicit one-hot matmul — the exact math the kernel runs.

    Kept separate from ``count_sketch_encode`` so tests can cross-check the
    scatter formulation against the matmul formulation independently of the
    Pallas machinery.
    """
    g = g.reshape(-1).astype(jnp.float32)
    d = g.shape[0]
    buckets, signs = cs.hash_buckets(cfg, jnp.arange(d))  # (R, d)
    onehot = jax.nn.one_hot(buckets, cfg.width, dtype=jnp.float32)  # (R, d, W)
    return jnp.einsum("d,rd,rdw->rw", g, signs, onehot)
