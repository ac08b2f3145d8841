"""Pallas TPU kernel: fused HEAVYMIX decode + selection scoring.

The recover stage (``heavymix.heavymix`` greedy fill) is two streaming
passes over all d coordinates: decode the estimate of every coordinate,
then score it for the top-k selection

    est_i   = median_r sign_r(i) * S[r, h_r(i)]
    heavy_i = est_i^2 >= ||U||^2 / k            (the (alpha, l2)-heavy set)
    score_i = |est_i| + BIG * heavy_i           (heavy coords beat fillers)

This kernel fuses them: it reuses the decoder's signed one-hot gather
formulation (grid over (d/block_d, W/block_w), (R, block_d) VMEM scratch)
and on the last bucket block emits BOTH the median estimate and the
selection score — the (d,)-sized estimate is read once from VMEM instead
of round-tripping through HBM between decode and scoring. The heavy
threshold ||U||^2/k is data-dependent (it comes from the summed sketch),
so it enters as a (1, 1) tensor input rather than a static param — no
retrace per step.

The final k-selection itself stays OUTSIDE the kernel: ``jax.lax.top_k``
over the score vector is already tuned per backend, and a data-dependent
Pallas sort would buy nothing on the MXU. Greedy fill only (the practical
default the train path uses); the faithful random-fill variant needs a
PRNG stream and stays on the pure-jnp path.

Oracle: ``kernels.ref.heavymix_recover`` (== ``heavymix.heavymix``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.count_sketch import SketchConfig
from repro.kernels.dispatch import default_interpret
from repro.kernels.sketch_decode import gather_rows, median_rows
from repro.kernels.sketch_encode import lane_block

Array = jax.Array

_BIG = 1e30  # matches heavymix._BIG — the heavy-set priority boost


def _scores_kernel(hash_ref, sk_ref, thr_ref, score_ref, est_ref, acc_ref, *,
                   rows: int, block_d: int, block_w: int, shift: int,
                   n_w: int):
    gather_rows(hash_ref, sk_ref, acc_ref, rows=rows, block_d=block_d,
                block_w=block_w, shift=shift, index_offset=0)

    @pl.when(pl.program_id(1) == n_w - 1)
    def _finalize():
        est = median_rows([acc_ref[r:r + 1, :] for r in range(rows)])
        heavy = (est * est >= thr_ref[0, 0]).astype(jnp.float32)
        est_ref[...] = est.reshape(block_d // 128, 128)
        score_ref[...] = (jnp.abs(est) + _BIG * heavy).reshape(
            block_d // 128, 128)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "d", "block_d", "block_w", "interpret"),
)
def heavymix_scores(cfg: SketchConfig, sketch: Array, thresh: Array, d: int,
                    *, block_d: int = 1024, block_w: int = 512,
                    interpret: bool | None = None) -> tuple[Array, Array]:
    """(scores (d,), estimates (d,)) for HEAVYMIX greedy selection.

    ``thresh``: scalar ||U||^2 / k heavy threshold (traced — computed from
    the summed sketch by the caller, e.g. ``cs.l2sq_estimate(sk) / k``).
    ``jax.lax.top_k(scores, k)`` completes the recovery; see
    ``kernels.ops.heavymix_recover`` for the dispatched entry.
    """
    interpret = default_interpret(interpret)
    block_d, d_pad = lane_block(d, block_d)
    block_w = min(block_w, cfg.width)
    n_d = d_pad // block_d
    w_pad = cfg.width + ((-cfg.width) % block_w)  # same pad as sketch_decode
    n_w = w_pad // block_w
    sk = sketch.astype(jnp.float32)
    if w_pad != cfg.width:
        sk = jnp.pad(sk, ((0, 0), (0, w_pad - cfg.width)))
    hash_params = jnp.asarray(cfg.hash_params)
    thr = jnp.asarray(thresh, jnp.float32).reshape(1, 1)

    kernel = functools.partial(
        _scores_kernel, rows=cfg.rows, block_d=block_d, block_w=block_w,
        shift=32 - cfg.log2_width, n_w=n_w)

    scores, est = pl.pallas_call(
        kernel,
        grid=(n_d, n_w),
        in_specs=[
            pl.BlockSpec((cfg.rows, 4), lambda i, j: (0, 0)),
            pl.BlockSpec((cfg.rows, block_w), lambda i, j: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_d // 128, 128), lambda i, j: (i, 0)),
            pl.BlockSpec((block_d // 128, 128), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d_pad // 128, 128), jnp.float32),
            jax.ShapeDtypeStruct((d_pad // 128, 128), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((cfg.rows, block_d), jnp.float32)],
        interpret=interpret,
    )(hash_params, sk, thr)
    return scores.reshape(-1)[:d], est.reshape(-1)[:d]
