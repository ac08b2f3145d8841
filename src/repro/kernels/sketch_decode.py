"""Pallas TPU kernel: Count-Sketch decode (query all coordinates).

Decode is the transpose of encode: the estimate matrix row is

    est[r, i] = sign_r(i) * sketch[r, h_r(i)]

i.e. a gather — again scatter/gather-hostile on TPU. We use the same signed
one-hot tile as the encoder and contract against the sketch row instead:

    est[r, iblk] = O_r[iblk, :] @ sketch[r, :]      (block_d, W) @ (W,)

Grid = (d/block_d, W/block_w) with the bucket axis innermost: a (R, block_d)
f32 VMEM scratch accumulates partial gathers over bucket blocks (each
coordinate's bucket lands in exactly one block, so "accumulate" = select),
and on the last bucket block the kernel reduces rows to the median estimate.
Median-of-R for small static R is a compare-exchange network of
``jnp.minimum``/``jnp.maximum`` over the rows (Mosaic lowers no sort).

``index_offset`` estimates coordinates [index_offset, index_offset + d) —
the gather-style partial decode matching ``sketch_encode``'s partial
encode (a bucket-local range of the fused interleaved pipeline).

VMEM per step ~= block_d*block_w*4 (one-hot) + R*(block_w + block_d)*4:
2.1 MB at defaults. Matmul dims MXU-aligned as in the encoder. The gather
contracts the sketch row against the tile's bucket axis (``q @ k.T`` form),
so each row's estimates come out lane-dense as ``(1, block_d)``, and the
output is held as ``(d_pad // 128, 128)`` like the encoder's input. The
sketch row enters as three bf16 parts (``sketch_encode.split_bf16``), so
the gather is exact, as in the encoder.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.count_sketch import SketchConfig
from repro.kernels.dispatch import default_interpret
from repro.kernels.sketch_encode import (lane_block, signed_onehot,
                                          split_bf16)

Array = jax.Array


def median_rows(vals: list) -> Array:
    """Per-coordinate median of R same-shape arrays: an odd-even
    transposition network of min/max (R is small and static), then the
    middle value, or the mean of the middle two for even R."""
    v = list(vals)
    n = len(v)
    for rnd in range(n):
        for k in range(rnd % 2, n - 1, 2):
            v[k], v[k + 1] = (jnp.minimum(v[k], v[k + 1]),
                              jnp.maximum(v[k], v[k + 1]))
    if n % 2 == 1:
        return v[n // 2]
    return 0.5 * (v[n // 2 - 1] + v[n // 2])


def gather_rows(hash_ref, sk_ref, acc_ref, *, rows: int, block_d: int,
                block_w: int, shift: int, index_offset):
    """Accumulate bucket block ``j``'s signed gathers of coordinate block
    ``i`` into the (R, block_d) scratch (zeroed on the first bucket block).
    Shared by the decode and HEAVYMIX kernels."""
    i = pl.program_id(0)  # coordinate block (outer)
    j = pl.program_id(1)  # bucket block (inner, accumulation axis)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = (jax.lax.broadcasted_iota(jnp.uint32, (block_d, block_w), 0)
           + jnp.uint32(index_offset + i * block_d))
    col = (jax.lax.broadcasted_iota(jnp.uint32, (block_d, block_w), 1)
           + jnp.uint32(j * block_w))

    for r in range(rows):  # R is small & static — unrolled
        onehot = signed_onehot(hash_ref, r, idx, col, shift)  # (B, BW)
        row3 = split_bf16(sk_ref[r:r + 1, :].astype(jnp.float32))  # (3, BW)
        parts = jax.lax.dot_general(
            row3, onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (3, B)
        acc_ref[r:r + 1, :] += jnp.sum(parts, axis=0, keepdims=True)


def _decode_kernel(hash_ref, sk_ref, out_ref, acc_ref, *, rows: int,
                   block_d: int, block_w: int, shift: int, n_w: int,
                   index_offset: int):
    gather_rows(hash_ref, sk_ref, acc_ref, rows=rows, block_d=block_d,
                block_w=block_w, shift=shift, index_offset=index_offset)

    @pl.when(pl.program_id(1) == n_w - 1)
    def _finalize():
        est = median_rows([acc_ref[r:r + 1, :] for r in range(rows)])
        out_ref[...] = est.reshape(block_d // 128, 128)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "d", "index_offset", "block_d", "block_w",
                     "interpret"),
)
def sketch_decode(cfg: SketchConfig, sketch: Array, d: int, *,
                  index_offset: int = 0, block_d: int = 1024,
                  block_w: int = 512,
                  interpret: bool | None = None) -> Array:
    """Estimate ``d`` coordinates from an (R, W) sketch -> (d,) f32.

    ``index_offset``: estimate coordinates [index_offset, index_offset+d)
    (partial decode). ``interpret=None`` derives the mode from the backend
    via the ``kernels.dispatch`` policy table (compiled on TPU,
    interpreter elsewhere).
    """
    interpret = default_interpret(interpret)
    block_d, d_pad = lane_block(d, block_d)
    block_w = min(block_w, cfg.width)
    n_d = d_pad // block_d
    # Pad the bucket axis to a block_w multiple with zero sketch columns:
    # bucket ids are < width so the padded columns are never selected.
    # Without this, a width not divisible by block_w silently dropped the
    # tail column blocks from every coordinate's gather.
    w_pad = cfg.width + ((-cfg.width) % block_w)
    n_w = w_pad // block_w
    sk = sketch.astype(jnp.float32)
    if w_pad != cfg.width:
        sk = jnp.pad(sk, ((0, 0), (0, w_pad - cfg.width)))
    hash_params = jnp.asarray(cfg.hash_params)

    kernel = functools.partial(
        _decode_kernel, rows=cfg.rows, block_d=block_d, block_w=block_w,
        shift=32 - cfg.log2_width, n_w=n_w, index_offset=int(index_offset))

    out = pl.pallas_call(
        kernel,
        grid=(n_d, n_w),
        in_specs=[
            pl.BlockSpec((cfg.rows, 4), lambda i, j: (0, 0)),
            pl.BlockSpec((cfg.rows, block_w), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_d // 128, 128), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((d_pad // 128, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((cfg.rows, block_d), jnp.float32)],
        interpret=interpret,
    )(hash_params, sk)
    return out.reshape(-1)[:d]


def sketch_decode_bucketed(cfgs, sketches, sizes, *, block_d: int = 1024,
                           block_w: int = 512,
                           interpret: bool | None = None) -> Array:
    """Per-bucket decode back to one flat estimate vector.

    Inverse companion of ``sketch_encode_bucketed``: bucket i's coordinates
    are estimated from bucket i's sketch with bucket i's geometry, then
    concatenated in bucket order — coordinate layout matches the flat
    vector the encoder split.
    """
    parts = [sketch_decode(cfg, sk, int(s), block_d=block_d,
                           block_w=block_w, interpret=interpret)
             for cfg, sk, s in zip(cfgs, sketches, sizes)]
    return jnp.concatenate(parts)
