"""Pallas TPU kernel: Count-Sketch decode as a factored one-hot matmul.

Decode is the transpose of encode: row ``r``'s estimate of coordinate ``i``
is ``sign_r(i) * sketch[r, h_r(i)]``, a data-dependent gather, which TPUs
do slowly. The kernel factors the gather as ``sketch_encode`` factors the
scatter (DESIGN.md §3.1). With ``hi = h >> 7`` and ``lo = h & 127``, view
the sketch row as ``S_r[hi, lo]``; then

    sketch[r, h_r(i)] = sum_lo [h_lo(i) = lo] * (S_r^T @ on_hi)[lo, i],
    on_hi[hi, i] = [h_hi(i) = hi],

an MXU contraction over the hi axis followed by a select over the 128 lo
sublanes on the vector unit. Per call the wrapper transposes each sketch
row to (128 lo, h_pad hi) and splits it into three bf16 parts
(``split_bf16``), stacked on the lo axis: an (R, 3 * 128, h_pad) operand,
0.5 MB at W = 16384, resident in VMEM for the whole grid. Per block of
``block_d`` coordinates and row the kernel

  1. hashes the block once, on a lane-dense ``(1, block_d)`` row (the
     multiply-shift of ``core.count_sketch.hash_buckets``, bit for bit);
  2. builds ``on_hi`` (block_h, block_d) in bf16 by comparing a sublane
     iota against the broadcast high bits;
  3. contracts ``parts_r @ on_hi`` on the MXU, (3 * 128, block_h) @
     (block_h, block_d) with f32 accumulation, and adds the three part
     slabs: ``S_r[hi_i, lo]`` for every lo and coordinate, exact in f32
     (each column of ``on_hi`` holds one 1, and the parts sum exactly to
     the value);
  4. picks ``lo_i`` on the vector unit, a sublane select-and-add down to
     a ``(1, block_d)`` row, and applies the sign.

On the last pass the R rows reduce to the median with a compare-exchange
network of min/max (``median_rows``; Mosaic lowers no sort), written as a
lane-dense ``(block_d // 128, 128)`` block. The estimates are bit for bit
those of the gather.

Cost per coordinate and row: O(1) hashing, ``block_h`` one-hot entries and
a 3 * 128-row select-and-add on the vector unit, and 3 * W MACs on the MXU:
3 * d * W * R in all, the kernel's floor.

Geometry follows the width: ``W / 128`` hi rows, padded to whole 16-row
tiles (one row for W < 128, whose lanes past W never match). A pass
contracts over ``block_w`` buckets, rounded up to whole 128-row hi blocks
(the lanes of the parts block); a wider sketch takes several passes,
innermost in the grid, adding into an (R, block_d) f32 VMEM accumulator,
and each coordinate's hi row lies in exactly one pass. VMEM per step at the 2048-coordinate, 128-row default:
about 3 MB for the contraction, 1 MB for the select, 1 MB for the parts
(double-buffered).

``index_offset`` estimates coordinates [index_offset, index_offset + d),
the partial decode matching ``sketch_encode``'s partial encode. It may be
traced (a scan's chunk base): it enters as a scalar-prefetch operand, so
one compiled kernel serves every offset. ``limit`` (static) bounds the
coordinates to estimate: those at or past it read 0, and a block wholly
past it skips the hashing and the MXU work.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.count_sketch import SketchConfig
from repro.kernels.dispatch import default_interpret
from repro.kernels.sketch_encode import (LANES, LO_BITS, hash_row, hi_block,
                                          lane_block, signed_onehot,
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
    ``i`` into the (R, block_d) scratch (zeroed on the first bucket block),
    against a (block_d, block_w) signed one-hot tile. Used by the HEAVYMIX
    scores kernel."""
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


def _decode_kernel(base_ref, hash_ref, parts_ref, out_ref, acc_ref, *,
                   rows: int, block_d: int, block_h: int, shift: int,
                   n_pass: int, limit: int | None):
    i = pl.program_id(0)  # coordinate block (outer)
    j = pl.program_id(1)  # pass over hi rows (inner, accumulation axis)
    start = base_ref[0] + i * block_d  # first coordinate of the block

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def estimate():
        idx = (jax.lax.broadcasted_iota(jnp.uint32, (1, block_d), 1)
               + start.astype(jnp.uint32))
        hi_ids = (jax.lax.broadcasted_iota(jnp.int32, (block_h, block_d), 0)
                  + j * block_h)
        lo_ids = jax.lax.broadcasted_iota(jnp.int32, (LANES, block_d), 0)
        for r in range(rows):  # R is small & static — unrolled
            bucket, sign = hash_row(hash_ref, r, idx, shift)
            bucket = bucket.astype(jnp.int32)  # < 2^31: shift >= 1
            on_hi = jnp.where((bucket >> LO_BITS) == hi_ids, 1.0,
                              0.0).astype(jnp.bfloat16)  # (block_h, block_d)
            t = jnp.dot(parts_ref[r], on_hi,
                        preferred_element_type=jnp.float32)  # (3*LANES, B)
            s = t[:LANES] + t[LANES:2 * LANES] + t[2 * LANES:]  # exact
            pick = jnp.sum(
                jnp.where((bucket & (LANES - 1)) == lo_ids, s, 0.0),
                axis=0, keepdims=True)  # (1, block_d)
            acc_ref[r:r + 1, :] += sign * pick

    if limit is None:
        estimate()
    else:
        pl.when(start < limit)(estimate)

    @pl.when(j == n_pass - 1)
    def _finalize():
        est = median_rows([acc_ref[r:r + 1, :] for r in range(rows)])
        if limit is not None:
            pos = (jax.lax.broadcasted_iota(jnp.int32, (1, block_d), 1)
                   + start)
            est = jnp.where(pos < limit, est, 0.0)
        out_ref[...] = est.reshape(block_d // 128, 128)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "d", "limit", "block_d", "block_w", "interpret"),
)
def sketch_decode(cfg: SketchConfig, sketch: Array, d: int, *,
                  index_offset: int | Array = 0, limit: int | None = None,
                  block_d: int = 2048, block_w: int = 16384,
                  interpret: bool | None = None) -> Array:
    """Estimate ``d`` coordinates from an (R, W) sketch -> (d,) f32.

    ``index_offset``: estimate coordinates [index_offset, index_offset+d)
    (partial decode; an int or a traced int32 scalar). ``limit``: the
    coordinates at or past it are not estimated and read 0.
    ``block_w``: the most buckets one pass contracts over, in whole
    128-row hi blocks (``sketch_encode.hi_block``).
    ``interpret=None`` derives the mode from the backend via the
    ``kernels.dispatch`` policy table (compiled on TPU, interpreter
    elsewhere).
    """
    interpret = default_interpret(interpret)
    rows = cfg.rows
    block_d, d_pad = lane_block(d, block_d)
    # A pass's hi rows are the lanes of its parts block: whole 128-row
    # blocks where a sketch takes more than one pass.
    block_h, h_pad = hi_block(cfg.width, -(-block_w // LANES**2) * LANES**2)
    n_pass = h_pad // block_h
    # Zero buckets past the width are never matched: bucket ids < width.
    sk = jnp.pad(sketch.astype(jnp.float32),
                 ((0, 0), (0, h_pad * LANES - cfg.width)))
    # bucket = hi * 128 + lo: (R, hi, lo) row-major, then lo on sublanes
    sk_t = sk.reshape(rows, h_pad, LANES).transpose(0, 2, 1)
    parts = split_bf16(sk_t, axis=1)  # (R, 3 * LANES, h_pad) bf16
    base = jnp.asarray(index_offset, jnp.int32).reshape(1)
    hash_params = jnp.asarray(cfg.hash_params)  # (R, 4) uint32

    kernel = functools.partial(
        _decode_kernel, rows=rows, block_d=block_d, block_h=block_h,
        shift=32 - cfg.log2_width, n_pass=n_pass,
        limit=None if limit is None else int(limit))

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(d_pad // block_d, n_pass),
            in_specs=[
                pl.BlockSpec((rows, 4), lambda i, j, b: (0, 0)),
                pl.BlockSpec((rows, 3 * LANES, block_h),
                             lambda i, j, b: (0, 0, j)),
            ],
            out_specs=pl.BlockSpec((block_d // 128, 128),
                                   lambda i, j, b: (i, 0)),
            scratch_shapes=[pltpu.VMEM((rows, block_d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((d_pad // 128, 128), jnp.float32),
        interpret=interpret,
    )(base, hash_params, parts)
    return out.reshape(-1)[:d]


def sketch_decode_bucketed(cfgs, sketches, sizes, *, block_d: int = 2048,
                           block_w: int = 16384,
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
