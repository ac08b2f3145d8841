"""Pallas TPU kernel: Count-Sketch encode as a factored one-hot matmul.

GPU Count-Sketch encoders rely on atomic scatter-add; TPUs have neither
atomics nor fast data-dependent scatter. A sketch row is a matmul with an
implicit signed one-hot matrix, ``sketch[r] = g @ O_r`` with
``O_r[i, h_r(i)] = sign_r(i)``, but contracting ``g`` against ``O_r``
directly costs d*W compare-and-selects per row and feeds the MXU one row.
The kernel factors the one-hot instead (DESIGN.md §3.1). Split each bucket
id into ``hi = h >> 7`` and ``lo = h & 127``; then

    sketch[r][hi, lo] = sum_i s_r(i) g_i [h_hi(i) = hi] [h_lo(i) = lo]
                      = (A_r^T B_r)[hi, lo],

with ``A_r`` a (d x W/128) one-hot carrying the signed values and ``B_r`` a
(d x 128) 0/1 one-hot: an ordinary matmul over the element axis with
128-row operands on both sides. The (R, W/128, 128) f32 result reshapes
row-major to the (R, W) sketch, since ``h = hi * 128 + lo``.

Grid = (hi blocks, element blocks), the element axis innermost, so the
(R, block_h, 128) output block stays resident in VMEM while ``g`` streams
through once per hi block (once in all for W <= ``block_w``). Per grid step
and row the kernel

  1. hashes the element block once, on a lane-dense ``(1, block_d)`` row
     (the multiply-shift of ``core.count_sketch.hash_buckets``, bit for
     bit), and folds the sign into the values: ``v = s_r(i) g_i``;
  2. splits ``v`` into three bf16 parts (``split_bf16``) and builds
     ``A^T`` (3 * block_h, block_d), part k at row ``k * block_h + hi_i``,
     and ``B^T`` (128, block_d) = ``[lo_i == l]``, both by comparing a
     sublane iota against the broadcast row;
  3. contracts ``A^T B`` over the element axis on the MXU (the ``q @ k.T``
     form), f32 accumulation, and adds the three part slabs into the
     output block.

The MXU rounds f32 operands to bf16 at default precision, which on a v5e
cost the sketch about 2e-3 of its relative accuracy. The one-hots are
exact in bf16 and the three parts sum exactly to ``v``, so the products
are f32's.

Cost per element and row: O(1) hashing, about 5 * 128 one-hot entries
built on the vector unit, and 3 * W MACs at full MXU height (3 * d * W * R
in all): that, not HBM traffic, is the kernel's floor.

Geometry follows the width: ``W_lo`` = 128 lanes and ``W_hi`` = W / 128
rows, padded to whole 16-row tiles (a packed bf16 tile); for W < 128 one
row, whose lanes past W never match. ``block_w`` caps the buckets one pass
holds (``block_w // 128`` rows, in whole tiles); a wider sketch takes more
passes over ``g``, which keeps the ``A^T`` tile and the output block within
VMEM. VMEM per step at the 128-row, 2048-element default: about 3 MB of
``A^T`` (f32 then bf16), 1.5 MB of ``B^T``, 1 MB of mask, 0.3 MB of output.

``index_offset`` hashes element ``j`` of ``g`` as coordinate
``index_offset + j`` — a PARTIAL encode of a contiguous slice. Count-sketch
linearity makes the sum of partial sketches over disjoint slices equal the
full encode, which is how the fused backward-interleaved pipeline
(DESIGN.md §7) consumes gradient chunks incrementally instead of waiting
for a bucket's full range.

Mosaic (the TPU compiler) constraints the code follows: ``g`` arrives as a
lane-dense ``(d_pad // 128, 128)`` array, so every block — including the
``(P, ...)`` block a ``vmap`` over workers adds — has (8, 128)-aligned
trailing dims; the sign bit is cast through int32 (no uint32 -> f32 cast);
and each row's contribution is added into the output ref in place (no
value-level scatter-add).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.count_sketch import SketchConfig
from repro.kernels.dispatch import default_interpret

Array = jax.Array


def hash_row(hash_ref, r: int, idx: Array, shift: int) -> tuple[Array, Array]:
    """Row ``r``'s (bucket uint32, sign f32) of the coordinates ``idx``:
    the multiply-shift of ``core.count_sketch.hash_buckets``, bit for bit."""
    a = hash_ref[r, 0]
    b = hash_ref[r, 1]
    c = hash_ref[r, 2]
    d_ = hash_ref[r, 3]
    bucket = (a * idx + b) >> jnp.uint32(shift)
    bit = ((c * idx + d_) >> jnp.uint32(31)).astype(jnp.int32)
    return bucket, 1.0 - 2.0 * bit.astype(jnp.float32)


def signed_onehot(hash_ref, r: int, idx: Array, col: Array,
                  shift: int) -> Array:
    """Row ``r``'s signed one-hot tile: ``sign_r(idx)`` where
    ``h_r(idx) == col``, else 0 (bf16, exact; the shape of ``idx``/``col``).

    Shared by the decode and HEAVYMIX kernels."""
    bucket, sign = hash_row(hash_ref, r, idx, shift)
    return jnp.where(bucket == col, sign, 0.0).astype(jnp.bfloat16)


def split_bf16(x: Array, axis: int = 0) -> Array:
    """f32 -> three bf16 parts that sum exactly to ``x``, stacked along
    ``axis``: ``(1, n)`` -> ``(3, n)`` rows by default.

    Each part takes the next 8 significant bits of the remainder (bf16
    keeps f32's exponent range), so three parts hold all 24 bits of an
    f32 significand."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, lo], axis=axis)


LANES = 128   # W_lo: the buckets of one hi row
HI_TILE = 16  # hi rows come in whole packed-bf16 sublane tiles
LO_BITS = LANES.bit_length() - 1


def _encode_kernel(hash_ref, g_ref, out_ref, *, rows: int, block_d: int,
                   block_h: int, shift: int, index_offset: int):
    j = pl.program_id(0)  # block of hi rows (outer)
    i = pl.program_id(1)  # element block (inner, accumulation axis)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # (block_d // 128, 128) lane-dense block -> one (1, block_d) row
    g = g_ref[...].astype(jnp.float32).reshape(1, block_d)
    idx = (jax.lax.broadcasted_iota(jnp.uint32, (1, block_d), 1)
           + jnp.uint32(index_offset + i * block_d))
    hi_ids = (jax.lax.broadcasted_iota(jnp.int32, (block_h, block_d), 0)
              + j * block_h)
    lo_ids = jax.lax.broadcasted_iota(jnp.int32, (LANES, block_d), 0)

    for r in range(rows):  # R is small & static — unrolled
        bucket, sign = hash_row(hash_ref, r, idx, shift)
        bucket = bucket.astype(jnp.int32)  # < 2^31: shift >= 1
        parts = split_bf16(sign * g).astype(jnp.float32)  # (3, block_d)
        on_hi = (bucket >> LO_BITS) == hi_ids  # (block_h, block_d)
        a_t = jnp.concatenate(
            [jnp.where(on_hi, parts[k:k + 1], 0.0) for k in range(3)],
            axis=0).astype(jnp.bfloat16)  # (3 * block_h, block_d)
        b_t = jnp.where((bucket & (LANES - 1)) == lo_ids, 1.0,
                        0.0).astype(jnp.bfloat16)  # (LANES, block_d)
        prod = jax.lax.dot_general(
            a_t, b_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (3 * block_h, LANES)
        out_ref[r] += (prod[:block_h] + prod[block_h:2 * block_h]
                       + prod[2 * block_h:])


def lane_block(d: int, block_d: int) -> tuple[int, int]:
    """(block_d, d_pad) for a length-``d`` vector held as ``(d_pad // 128,
    128)``: the block shrinks to cover a short vector and stays a multiple
    of 128 lanes; ``d_pad`` is a whole number of blocks."""
    if block_d % 128:
        raise ValueError(f"block_d must be a multiple of 128, got {block_d}")
    block_d = min(block_d, -(-d // 128) * 128)
    return block_d, -(-d // block_d) * block_d


def hi_block(width: int, block_w: int) -> tuple[int, int]:
    """(block_h, h_pad): hi rows per pass and in all for a ``width``-bucket
    sketch. A pass holds at most ``block_w`` buckets, in whole
    ``HI_TILE``-row tiles and at least one; ``h_pad`` is a whole number
    of passes."""
    n_hi = -(-width // LANES)
    cap = max(HI_TILE, block_w // (HI_TILE * LANES) * HI_TILE)
    block_h = min(cap, -(-n_hi // HI_TILE) * HI_TILE)
    return block_h, -(-n_hi // block_h) * block_h


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "index_offset", "block_d", "block_w", "interpret"),
)
def sketch_encode(cfg: SketchConfig, g: Array, *, index_offset: int = 0,
                  block_d: int = 2048, block_w: int = 16384,
                  interpret: bool | None = None) -> Array:
    """Count-Sketch encode ``g`` (any shape) -> (rows, width) f32 sketch.

    ``index_offset``: hash element j as coordinate index_offset + j
    (partial encode of a slice; see module docstring).
    ``block_w``: the most buckets one pass over ``g`` holds (``hi_block``).
    ``interpret=None`` derives the mode from the backend via the
    ``kernels.dispatch`` policy table (compiled on TPU, interpreter
    elsewhere) — a direct caller bypassing ``kernels/ops.py`` gets the
    same dispatch the ops layer applies.
    """
    interpret = default_interpret(interpret)
    g = g.reshape(-1)
    d = g.shape[0]
    block_d, d_pad = lane_block(d, block_d)
    if d_pad != d:
        g = jnp.pad(g, (0, d_pad - d))  # zero elements contribute nothing
    block_h, h_pad = hi_block(cfg.width, block_w)
    hash_params = jnp.asarray(cfg.hash_params)  # (R, 4) uint32

    kernel = functools.partial(
        _encode_kernel, rows=cfg.rows, block_d=block_d, block_h=block_h,
        shift=32 - cfg.log2_width, index_offset=int(index_offset))

    # Hi rows past the width (padding) never match a bucket and stay zero.
    out = pl.pallas_call(
        kernel,
        grid=(h_pad // block_h, d_pad // block_d),
        in_specs=[
            pl.BlockSpec((cfg.rows, 4), lambda j, i: (0, 0)),
            pl.BlockSpec((block_d // 128, 128), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((cfg.rows, block_h, LANES),
                               lambda j, i: (0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((cfg.rows, h_pad, LANES), jnp.float32),
        interpret=interpret,
    )(hash_params, g.reshape(d_pad // 128, 128))
    # bucket = hi * 128 + lo: row-major (hi, lo) is the bucket axis
    return out.reshape(cfg.rows, h_pad * LANES)[:, :cfg.width]


def sketch_encode_bucketed(cfgs, g: Array, sizes, *, block_d: int = 2048,
                           block_w: int = 16384,
                           interpret: bool | None = None) -> tuple[Array, ...]:
    """Per-bucket encode of a flat vector (bucketed pipeline, DESIGN.md §5).

    ``cfgs``/``sizes``: one SketchConfig + length per contiguous bucket
    (sizes sum to g.size). One kernel launch per bucket — each launch keeps
    its own MXU-aligned grid for its own (rows, width) geometry, and the
    launches have no data dependence on each other, so the TPU scheduler
    may overlap bucket i's DMA-out with bucket i+1's encode. Widths differ
    per bucket, hence a tuple of (rows_i, width_i) sketches, not a stack.
    """
    g = g.reshape(-1)
    if sum(int(s) for s in sizes) != g.shape[0]:
        raise ValueError(
            f"bucket sizes {tuple(sizes)} must sum to the flat gradient "
            f"dimension {g.shape[0]}")
    out, off = [], 0
    for cfg, s in zip(cfgs, sizes):
        out.append(sketch_encode(cfg, jax.lax.slice_in_dim(g, off, off + s),
                                 block_d=block_d, block_w=block_w,
                                 interpret=interpret))
        off += s
    return tuple(out)
