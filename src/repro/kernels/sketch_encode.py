"""Pallas TPU kernel: Count-Sketch encode as blocked signed one-hot matmuls.

GPU Count-Sketch encoders rely on atomic scatter-add; TPUs have neither
atomics nor fast data-dependent scatter. The TPU-native formulation (DESIGN.md
§3.1) observes that a sketch row is a matmul with an implicit signed one-hot
matrix:

    sketch[r] = g @ O_r,   O_r[i, h_r(i)] = sign_r(i), else 0.

We tile ``g`` into blocks of ``block_d`` elements and the ``W`` buckets into
blocks of ``block_w`` lanes. Grid = (W/block_w, d/block_d) with the element
axis innermost, so each output column-block stays resident in VMEM while the
gradient streams through. Per grid step the kernel

  1. recomputes bucket ids / signs for the element block with branch-free
     multiply-shift hashes (uint32 vector ALU),
  2. materializes the (block_d, block_w) signed one-hot tile,
  3. contracts (3, block_d) @ (block_d, block_w) on the MXU: the gradient
     block split into three bf16 parts (``split_bf16``),
  4. accumulates the parts' sum into the (R, block_w) output tile (f32).

The MXU rounds f32 operands to bf16 at default precision, which on a v5e
cost the sketch about 2e-3 of its relative accuracy. The one-hot tile is
exact in bf16 (0, +-1), and the three bf16 parts of ``g`` sum exactly to
``g``, so one bf16 matmul with three LHS rows and f32 accumulation gives
f32 products for the MXU price of one row.

VMEM per step ~= block_d * block_w * 4 B (one-hot tile) + R * block_w * 4 B
(accumulator) + block_d * 4 B (gradient block): 2.1 MB at the 1024x512
default. All matmul dims are multiples of 128 -> MXU-aligned.

``index_offset`` hashes element ``j`` of ``g`` as coordinate
``index_offset + j`` — a PARTIAL encode of a contiguous slice. Count-sketch
linearity makes the sum of partial sketches over disjoint slices equal the
full encode, which is how the fused backward-interleaved pipeline
(DESIGN.md §7) consumes gradient chunks incrementally instead of waiting
for a bucket's full range.

FLOP cost is 2*d*W*R MACs (the price of scatter-free encoding). Its share
of a training step on the chip is not measured.

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


def signed_onehot(hash_ref, r: int, idx: Array, col: Array,
                  shift: int) -> Array:
    """Row ``r``'s signed one-hot tile: ``sign_r(idx)`` where
    ``h_r(idx) == col``, else 0 (bf16, exact; the shape of ``idx``/``col``).

    Shared by the encode, decode and HEAVYMIX kernels; the hash is the
    multiply-shift of ``core.count_sketch.hash_buckets``."""
    a = hash_ref[r, 0]
    b = hash_ref[r, 1]
    c = hash_ref[r, 2]
    d_ = hash_ref[r, 3]
    bucket = (a * idx + b) >> jnp.uint32(shift)
    bit = ((c * idx + d_) >> jnp.uint32(31)).astype(jnp.int32)
    sign = 1.0 - 2.0 * bit.astype(jnp.float32)
    return jnp.where(bucket == col, sign, 0.0).astype(jnp.bfloat16)


def split_bf16(x: Array) -> Array:
    """``(1, n)`` f32 -> ``(3, n)`` bf16 rows that sum exactly to ``x``.

    Each part takes the next 8 significant bits of the remainder (bf16
    keeps f32's exponent range), so three parts hold all 24 bits of an
    f32 significand."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, lo], axis=0)


def _encode_kernel(hash_ref, g_ref, out_ref, *, rows: int, block_d: int,
                   block_w: int, shift: int, index_offset: int):
    j = pl.program_id(0)  # bucket-column block (outer)
    i = pl.program_id(1)  # element block (inner, accumulation axis)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # (block_d // 128, 128) lane-dense block -> (1, block_d) -> 3 bf16 parts
    g3 = split_bf16(g_ref[...].astype(jnp.float32).reshape(1, block_d))

    # Element index for every (element, bucket) cell; uniform across columns.
    idx = (jax.lax.broadcasted_iota(jnp.uint32, (block_d, block_w), 0)
           + jnp.uint32(index_offset + i * block_d))
    # Bucket id owned by each column of this tile.
    col = (jax.lax.broadcasted_iota(jnp.uint32, (block_d, block_w), 1)
           + jnp.uint32(j * block_w))

    for r in range(rows):  # R is small & static — unrolled
        onehot = signed_onehot(hash_ref, r, idx, col, shift)  # (B, BW)
        parts = jnp.dot(g3, onehot, preferred_element_type=jnp.float32)
        out_ref[r:r + 1, :] += jnp.sum(parts, axis=0, keepdims=True)


def lane_block(d: int, block_d: int) -> tuple[int, int]:
    """(block_d, d_pad) for a length-``d`` vector held as ``(d_pad // 128,
    128)``: the block shrinks to cover a short vector and stays a multiple
    of 128 lanes; ``d_pad`` is a whole number of blocks."""
    if block_d % 128:
        raise ValueError(f"block_d must be a multiple of 128, got {block_d}")
    block_d = min(block_d, -(-d // 128) * 128)
    return block_d, -(-d // block_d) * block_d


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "index_offset", "block_d", "block_w", "interpret"),
)
def sketch_encode(cfg: SketchConfig, g: Array, *, index_offset: int = 0,
                  block_d: int = 1024, block_w: int = 512,
                  interpret: bool | None = None) -> Array:
    """Count-Sketch encode ``g`` (any shape) -> (rows, width) f32 sketch.

    ``index_offset``: hash element j as coordinate index_offset + j
    (partial encode of a slice; see module docstring).
    ``interpret=None`` derives the mode from the backend via the
    ``kernels.dispatch`` policy table (compiled on TPU, interpreter
    elsewhere) — a direct caller bypassing ``kernels/ops.py`` gets the
    same dispatch the ops layer applies.
    """
    interpret = default_interpret(interpret)
    g = g.reshape(-1)
    d = g.shape[0]
    block_d, d_pad = lane_block(d, block_d)
    block_w = min(block_w, cfg.width)
    if d_pad != d:
        g = jnp.pad(g, (0, d_pad - d))  # zero elements contribute nothing
    n_d = d_pad // block_d
    # Pad the bucket axis up to a block_w multiple: bucket ids are < width,
    # so the padded columns never match and stay zero (sliced off below).
    # Without this, a width not divisible by block_w silently DROPPED the
    # tail column blocks (n_w = width // block_w rounded down).
    w_pad = cfg.width + ((-cfg.width) % block_w)
    n_w = w_pad // block_w
    hash_params = jnp.asarray(cfg.hash_params)  # (R, 4) uint32

    kernel = functools.partial(
        _encode_kernel, rows=cfg.rows, block_d=block_d, block_w=block_w,
        shift=32 - cfg.log2_width, index_offset=int(index_offset))

    out = pl.pallas_call(
        kernel,
        grid=(n_w, n_d),
        in_specs=[
            pl.BlockSpec((cfg.rows, 4), lambda j, i: (0, 0)),
            pl.BlockSpec((block_d // 128, 128), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((cfg.rows, block_w), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((cfg.rows, w_pad), jnp.float32),
        interpret=interpret,
    )(hash_params, g.reshape(d_pad // 128, 128))
    return out[:, :cfg.width] if w_pad != cfg.width else out


def sketch_encode_bucketed(cfgs, g: Array, sizes, *, block_d: int = 1024,
                           block_w: int = 512,
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
