"""Pallas TPU kernel: TS-sketch encode (O(d*R), scatter- and matmul-free).

Grid over d/W coordinate blocks (block size == W). Per block and row r
(static unroll) with factorization m_r * n_r = d_pad, n_r <= W/2:

Within a W-aligned block starting at i0, ``i mod m_r`` never wraps
(m_r >= 2W), so the bucket sequence over the block is the arithmetic
progression (c + t*n_r) mod W with c = p_r(i0) mod W. Since n_r | W, the
bucket of offset t depends only on s = t mod (W/n_r); the block therefore
reduces with

  1. multiply-shift signs (uint32 VPU) and y = g_block * signs,
  2. group-sum: y.reshape(n_r, W/n_r).sum(0)      -> (W/n_r,) sums,
  3. strided placement: zeros(W/n_r, n_r)[:, 0] = sums, ravel,
  4. rotate by c (jnp.roll) and accumulate into the (R, W) VMEM tile.

Pure vector ops — no gather/scatter/matmul. VMEM ~ (R+3)*W*4 B. Compare
kernels/sketch_encode.py (exact hash): 3*d*W*R MXU MACs vs ~4*d*R VPU ops.

Oracle: repro.core.ts_sketch.encode (tests/test_ts_sketch.py sweeps,
interpret=True).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.ts_sketch import TSketchConfig
from repro.kernels.dispatch import default_interpret

Array = jax.Array


def _kernel(sign_ref, g_ref, out_ref, *, rows: int, width: int,
            bits: int, log_m: tuple[int, ...], offsets: tuple[int, ...]):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = g_ref[...].astype(jnp.float32)                    # (W,)
    i0 = jnp.uint32(i) * jnp.uint32(width)
    idx = jax.lax.iota(jnp.uint32, width) + i0

    for r in range(rows):                                  # static unroll
        cmul = sign_ref[r, 0]
        cadd = sign_ref[r, 1]
        bit = ((cmul * idx + cadd) >> jnp.uint32(31)).astype(jnp.int32)
        sign = 1.0 - 2.0 * bit.astype(jnp.float32)
        y = g * sign
        a = log_m[r]
        n_log = bits - a
        n = 1 << n_log
        # positions are (i + b_r) mod d_pad; b_r is a multiple of W so the
        # whole block shifts together: c = p((i0 + b_r) mod D) mod W
        i0b = (i0 + jnp.uint32(offsets[r])) & jnp.uint32((1 << bits) - 1)
        c = ((((i0b & jnp.uint32((1 << a) - 1)) << jnp.uint32(n_log))
              + (i0b >> jnp.uint32(a))) & jnp.uint32(width - 1))
        sums = y.reshape(n, width >> n_log).sum(axis=0)    # (W/n,)
        placed = jnp.zeros((width >> n_log, n), jnp.float32) \
            .at[:, 0].set(sums).reshape(width)
        out_ref[r, :] += jnp.roll(placed, c)


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def ts_encode(cfg: TSketchConfig, g: Array, *,
              interpret: bool | None = None) -> Array:
    """TS-sketch encode ``g`` -> (rows, width) f32.

    ``interpret=None`` derives the mode from the backend via the
    ``kernels.dispatch`` policy table (compiled on TPU, interpreter
    elsewhere)."""
    interpret = default_interpret(interpret)
    g = g.reshape(-1)
    gp = jnp.pad(g.astype(jnp.float32), (0, cfg.d_pad - g.shape[0]))
    n = cfg.d_pad // cfg.width
    bits = (cfg.d_pad - 1).bit_length()
    kernel = functools.partial(_kernel, rows=cfg.rows, width=cfg.width,
                               bits=bits, log_m=cfg.log_m,
                               offsets=cfg.offsets)
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((cfg.rows, 2), lambda i: (0, 0)),
            pl.BlockSpec((cfg.width,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((cfg.rows, cfg.width), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((cfg.rows, cfg.width), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(cfg.sign_params), gp)
