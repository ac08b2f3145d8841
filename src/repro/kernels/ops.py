"""Public jit'd entry points for the Count-Sketch kernels.

Dispatch policy lives in ``kernels.dispatch.resolve_dispatch`` (one pure
function, one table — see its docstring): on TPU the Pallas kernels run
compiled; everywhere else the pure-jnp reference runs (fast on CPU), while
tests exercise the kernels in ``interpret=True`` mode explicitly to
validate the TPU code path. Direct kernel callers that bypass this module
get the same per-backend ``interpret`` default via
``dispatch.default_interpret`` — the two layers cannot disagree.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import count_sketch as cs
from repro.core.count_sketch import SketchConfig
from repro.kernels import ref
from repro.kernels.dispatch import resolve_dispatch
from repro.kernels.sketch_encode import sketch_encode as _pallas_encode
from repro.kernels.sketch_decode import sketch_decode as _pallas_decode

Array = jax.Array


def _resolve(use_pallas: bool | None,
             interpret: bool | None) -> tuple[bool, bool]:
    return resolve_dispatch(jax.default_backend(), use_pallas=use_pallas,
                            interpret=interpret)


def encode(cfg: SketchConfig, g: Array, *, offset: int = 0,
           use_pallas: bool | None = None,
           interpret: bool | None = None) -> Array:
    """Count-Sketch encode: any-shape ``g`` -> (rows, width) f32.

    ``offset`` hashes element j as coordinate offset + j — a partial encode
    of a contiguous slice (count-sketch linearity: partial sketches over a
    disjoint tiling sum to the full encode). The fused backward-interleaved
    pipeline encodes each bucket fragment this way as it emits.
    """
    pallas, interp = _resolve(use_pallas, interpret)
    if pallas:
        return _pallas_encode(cfg, g, index_offset=int(offset),
                              interpret=interp)
    return ref.count_sketch_encode(cfg, g.reshape(-1), offset=int(offset))


def decode(cfg: SketchConfig, sketch: Array, d: int, *,
           offset: int | Array = 0, limit: int | None = None,
           use_pallas: bool | None = None,
           interpret: bool | None = None) -> Array:
    """Count-Sketch decode: (rows, width) -> (d,) coordinate estimates.

    ``offset`` estimates coordinates [offset, offset + d) — the partial
    decode matching a partial encode; it may be traced (a scan's chunk
    base). ``limit``: coordinates at or past it read 0 (the Pallas kernel
    skips the blocks wholly past it)."""
    pallas, interp = _resolve(use_pallas, interpret)
    if pallas:
        return _pallas_decode(cfg, sketch, d, index_offset=offset,
                              limit=limit, interpret=interp)
    return ref.count_sketch_decode(cfg, sketch, d, offset=offset,
                                   limit=limit)


def heavymix_recover(cfg: SketchConfig, sketch: Array, k: int, d: int, *,
                     use_pallas: bool | None = None,
                     interpret: bool | None = None) -> tuple[Array, Array]:
    """HEAVYMIX greedy recovery from a summed sketch -> (idx (k,), est (k,)).

    Pallas path: fused decode+score kernel (``kernels.heavymix_topk``)
    followed by ``jax.lax.top_k`` over the score vector. Reference path:
    ``core.heavymix.heavymix`` (which self-selects its chunked hierarchical
    variant at very large d). Greedy fill only — the paper-faithful
    random-fill variant stays on the pure-jnp path (it needs a PRNG
    stream; see ``core.heavymix``).
    """
    pallas, interp = _resolve(use_pallas, interpret)
    if pallas:
        from repro.kernels.heavymix_topk import heavymix_scores
        thr = cs.l2sq_estimate(sketch.astype(jnp.float32)) / k
        scores, est = heavymix_scores(cfg, sketch, thr, int(d),
                                      interpret=interp)
        _, idx = jax.lax.top_k(scores, k)
        return idx, est[idx]
    return ref.heavymix_recover(cfg, sketch, k, d)


def encode_buckets(cfgs, g: Array, sizes, *, use_pallas: bool | None = None,
                   interpret: bool | None = None) -> tuple[Array, ...]:
    """Per-bucket encode with the same Pallas/ref dispatch as ``encode``.

    One (rows_i, width_i) sketch per contiguous bucket of ``g`` (sizes sum
    to g.size); bucket geometries may differ, so the result is a tuple.
    The Pallas path delegates to ``sketch_encode_bucketed`` (one kernel
    launch per bucket).

    Direct kernel-layer entry for benches/tests and TPU callers holding a
    whole flat vector; the train pipeline reaches the same kernels with
    the same per-bucket geometry via each bucket-compressor's ``encode``
    on its own slice (``compression.GsSGD.stage_encode``).
    """
    from repro.kernels.sketch_encode import sketch_encode_bucketed
    g = g.reshape(-1)
    sizes = tuple(int(s) for s in sizes)
    pallas, interp = _resolve(use_pallas, interpret)
    if pallas:
        return sketch_encode_bucketed(cfgs, g, sizes, interpret=interp)
    if sum(sizes) != g.shape[0]:
        raise ValueError(
            f"bucket sizes {sizes} must sum to the flat gradient "
            f"dimension {g.shape[0]}")
    out, off = [], 0
    for cfg, s in zip(cfgs, sizes):
        out.append(ref.count_sketch_encode(
            cfg, jax.lax.slice_in_dim(g, off, off + s)))
        off += s
    return tuple(out)


def decode_buckets(cfgs, sketches, sizes, *, use_pallas: bool | None = None,
                   interpret: bool | None = None) -> Array:
    """Per-bucket decode concatenated back into one flat estimate vector.

    Pallas path delegates to ``sketch_decode_bucketed``."""
    from repro.kernels.sketch_decode import sketch_decode_bucketed
    sizes = tuple(int(s) for s in sizes)
    pallas, interp = _resolve(use_pallas, interpret)
    if pallas:
        return sketch_decode_bucketed(cfgs, sketches, sizes,
                                      interpret=interp)
    return jnp.concatenate([ref.count_sketch_decode(cfg, sk, s)
                            for cfg, sk, s in zip(cfgs, sketches, sizes)])
