"""HEAVYMIX (paper Algorithm 2): recover Top-k coordinates from a summed sketch.

Given the merged sketch ``S = sum_p S(u_p)`` of the (error-corrected) global
gradient ``U = sum_p u_p``:

  1. query the estimate ``ĝ_i`` of every coordinate (|ĝ_i - U_i| <= eps*||U||),
  2. the heavy set  H = { i : ĝ_i^2 >= ||U||^2 / k },
  3. Top_k = H ∪ rand_l(NH) with l = k - |H|  (random fill from the non-heavy
     set, paper-faithful), or greedy fill by next-largest estimate (practical
     default — strictly dominates random fill and is what the exact second
     round makes cheap),
  4. a second round of communication fetches the exact values of Top_k
     (implemented in ``compression.py`` as gather + psum of k scalars).

Every worker holds the identical summed sketch and identical PRNG key, so all
workers select the same indices — no index exchange is needed (in contrast
with Top-k methods, which must ship coordinates alongside values).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import count_sketch as cs
from repro.kernels import ops as kops

Array = jax.Array

_BIG = 1e30  # priority boost guaranteeing heavy coords beat all fillers


_CHUNK = 1 << 22  # coords per selection chunk (hierarchical top-k)


def heavymix(cfg: cs.SketchConfig, sketch: Array, k: int, d: int, *,
             key: Array | None = None, faithful: bool = False,
             estimates: Array | None = None,
             use_pallas: bool | None = None) -> tuple[Array, Array]:
    """Select k indices from a summed sketch. Returns (idx (k,), est (k,)).

    faithful=True pads the heavy set with uniformly random non-heavy
    coordinates exactly as Alg. 2; the default pads with the next-largest
    estimates instead. If ``estimates`` is given (precomputed, e.g. by the
    'ts' sketch's own decode) the internal decode is skipped. The
    estimates come from ``kernels.ops.decode``, whose dispatch
    (``use_pallas``, ``kernels.dispatch``) runs the Pallas decode kernel on
    TPU and the jnp gather elsewhere; the two agree bit for bit.

    For d beyond ~4M coords the selection runs *hierarchically*: decode and
    top-k per chunk inside a scan, then a final top-k over the union of the
    per-chunk winners — mathematically identical to a flat top-k (every
    global winner wins its chunk), but the (d,)-sized estimate/score
    vectors never materialize (they are multi-GB at d ~ 10^9).

    The estimates run under the device scope ``recover/decode``, the
    threshold, scores and top-k under ``recover/select``.
    """
    if estimates is None and not faithful and d > _CHUNK and d > 4 * k:
        return _heavymix_chunked(cfg, sketch, k, d, use_pallas=use_pallas)
    with jax.named_scope("recover/decode"):
        est = (kops.decode(cfg, sketch, d, use_pallas=use_pallas)
               if estimates is None else estimates)
    with jax.named_scope("recover/select"):
        l2sq = cs.l2sq_estimate(sketch)
        heavy = est * est >= l2sq / k  # (alpha, l2)-heavy coordinates

        if faithful:
            if key is None:
                key = jax.random.PRNGKey(0)
            filler = jax.random.uniform(key, (d,))  # random priority for NH
            score = jnp.where(heavy, jnp.abs(est) + _BIG, filler)
        else:
            score = jnp.where(heavy, jnp.abs(est) + _BIG, jnp.abs(est))

        _, idx = jax.lax.top_k(score, k)
        return idx, est[idx]


def _heavymix_chunked(cfg: cs.SketchConfig, sketch: Array, k: int, d: int,
                      *, use_pallas: bool | None = None
                      ) -> tuple[Array, Array]:
    """Greedy-fill HEAVYMIX with chunked decode + hierarchical top-k.

    Greedy fill orders by |estimate|, and the heavy set H is exactly the
    top-|H| by |estimate| (heaviness is a threshold on est^2), so a plain
    top-k by |est| selects H ∪ greedy fill — no heavy-boost term needed.
    Every chunk decodes through one compiled decode, its base traced; the
    coordinates past ``d`` in the last chunk are not estimated.
    """
    with jax.named_scope("recover/decode"):
        sk = sketch.astype(jnp.float32)
    n = (d + _CHUNK - 1) // _CHUNK
    k_c = min(k, _CHUNK)

    def body(_, i):
        base = i * _CHUNK
        with jax.named_scope("recover/decode"):
            est = kops.decode(cfg, sk, _CHUNK, offset=base, limit=d,
                              use_pallas=use_pallas)
        with jax.named_scope("recover/select"):
            idx = jnp.arange(_CHUNK) + base
            score = jnp.where(idx < d, jnp.abs(est), -1.0)  # tail padding
            v, loc = jax.lax.top_k(score, k_c)
            return None, (v, loc + base, est[loc])

    _, (vals, idxs, ests) = jax.lax.scan(body, None, jnp.arange(n))
    with jax.named_scope("recover/select"):
        vals, idxs, ests = (vals.reshape(-1), idxs.reshape(-1),
                            ests.reshape(-1))
        _, sel = jax.lax.top_k(vals, k)
        return idxs[sel], ests[sel]
