"""Paper Figs. 4-5: per-iteration time breakdown t_compu / t_compr / t_commu.

Two columns per method:

* measured — wall time of the jitted compute/compression parts on THIS
  host (CPU). Honest but hardware-skewed: a CPU runs the O(d) sketch
  encode ~1000x slower than an accelerator's memory system.
* modeled accelerator — compression priced at HBM streaming cost
  (d * rows reads + writes at 819 GB/s, the TPU Pallas-kernel regime) and
  gTop-k's per-round merge re-sparsifications priced as top-k passes over
  2k candidates; compute taken from the measured forward/backward scaled
  into the accelerator's FLOP budget. Communication always comes from the
  paper's own Eq. 1 cost model at 1 GbE (alpha = 0.5 ms, beta = 8 ns/B)
  on each method's measured CommStats.

Key structural point the paper makes (and we reproduce): gTop-k's tree
performs a SEQUENTIAL top-k re-sparsification per round (latency chain),
while gs-SGD's sketch merge is a plain add and its single recovery happens
once, locally, after the all-reduce.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

from repro.core import compression as comp
from repro.core import count_sketch as cs
from repro.data import ImageStream
from repro.models import cnn

OUT = os.path.join(os.path.dirname(__file__), "..", "experiments", "bench")

from repro.sim.network import LINK_1GBE      # canonical Eq. 1 link model
from repro.sim.replay import ENCODE_BW       # canonical HBM stream rate

ALPHA_1GBE = LINK_1GBE.alpha  # per-round startup, seconds
BETA_1GBE = LINK_1GBE.beta    # seconds per byte at 1 Gbit/s
HBM_BW = ENCODE_BW            # accelerator memory bandwidth (bytes/s)
ACCEL_FLOPS = 50e12           # f32-ish sustained flops for the CNN parts

METHODS = ["gs-sgd", "sketched-sgd", "gtopk"]


def _time(f, *args, n=5):
    f(*args)  # compile + warmup
    t0 = time.time()
    for _ in range(n):
        jax.block_until_ready(f(*args))
    return (time.time() - t0) / n


def hbm_encode_time(d: int, rows: int, hbm: float = HBM_BW) -> float:
    """Sketch-encode compute stage priced at HBM streaming: read+write
    (8 B) per coordinate per row — the Pallas-kernel regime. Shared by the
    bucketed-overlap models here and in comm_model.py."""
    return d * rows * 8 / hbm


def paper_geometry(d: int) -> tuple[int, int]:
    """Paper-regime sparsity: k = 0.4% of d (Sec. IV-A final density);
    sketch width ~ k/2 so the sketch payload undercuts gTop-k's per-round
    2k (value, index) payload — the regime where Figs. 4-5 place gs-SGD."""
    k = max(64, int(0.004 * d))
    width = 1 << max(8, (k // 2 - 1).bit_length())
    return k, width


def breakdown(model: str, method: str, *, P=4, k=None, width=None,
              width_kw=None) -> dict:
    init, apply = cnn.MODELS[model]
    p0 = init(jax.random.PRNGKey(0), **(width_kw or {}))
    flat, _ = cs.ravel_tree(p0)
    d = flat.shape[0]
    if k is None or width is None:
        k, width = paper_geometry(d)
    b = ImageStream(global_batch=32).global_batch_at(0)
    imgs, labs = b["images"][:8], b["labels"][:8]

    # ---- t_compu: forward+backward (measured; modeled via flop count) ----
    grad_fn = jax.jit(jax.grad(
        lambda p: cnn.ce_loss(apply(p, imgs), labs)))
    t_compu = _time(grad_fn, p0)
    ca = jax.jit(grad_fn).lower(p0).compile().cost_analysis()
    fwd_flops = (ca or {}).get("flops", 0.0)
    t_compu_model = max(fwd_flops / ACCEL_FLOPS, 1e-5)

    # ---- t_compr -----------------------------------------------------------
    kw = dict(k=k)
    if method in ("gs-sgd", "sketched-sgd"):
        kw.update(rows=5, width=width)
    c = comp.make(method, **kw)
    g = jax.random.normal(jax.random.PRNGKey(1), (d,))
    rounds_tree = comp.ar.tree_allreduce_rounds(P) // 2
    n_rep = 2 if d > 5_000_000 else 5
    if method in ("gs-sgd", "sketched-sgd"):
        enc = jax.jit(lambda v: cs.encode(c.sketch, v))
        t_compr = _time(enc, g, n=n_rep)
        # accelerator: stream d coords x rows, read+write
        t_compr_model = hbm_encode_time(d, c.sketch.rows)
    else:
        # gTop-k re-sparsifies the full-length merged vector once per tree
        # round (sequential, on the critical path — our GTopK._sparsify
        # mirrors the reference implementation): (1 + rounds) top-k over d.
        t_local = _time(jax.jit(lambda v: jax.lax.top_k(jnp.abs(v), k)), g,
                        n=n_rep)
        t_compr = (1 + rounds_tree) * t_local
        # accelerator: top-k over d is a multi-pass select (~10 passes of
        # radix-select on real hardware), once per round + once locally
        t_compr_model = (1 + rounds_tree) * (10 * d * 4 / HBM_BW)

    # ---- t_commu: paper Eq. 1 on the method's measured CommStats ----------
    box = {}

    def probe(state, gg):
        u, s, stats = c.step(state, gg, axis="data", nworkers=P)
        box["stats"] = stats
        return u, s

    jax.vmap(probe, axis_name="data")(
        jnp.stack([c.init(d)] * P), jnp.stack([g] * P))
    t_commu = box["stats"].time(ALPHA_1GBE, BETA_1GBE)
    return {"t_compu": t_compu, "t_compr": t_compr, "t_commu": t_commu,
            "t_compu_model": t_compu_model, "t_compr_model": t_compr_model,
            "bytes": box["stats"].bytes_out, "rounds": box["stats"].rounds,
            "d": d}


def model_bucket_pipeline(d: int, n_buckets: int, *, P: int = 4,
                          k: int | None = None, width: int | None = None,
                          rows: int = 5, alpha: float = ALPHA_1GBE,
                          beta: float = BETA_1GBE, hbm: float = HBM_BW,
                          t_backward: float = 0.0,
                          bwd_chunks: int | None = None) -> dict:
    """Per-bucket CommStats + modeled comm/compute-overlap saving.

    Prices the bucketed gs-SGD exchange on the paper's Eq. 1 cost model
    with the REAL readiness schedule (DESIGN.md §7, the executable
    ``gs_sgd.exchange_interleaved`` path — no longer the old per-layer
    readiness upper bound): the backward scan emits buckets in
    reverse-layer order over ``bwd_chunks`` chunk events (the same
    ``sim/replay.bucket_readiness`` timeline the cluster simulator
    replays), each bucket's HBM-streaming encode starts when its gradient
    is emitted, and its sketch all-reduce + second round (Eq. 1) rides the
    3-stage ``compression.interleaved_schedule_time`` recurrence.

    Monolithic/serial = full backward, then every stage back-to-back.
    Saving is 0 at n_buckets=1 with t_backward=0 by construction and
    strictly positive once a second bucket exists to hide behind.

    t_backward=0 (default) models exactly what the post-accumulation
    schedule in ``core/gs_sgd.exchange_bucketed`` can hide (all buckets
    ready at once). t_backward>0 with bwd_chunks=K (default: one chunk
    per bucket) is the shipped backward-interleaved schedule of
    ``make_train_step(..., bwd_chunks=K)``.
    """
    from repro.sim.replay import bucket_readiness, event_times

    if k is None or width is None:
        k, width = paper_geometry(d)
    base = comp.make("gs-sgd", k=k, rows=rows, width=width)
    bc = comp.bucketize(base, comp.even_bucket_sizes(d, n_buckets))
    n = bc.spec.n
    kc = n if bwd_chunks is None else max(1, int(bwd_chunks))
    per, t_enc, t_comm = [], [], []
    for c, db in zip(bc.parts, bc.spec.sizes):
        stats = c.comm_stats(db, P)
        per.append({"d": db, "k": c.k, "width": c.sketch.width,
                    "bytes": stats.bytes_out, "rounds": stats.rounds,
                    "t_comm": stats.time(alpha, beta)})
        t_enc.append(hbm_encode_time(db, c.sketch.rows, hbm=hbm))
        t_comm.append(stats.time(alpha, beta))
    ev_t = event_times(t_backward, kc)
    ready = [ev_t[e] for e in bucket_readiness(bc.spec.offsets,
                                               bc.spec.sizes, d, kc)]
    serial, pipelined, exposed, _ = comp.interleaved_schedule_time(
        t_enc, t_comm, ready, t_backward=t_backward)
    return {"n_buckets": n, "bwd_chunks": kc, "per_bucket": per,
            "t_serial": serial, "t_pipelined": pipelined,
            "t_exposed": exposed, "overlap_saving": serial - pipelined}


def main() -> dict:
    results = {}
    for model in ("resnet20", "vgg16"):
        width_kw = ({"width": 8} if model == "resnet20"
                    else {"width_mult": 0.25})
        # paper regime: k ~ 0.4% of d, sketch width sized so the sketch
        # payload ~ the gTop-k per-round payload (Sec. IV densities)
        per = {}
        for method in METHODS:
            r = breakdown(model, method, width_kw=width_kw)
            per[method] = r
            tot = r["t_compu"] + r["t_compr"] + r["t_commu"]
            tot_m = r["t_compu_model"] + r["t_compr_model"] + r["t_commu"]
            print(f"{model:9s} {method:12s} "
                  f"measured: compu {r['t_compu'] * 1e3:7.1f} compr "
                  f"{r['t_compr'] * 1e3:7.1f} commu {r['t_commu'] * 1e3:6.1f}"
                  f" tot {tot * 1e3:7.1f}ms | accel-modeled tot "
                  f"{tot_m * 1e3:6.1f}ms")
        # bucketed gs-sgd: per-bucket CommStats + modeled overlap saving.
        # 'post-accum' = the post-accumulation encode/comm pipeline
        # (exchange_bucketed); 'interleaved' = the REAL backward-
        # interleaved readiness schedule (exchange_interleaved with
        # bwd_chunks=n_b), priced by the same 3-stage recurrence the
        # cluster simulator replays (DESIGN.md §7).
        d = per["gs-sgd"]["d"]
        tb = per["gs-sgd"]["t_compu_model"]  # accel-modeled fwd+bwd
        per["bucketed"] = {}
        for n_b in (1, 4, 8):
            r = model_bucket_pipeline(d, n_b)
            sched = model_bucket_pipeline(d, n_b, t_backward=tb,
                                          bwd_chunks=n_b)
            r["interleaved"] = {k: sched[k] for k in
                                ("t_serial", "t_pipelined", "t_exposed",
                                 "overlap_saving")}
            per["bucketed"][str(n_b)] = r
            print(f"{model:9s} gs-sgd x{r['n_buckets']:<2d} buckets: "
                  f"serial {r['t_serial'] * 1e3:6.2f}ms pipelined "
                  f"{r['t_pipelined'] * 1e3:6.2f}ms saving "
                  f"{r['overlap_saving'] * 1e3:6.3f}ms (interleaved "
                  f"{sched['overlap_saving'] * 1e3:6.3f}ms, exposed "
                  f"{sched['t_exposed'] * 1e3:6.3f}ms) | per-bucket "
                  f"bytes {[int(b['bytes']) for b in r['per_bucket']]}")
        results[model] = per
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "time_breakdown.json"), "w") as f:
        json.dump(results, f)
    return results


if __name__ == "__main__":
    main()
