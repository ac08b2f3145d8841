"""Backward-interleaved bucket readiness (PR 3 tentpole): chunked-backward
gradient equivalence, the readiness scheduler's bit-exactness against the
post-accumulation pipeline, the bucket plan, the 3-stage recurrence, and
the simulator's readiness-timeline replay."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKES
from repro.core import compression as comp
from repro.core.gs_sgd import MeshAxes, make_state, make_train_step
from repro.models import model as mdl
from repro.models.common import ShardCtx
from repro.models.flatten import (bucket_plan, bucket_sizes, chunk_plan,
                                  init_flat_params, make_flat_spec,
                                  packed_offsets)

CFG = SMOKES["qwen3-4b"]
P, B, S = 4, 2, 16


# ---------------------------------------------------------------------------
# Chunked backward: per-chunk VJPs compose to the monolithic gradient
# ---------------------------------------------------------------------------


def _grads_of(chunks, remat=False):
    fs = make_flat_spec(CFG, 1)
    ctx = ShardCtx(tp=1, tp_axis=None, dp_axes=(), dtype=jnp.float32)
    segs = init_flat_params(CFG, jax.random.PRNGKey(0), 1, fs)
    t = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, CFG.vocab_size)
    batch = {"tokens": t, "labels": t}
    if chunks is None:
        return jax.value_and_grad(
            lambda p: mdl.loss_fn(CFG, ctx, fs, p, batch, remat=remat))(segs)
    loss, steps, top, _ = mdl.chunked_loss_vjp(CFG, ctx, fs, segs, batch,
                                               chunks=chunks, remat=remat)
    d_cs = jnp.zeros_like(segs["cycles_s"])
    d_cr = jnp.zeros_like(segs["cycles_r"])
    spans = []
    for s in steps:
        (a, b), dcs, dcr = s()
        spans.append((a, b))
        d_cs = d_cs.at[a:b].set(dcs)
        d_cr = d_cr.at[a:b].set(dcr)
    d_ts, d_tr = top()
    # emission is reverse-chunk order and spans tile [0, n_cycles)
    assert spans == sorted(spans, reverse=True)
    assert spans[-1][0] == 0 and spans[0][1] == CFG.n_cycles
    return loss, {"top_s": d_ts, "top_r": d_tr,
                  "cycles_s": d_cs, "cycles_r": d_cr}


@pytest.mark.parametrize("chunks", [1, 2, 3])
def test_chunked_vjp_matches_monolithic_grad(chunks):
    loss_m, g_m = _grads_of(None)
    loss_c, g_c = _grads_of(chunks)
    assert float(loss_c) == float(loss_m)
    for k in g_m:
        np.testing.assert_array_equal(np.asarray(g_c[k]), np.asarray(g_m[k]),
                                      err_msg=k)


def test_chunked_vjp_matches_under_remat():
    loss_m, g_m = _grads_of(None, remat=True)
    loss_c, g_c = _grads_of(2, remat=True)
    assert float(loss_c) == pytest.approx(float(loss_m), rel=1e-6)
    for k in g_m:
        np.testing.assert_allclose(np.asarray(g_c[k]), np.asarray(g_m[k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)


# ---------------------------------------------------------------------------
# Train-step equivalence (acceptance criterion)
# ---------------------------------------------------------------------------


def _run(buckets=None, bwd_chunks=None, overlap=True, steps=3,
         name="gs-sgd", **ckw):
    from repro.optim import make as make_opt
    opt = make_opt("adamw", lr=2e-3)
    ma = MeshAxes(tp=1, data=P, tp_axis=None, data_axis="data")
    ts = make_train_step(CFG, ma, opt, dp_mode="dp", compressor_name=name,
                         compressor_kw=ckw or None, remat=False,
                         dtype=jnp.float32, buckets=buckets, overlap=overlap,
                         bwd_chunks=bwd_chunks)
    params = init_flat_params(CFG, jax.random.PRNGKey(0), 1, ts.fs)
    st = make_state(params, opt, ts.compressor, ts.d_local)
    st = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (P,) + a.shape), st)
    fn = jax.jit(jax.vmap(ts.fn, axis_name="data"))
    for i in range(steps):
        t = jax.random.randint(jax.random.PRNGKey(100 + i), (P, B, S), 0,
                               CFG.vocab_size)
        st, m = fn(st, {"tokens": t, "labels": t})
        assert np.isfinite(float(m["loss"][0]))
    return st, ts


def _assert_params(a, b, exact=True):
    for k in a["params"]:
        if exact:
            np.testing.assert_array_equal(np.asarray(a["params"][k]),
                                          np.asarray(b["params"][k]),
                                          err_msg=k)
        else:
            np.testing.assert_allclose(np.asarray(a["params"][k]),
                                       np.asarray(b["params"][k]),
                                       rtol=1e-5, atol=1e-6, err_msg=k)


@pytest.mark.parametrize("buckets", [1, 4])
def test_chunks1_bitexact_vs_post_accumulation(buckets):
    """bwd_chunks=1 routes through chunked_loss_vjp + the readiness
    scheduler but must reproduce the existing post-accumulation
    ``exchange_bucketed`` step BIT-EXACTLY (the PR acceptance pin)."""
    legacy, ts_l = _run(buckets=buckets, bwd_chunks=None,
                        k=1024, rows=5, width=2048)
    ready, ts_r = _run(buckets=buckets, bwd_chunks=1,
                       k=1024, rows=5, width=2048)
    assert ts_l.bwd_chunks == 0 and ts_r.bwd_chunks == 1
    assert ts_r.plan is not None and ts_r.plan.n_events == 2
    _assert_params(legacy, ready, exact=True)


def test_chunks2_matches_post_accumulation_close():
    """K>1 re-chunks the backward graph (XLA refuses bitwise identity for
    a re-fused scan) but the schedule itself is a pure reordering of
    disjoint bucket chains — parameters must agree to float tolerance."""
    legacy, _ = _run(buckets=4, bwd_chunks=None, k=1024, rows=5, width=2048)
    inter, ts = _run(buckets=4, bwd_chunks=2, k=1024, rows=5, width=2048)
    assert ts.plan.n_events == 3
    _assert_params(legacy, inter, exact=False)


def test_interleaved_still_learns_and_replicas_agree():
    st, ts = _run(buckets=4, bwd_chunks=3, steps=6, k=2048, rows=5,
                  width=4096)
    assert ts.bwd_chunks == 3
    for v in st["params"].values():   # replicas never diverge
        assert float(jnp.max(jnp.abs(v - v[0:1]))) == 0.0


def test_bwd_chunks_with_microbatch_raises():
    from repro.optim import make as make_opt
    ma = MeshAxes(tp=1, data=P, tp_axis=None, data_axis="data")
    with pytest.raises(ValueError, match="microbatch"):
        make_train_step(CFG, ma, make_opt("adamw", lr=1e-3),
                        microbatch=1, bwd_chunks=2, buckets=2)


# ---------------------------------------------------------------------------
# Bucket plan
# ---------------------------------------------------------------------------


def _shapes(top_s=53760, top_r=512, n_cyc=6, cyc_s=9216, cyc_r=512):
    return {"top_s": (top_s,), "top_r": (top_r,),
            "cycles_s": (n_cyc, cyc_s), "cycles_r": (n_cyc, cyc_r)}


def test_chunk_plan_tiles_and_clamps():
    assert chunk_plan(6, 2) == ((0, 3), (3, 6))
    assert chunk_plan(5, 3) == ((0, 2), (2, 4), (4, 5))
    assert chunk_plan(2, 8) == ((0, 1), (1, 2))   # clamped to n_cycles
    assert chunk_plan(7, 1) == ((0, 7),)


@pytest.mark.parametrize("n_buckets,n_chunks", [(1, 1), (4, 1), (4, 2),
                                                (8, 3), (6, 6), (2, 4)])
def test_bucket_plan_partition_and_readiness(n_buckets, n_chunks):
    shapes = _shapes()
    plan = bucket_plan(shapes, n_buckets, n_chunks)
    # partition is EXACTLY the PR 1 partition (geometry pinned)
    assert plan.sizes == bucket_sizes(shapes, n_buckets)
    k = len(plan.chunks)
    assert plan.n_events == k + 1
    assert all(0 <= r <= k for r in plan.readiness)
    # the bucket containing packed offset 0 (top_s = embed+head) is only
    # ready at the LAST event
    assert plan.readiness[0] == k
    # exchange order covers every bucket once, readiness nondecreasing
    order = plan.order
    assert sorted(order) == list(range(plan.n))
    rs = [plan.readiness[i] for i in order]
    assert rs == sorted(rs)


def test_bucket_plan_chunks1_degenerates_to_two_events():
    plan = bucket_plan(_shapes(), 4, 1)
    assert plan.n_events == 2
    # cycle-only buckets ready at event 0, anything touching top at event 1
    offs = packed_offsets(_shapes())
    off = 0
    for s, r in zip(plan.sizes, plan.readiness):
        expect = 1 if off < offs["cycles_s"] else 0
        assert r == expect, (off, s)
        off += s


def test_bucket_plan_reverse_layer_order():
    """With buckets aligned to cycle rows, later cycles are ready earlier
    (reverse-layer emission) and embed+head last."""
    shapes = _shapes(top_s=9216, n_cyc=8)
    plan = bucket_plan(shapes, 8, 4)
    order = plan.order
    # the first exchanged bucket must sit at the END of the cycles_s region
    first = order[0]
    start = sum(plan.sizes[:first])
    assert start >= packed_offsets(shapes)["cycles_s"]
    # the top bucket (offset 0) is exchanged last
    assert order[-1] == 0 or plan.readiness[0] == len(plan.chunks)


# ---------------------------------------------------------------------------
# 3-stage recurrence
# ---------------------------------------------------------------------------


def test_interleaved_recurrence_reduces_to_overlap_at_one_chunk():
    t_enc, t_comm = [1.0, 1.0, 1.0], [2.0, 2.0, 2.0]
    t_b = 5.0
    serial0, pipe0 = comp.overlap_schedule_time(t_enc, t_comm)
    serial, pipe, exposed, enc_done = comp.interleaved_schedule_time(
        t_enc, t_comm, [t_b] * 3, t_backward=t_b)
    assert enc_done == pytest.approx(t_b + sum(t_enc))
    assert serial == pytest.approx(t_b + serial0)
    assert pipe == pytest.approx(t_b + pipe0)
    assert exposed == pytest.approx(pipe0)


def test_interleaved_recurrence_exposed_shrinks_with_earlier_readiness():
    t_enc, t_comm = [0.1] * 4, [1.0] * 4
    t_b = 2.0
    prev = None
    for k in (1, 2, 4):
        # k chunk events at uniform fractions, buckets in reverse order
        ready = [t_b * (k - min(k - 1, i)) / k for i in range(4)][::-1]
        ready = sorted(ready)
        _, _, exposed, _ = comp.interleaved_schedule_time(
            t_enc, t_comm, ready, t_backward=t_b)
        if prev is not None:
            assert exposed <= prev + 1e-12
        prev = exposed


def test_interleaved_recurrence_sorts_by_readiness():
    # identical schedule regardless of the input order of buckets
    t_enc, t_comm = [0.1, 0.2, 0.3], [1.0, 2.0, 3.0]
    ready = [3.0, 2.0, 1.0]
    a = comp.interleaved_schedule_time(t_enc, t_comm, ready, t_backward=3.0)
    perm = [2, 1, 0]
    b = comp.interleaved_schedule_time([t_enc[i] for i in perm],
                                       [t_comm[i] for i in perm],
                                       [ready[i] for i in perm],
                                       t_backward=3.0)
    assert a == pytest.approx(b)


# ---------------------------------------------------------------------------
# Simulator readiness replay
# ---------------------------------------------------------------------------


def test_replay_readiness_indices_reverse_emission():
    from repro.sim.replay import bucket_readiness, event_times
    sizes = (25, 25, 25, 25)
    offsets = (0, 25, 50, 75)
    assert bucket_readiness(offsets, sizes, 100, 4) == (3, 2, 1, 0)
    assert bucket_readiness(offsets, sizes, 100, 1) == (0, 0, 0, 0)
    assert bucket_readiness(offsets, sizes, 100, 2) == (1, 1, 0, 0)
    assert event_times(1.0, 4) == [0.25, 0.5, 0.75, 1.0]


def test_replay_step_cost_backcompat_and_interleave():
    from repro.sim.network import make_network
    from repro.sim.replay import ExchangeReplay
    net = make_network("hier", group_size=8)
    rep = ExchangeReplay("gs-sgd", 2 ** 20, buckets=8, k=1024, rows=5,
                         width=2 ** 15)
    ids = list(range(32))
    base = rep.step_cost(net, ids)
    # bwd_chunks=1 is byte-for-byte the PR 2 pipeline, t_backward ignored
    same = rep.step_cost(net, ids, t_backward=0.5, bwd_chunks=1)
    assert same == base
    prev = base.comm + base.encode
    for k in (2, 4, 8):
        pc = rep.step_cost(net, ids, t_backward=0.5, bwd_chunks=k)
        assert pc.comm_serial == base.comm_serial     # same priced rounds
        assert pc.bytes_critical == base.bytes_critical
        exposed = pc.comm + pc.encode
        assert exposed < prev                         # strictly more hidden
        prev = exposed


def test_simulate_bwd_chunks_reduces_exposed_comm():
    from repro.sim import ComputeModel, SimConfig, simulate
    base = dict(p=32, d=1_000_000, method="gs-sgd", buckets=8, steps=4,
                k=2048, rows=5, width=2 ** 15, topology="hier",
                compute=ComputeModel(mean=0.05, jitter=0.0),
                drop_stragglers=False)
    r1 = simulate(SimConfig(**base, bwd_chunks=1))
    r4 = simulate(SimConfig(**base, bwd_chunks=4))
    t1, t4 = r1.totals(), r4.totals()
    assert t4["comm"] < t1["comm"]
    assert t4["makespan"] < t1["makespan"]
    # payload accounting is schedule-independent
    assert t4["bytes_critical"] == pytest.approx(t1["bytes_critical"])
    assert t4["rounds"] == t1["rounds"]


def test_simulate_json_curves_shape():
    """--json emits the comm_complexity.json shape (model/curves/checks)."""
    import json
    import tempfile

    from repro.launch.simulate import main
    with tempfile.NamedTemporaryFile(suffix=".json", mode="r") as f:
        main(["--p", "4", "--steps", "3", "--bwd-chunks", "2",
              "--buckets", "4", "--json", f.name])
        out = json.load(open(f.name))
    for key in ("model", "methods", "curves", "checks"):
        assert key in out
    assert out["model"]["bwd_chunks"] == 2
    assert len(out["curves"]) == 3
    row = out["curves"][0]
    for key in ("method", "p", "bytes", "rounds", "comm", "time_sim"):
        assert key in row
