"""kanana-2-30b-a3b's mechanisms against the plain reference
(``bench/reference/mla_moe.py``) at a tiny size on the CPU: latent
attention, a leading dense layer, sigmoid routing with a selection-only
bias, normalised and scaled gates, shared experts and a dropless held
share of the routed experts.

Tolerances. Both sides compute in float32 on the CPU, where a matmul at
default precision is a float32 matmul, so they differ only by the order
of their sums: online-softmax attention over key blocks against an exact
softmax, a grouped matmul over sorted rows against a dense one per
expert. That reassociation moves a float32 result by a few ulps per
operation; through three layers and the loss it stays under 1e-5 of a
value, so loss and logits are held to 1e-5 (relative, and absolute for
logits of size ~1) and each gradient leaf to 1e-4 of its largest entry.
Bias and loads are counts and sign steps: exact.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import mla_moe as ref
from repro.configs import SMOKES
from repro.core.gs_sgd import MeshAxes, make_state, make_train_step
from repro.models import model as mdl
from repro.models import moe as moe_lib
from repro.models.common import ShardCtx
from repro.models.flatten import SEG_NAMES, init_flat_params, make_flat_spec
from repro.models.layers import rope_interleaved
from repro.optim import make as make_opt

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = SMOKES["kanana-2-30b-a3b"]
CTX = ShardCtx(tp=1, tp_axis=None, dtype=jnp.float32)
SEED = 2**31 + 77
B, S = 2, 16


def ref_config(**kw) -> dict:
    """The benchmark's configuration file at the smoke size."""
    with open(os.path.join(ROOT, "bench", "configs",
                           "kanana-2-30b-a3b-l5-ep16.json")) as f:
        c = json.load(f)
    c.update(num_hidden_layers=CFG.n_layers, hidden_size=CFG.d_model,
             num_attention_heads=CFG.n_heads,
             num_key_value_heads=CFG.n_kv_heads,
             intermediate_size=CFG.d_ff, vocab_size=CFG.vocab_size,
             kv_lora_rank=CFG.kv_lora_rank,
             qk_nope_head_dim=CFG.qk_nope_dim,
             qk_rope_head_dim=CFG.qk_rope_dim, v_head_dim=CFG.v_head_dim,
             n_routed_experts=CFG.experts_held,
             num_experts_per_tok=CFG.experts_per_tok,
             moe_intermediate_size=CFG.moe_d_ff,
             published=dict(c["published"], n_routed_experts=CFG.n_experts))
    c.update(kw)
    return c


def batch(seed=1):
    t = jax.random.randint(jax.random.PRNGKey(seed), (B, S + 1), 0,
                           CFG.vocab_size)
    return {"tokens": t[:, :-1], "labels": t[:, 1:]}


def close_rel(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= tol * scale, np.abs(a - b).max() / scale


@pytest.fixture(scope="module")
def model():
    m = ref.Decoder.from_config(ref_config())
    fs = make_flat_spec(CFG, 1)
    return m, fs, init_flat_params(CFG, jax.random.PRNGKey(SEED), 1, fs)


def test_flat_layout_and_init_match_the_reference(model):
    m, fs, segs = model
    assert fs.total == ref.flat_size(m)
    want = ref.to_segments(m, ref.init_params(m, SEED))
    for k in SEG_NAMES:   # the same draws, scaled eagerly or jitted: 1 ulp
        np.testing.assert_allclose(np.asarray(segs[k]), np.asarray(want[k]),
                                   rtol=2e-7, atol=1e-30)


def test_loss_logits_and_gradients_match_the_reference(model):
    m, fs, segs = model
    b = batch()
    loss, grads = jax.value_and_grad(
        lambda s: mdl.loss_fn(CFG, CTX, fs, s, b, remat=True))(segs)
    params = ref.init_params(m, SEED)
    r_loss, r_grads = ref.loss_and_grad(m, params, b["tokens"], b["labels"])
    np.testing.assert_allclose(float(loss), float(r_loss), rtol=1e-5)
    got = ref.leaves_of_segments(m, grads)
    for k, g in r_grads.items():
        close_rel(got[k], g, 1e-4)

    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    hid, _, _, top, _ = mdl._backbone(CFG, CTX, fs, segs, b["tokens"], pos,
                                      "train")
    logits = mdl.lm_logits(hid, top["head"], CFG, CTX)
    r_logits, _ = ref.logits(m, params, b["tokens"])
    np.testing.assert_allclose(np.asarray(logits)[..., :CFG.vocab_size],
                               np.asarray(r_logits)[..., :CFG.vocab_size],
                               rtol=1e-5, atol=1e-5)


def _ragged_dot_with_junk():
    """``jax.lax.ragged_dot`` as a TPU may run it: the rows past the
    groups hold junk, in its output and in its lhs cotangent."""
    plain = jax.lax.ragged_dot

    def junk(a, live):
        return jnp.where(live, a, 7.0 + jnp.arange(a.size, dtype=a.dtype
                                                   ).reshape(a.shape) % 13)

    @jax.custom_vjp
    def rd(a, w, sizes):
        return rd_fwd(a, w, sizes)[0]

    def rd_fwd(a, w, sizes):
        live = (jnp.arange(a.shape[0]) < jnp.sum(sizes))[:, None]
        return junk(plain(a, w, sizes), live), (a, w, sizes)

    def rd_bwd(res, g):
        a, w, sizes = res
        live = (jnp.arange(a.shape[0]) < jnp.sum(sizes))[:, None]
        da, dw = jax.vjp(lambda x, y: plain(x, y, sizes), a, w)[1](g)
        return junk(da, live), dw, np.zeros(sizes.shape, jax.dtypes.float0)

    rd.defvjp(rd_fwd, rd_bwd)
    return lambda lhs, rhs, group_sizes, **kw: rd(lhs, rhs, group_sizes)


def test_rows_past_the_groups_reach_neither_output_nor_gradient(
        model, monkeypatch):
    m, fs, segs = model
    monkeypatch.setattr(jax.lax, "ragged_dot", _ragged_dot_with_junk())
    b = batch(4)
    loss, grads = jax.value_and_grad(
        lambda s: mdl.loss_fn(CFG, CTX, fs, s, b, remat=True))(segs)
    r_loss, r_grads = ref.loss_and_grad(m, ref.init_params(m, SEED),
                                        b["tokens"], b["labels"])
    np.testing.assert_allclose(float(loss), float(r_loss), rtol=1e-5)
    got = ref.leaves_of_segments(m, grads)
    for k, g in r_grads.items():
        close_rel(got[k], g, 1e-4)


def test_chunked_backward_matches_the_plain_gradient(model):
    # the leading dense layer runs in the prologue of the chunked path
    _, fs, segs = model
    b = batch(2)
    bias = 0.01 * jnp.arange(CFG.n_cycles * CFG.n_experts, dtype=jnp.float32
                             ).reshape(CFG.n_cycles, CFG.n_experts)
    (loss, load), grads = jax.value_and_grad(
        lambda s: mdl.loss_fn(CFG, CTX, fs, s, b, router_bias=bias,
                              with_load=True), has_aux=True)(segs)
    c_loss, steps, top, c_load = mdl.chunked_loss_vjp(
        CFG, CTX, fs, segs, b, chunks=2, router_bias=bias)
    got = {k: jnp.zeros_like(v) for k, v in segs.items()}
    for st in steps:
        (a, e), d_cs, d_cr = st()
        got["cycles_s"] = got["cycles_s"].at[a:e].set(d_cs)
        got["cycles_r"] = got["cycles_r"].at[a:e].set(d_cr)
    got["top_s"], got["top_r"] = top()
    assert float(c_loss) == pytest.approx(float(loss), rel=1e-6)
    np.testing.assert_array_equal(np.asarray(c_load), np.asarray(load))
    for k in SEG_NAMES:
        close_rel(got[k], grads[k], 1e-5)


def _moe_params(key, n_experts):
    d, ff = CFG.d_model, CFG.moe_d_ff
    ks = jax.random.split(key, 6)
    w = lambda k, *s: 0.1 * jax.random.normal(k, s, jnp.float32)  # noqa
    return {"router": w(ks[0], d, CFG.n_experts),
            "experts": {"wi": w(ks[1], n_experts, d, 2 * ff),
                        "wo": w(ks[2], n_experts, ff, d)},
            "shared": {"wg": w(ks[3], d, 2 * ff), "wu": w(ks[4], d, 2 * ff),
                       "wo": w(ks[5], 2 * ff, d)},
            "norm": jnp.zeros((d,), jnp.float32)}


def _ref_moe(p, h, bias):
    """The reference's MoE layer with every expert held."""
    m = ref.Decoder.from_config(ref_config(n_routed_experts=CFG.n_experts))
    lp = {"moe.router": p["router"], "moe.experts.wi": p["experts"]["wi"],
          "moe.experts.wo": p["experts"]["wo"],
          "moe.shared.wg": p["shared"]["wg"],
          "moe.shared.wu": p["shared"]["wu"],
          "moe.shared.wo": p["shared"]["wo"]}
    return jax.vmap(lambda r: ref._moe(m, lp, r, bias, ref.HIGHEST))(h)


def test_shares_of_the_experts_add_up_to_the_whole_layer():
    # four chips of 4 experts each: their partial outputs, with the shared
    # experts (computed alike on every chip) counted once, are the layer
    p = _moe_params(jax.random.PRNGKey(3), CFG.n_experts)
    h = jax.random.normal(jax.random.PRNGKey(4), (B, S, CFG.d_model))
    bias = jnp.zeros((CFG.n_experts,), jnp.float32)
    n = CFG.experts_held
    total = 0.0
    for s in range(CFG.n_experts // n):
        share = dict(p, experts=jax.tree_util.tree_map(
            lambda a, s=s: a[s * n:(s + 1) * n], p["experts"]))
        y, _, load = moe_lib.moe_ffn(share, CFG, CTX, h, bias, first=s * n,
                                     shared=(s == 0))
        total = total + y
    want, want_load = _ref_moe(p, h, bias)
    close_rel(total, want, 1e-5)
    np.testing.assert_array_equal(np.asarray(load),
                                  np.asarray(want_load).sum(0))


@pytest.mark.parametrize("skew", [1, 3])
def test_skewed_routing_drops_no_token(skew):
    # the bias sends every token to the first ``skew`` held experts: one
    # expert takes every token, or every choice of every token is held
    p = _moe_params(jax.random.PRNGKey(5), CFG.experts_held)
    h = jax.random.normal(jax.random.PRNGKey(6), (B, S, CFG.d_model))
    bias = jnp.zeros((CFG.n_experts,), jnp.float32).at[:skew].set(10.0)
    y, _, load = moe_lib.moe_ffn(p, CFG, CTX, h, bias, first=0)
    assert np.all(np.asarray(load)[:skew] == B * S)
    full = dict(p, experts=jax.tree_util.tree_map(
        lambda a: jnp.concatenate(
            [a, jnp.zeros((CFG.n_experts - a.shape[0],) + a.shape[1:])]),
        p["experts"]))
    want, _ = _ref_moe(full, h, bias)
    close_rel(y, want, 1e-5)


def test_selection_bias_is_state_moved_by_the_load(model):
    m, fs, segs = model
    opt = make_opt("adamw", lr=1e-3)
    ts = make_train_step(CFG, MeshAxes(tp=1, data=1, tp_axis=None,
                                       data_axis=None), opt,
                         compressor_name=None, dtype=jnp.float32)
    state = make_state(segs, opt, None, ts.d_local, cfg=CFG)
    # the bias is out of the flat vector, the gradient and the optimizer
    assert ts.d_local == ref.flat_size(m)
    assert set(state["params"]) == set(state["opt"]) == set(SEG_NAMES)
    np.testing.assert_array_equal(np.asarray(state["router_bias"]), 0.0)
    b = batch(10)
    state, met = jax.jit(ts.fn)(state, b)
    params = ref.init_params(m, SEED)
    _, loads = ref.logits(m, params, b["tokens"])
    load = np.asarray(loads).sum(0)                # (moe layers, experts)
    assert float(met["moe_held_rows"]) == load[:, :CFG.experts_held].sum()
    want = CFG.bias_rate * np.sign(load.sum(-1, keepdims=True)
                                   / CFG.n_experts - load)
    np.testing.assert_array_equal(np.asarray(state["router_bias"]), want)
    assert np.any(want != 0)
    # the reference moves its own bias the same way once a new step opens
    ref.loss_and_grad(m, params, b["tokens"], b["labels"])
    np.testing.assert_array_equal(np.asarray(m.state.bias), 0.0)
    ref.loss_and_grad(m, dict(params), b["tokens"], b["labels"])
    np.testing.assert_array_equal(np.asarray(m.state.bias), want)


def test_gs_sgd_workers_share_one_bias():
    from repro.api import ClusterSpec, ExchangeSpec, RunSpec
    from repro.data import LMStream
    from repro.launch import train
    spec = RunSpec(arch="kanana-2-30b-a3b", smoke=True, batch=4, seq=16,
                   cluster=ClusterSpec(p=2),
                   exchange=ExchangeSpec(compressor="gs-sgd"))
    cfg, opt, _, ts = train.build(spec)
    state = train.init_state(spec, cfg, opt, ts)
    stream = LMStream(vocab_size=cfg.vocab_size, seq_len=16, global_batch=4)
    step = train.make_step_fn(ts, 2)
    for i in range(2):
        state, met = step(state, train.worker_batch(stream, i, spec))
    bias = np.asarray(state["router_bias"])
    np.testing.assert_array_equal(bias[0], bias[1])
    assert np.all(np.isfinite(np.asarray(met["loss"])))


def test_interleaved_rotary_turns_each_pair():
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 5, 2, 8))
    pos = jnp.arange(5)[None]
    got = np.asarray(rope_interleaved(x, pos, 1e6))
    xs = np.asarray(x, np.float64)
    for t in range(5):
        for i in range(4):
            a = t * 1e6 ** (-2 * i / 8)
            rot = np.array([[np.cos(a), -np.sin(a)], [np.sin(a), np.cos(a)]])
            want = xs[0, t, :, 2 * i:2 * i + 2] @ rot.T
            np.testing.assert_allclose(got[0, t, :, 2 * i:2 * i + 2], want,
                                       rtol=1e-5, atol=1e-6)


def test_serving_latent_attention_is_refused(model):
    _, fs, segs = model
    with pytest.raises(NotImplementedError, match="latent attention"):
        mdl.init_cache(CFG, CTX, B, 32, jnp.float32)
    with pytest.raises(NotImplementedError, match="latent attention"):
        mdl.prefill_fn(CFG, CTX, fs, segs, batch(), None)
