"""Pallas Count-Sketch kernels vs pure-jnp oracle (interpret=True on CPU).

Shape/dtype sweeps per the kernel-validation contract: the kernel body
executes in Python via the interpreter, checking the real BlockSpec
tiling/index-map logic the TPU build will use. These oracle sweeps run
WITHOUT hypothesis — the property-based generators live in
tests/test_properties.py behind an importorskip, so a container missing
the dev extras still validates every kernel (a module-scope importorskip
here once silently skipped this whole file; see
test_kernel_suite_collects_without_hypothesis).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import count_sketch as cs
from repro.core.count_sketch import SketchConfig
from repro.kernels import ops, ref
from repro.kernels.dispatch import default_interpret, resolve_dispatch
from repro.kernels.sketch_decode import sketch_decode
from repro.kernels.sketch_encode import (hi_block, sketch_encode,
                                          sketch_encode_bucketed)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("d", [128, 1024, 4096, 5000, 16384])
@pytest.mark.parametrize("rows,width", [(1, 256), (3, 512), (5, 1024)])
def test_encode_matches_ref_shapes(d, rows, width):
    cfg = SketchConfig(rows=rows, width=width, seed=2)
    g = jax.random.normal(jax.random.PRNGKey(d), (d,))
    out = sketch_encode(cfg, g, interpret=True)
    want = ref.count_sketch_encode(cfg, g)
    assert out.shape == (rows, width)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_encode_dtypes(dtype):
    cfg = SketchConfig(rows=3, width=512, seed=2)
    g = jax.random.normal(jax.random.PRNGKey(0), (2048,)).astype(dtype)
    out = sketch_encode(cfg, g, interpret=True)
    want = ref.count_sketch_encode(cfg, g.astype(jnp.float32))
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("block_d,block_w", [(256, 2048), (1024, 4096),
                                             (4096, 8192)])
def test_encode_block_shapes(block_d, block_w):
    """Element blocks of 256 to 4096 and one to four passes over ``g``
    (``block_w`` buckets a pass: 16, 32 and 64 of the 64 hi rows)."""
    cfg = SketchConfig(rows=3, width=8192, seed=5)
    g = jax.random.normal(jax.random.PRNGKey(1), (8192,))
    out = sketch_encode(cfg, g, block_d=block_d, block_w=block_w,
                        interpret=True)
    want = ref.count_sketch_encode(cfg, g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("width,block_w", [(8192, 6144), (16384, 6144),
                                           (16384, 10240)])
def test_encode_width_not_divisible_by_block(width, block_w):
    """Regression: a grid of width // block_w passes silently DROPPED the
    tail buckets for any width not a block_w multiple — every coordinate
    hashed into the dropped buckets vanished from the sketch. The hi rows
    pad up to whole passes instead."""
    cfg = SketchConfig(rows=4, width=width, seed=9)
    g = jax.random.normal(jax.random.PRNGKey(7), (6000,))
    out = sketch_encode(cfg, g, block_w=block_w, interpret=True)
    want = ref.count_sketch_encode(cfg, g)
    assert out.shape == (4, width)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # the tail columns specifically must carry mass, not zeros
    tail = np.asarray(want)[:, (width // block_w) * block_w:]
    assert np.abs(tail).max() > 0


def factored_encode(cfg: SketchConfig, g, offset: int = 0):
    """The kernel's factored contraction in plain jnp: bucket = hi * 128 +
    lo, the three bf16 parts of the signed values one-hot at hi, contracted
    over the elements against a 0/1 one-hot at lo."""
    g = g.reshape(-1).astype(jnp.float32)
    buckets, signs = cs.hash_buckets(cfg, jnp.arange(g.shape[0]) + offset)
    n_hi = -(-cfg.width // 128)
    v = signs * g  # (R, d)
    hi = v.astype(jnp.bfloat16)
    mid = (v - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    lo = (v - hi.astype(jnp.float32) - mid.astype(jnp.float32))
    at_hi = jax.nn.one_hot(buckets >> 7, n_hi, dtype=jnp.bfloat16)
    at_lo = jax.nn.one_hot(buckets & 127, 128, dtype=jnp.bfloat16)
    out = sum(jnp.einsum("rdh,rdl->rhl",
                         at_hi * p.astype(jnp.bfloat16)[..., None], at_lo,
                         preferred_element_type=jnp.float32)
              for p in (hi, mid, lo))
    return out.reshape(cfg.rows, n_hi * 128)[:, :cfg.width]


@pytest.mark.parametrize("width", [64, 128, 1024])
def test_factored_formulation_equals_onehot(width):
    """The factorisation itself, without Pallas: the (hi one-hot x signed
    values) (lo one-hot) contraction equals the (d x W) one-hot matmul."""
    cfg = SketchConfig(rows=3, width=width, seed=4)
    g = jax.random.normal(jax.random.PRNGKey(width), (3000,))
    np.testing.assert_allclose(
        np.asarray(factored_encode(cfg, g)),
        np.asarray(ref.count_sketch_encode_onehot(cfg, g)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("offset", [0, 12345])
@pytest.mark.parametrize("width", [64, 128, 16384, 65536])
def test_encode_factored_widths(width, offset):
    """Under 128 buckets (one hi row, padded lanes), 128 (one hi row),
    the benchmark's 16384 (128 x 128) and 65536 (four passes of 128 hi
    rows at the default ``block_w``), at coordinate 0 and at an offset."""
    cfg = SketchConfig(rows=5, width=width, seed=8)
    g = jax.random.normal(jax.random.PRNGKey(width + offset), (5000,))
    out = sketch_encode(cfg, g, index_offset=offset, interpret=True)
    want = ref.count_sketch_encode(cfg, g, offset=offset)
    assert out.shape == (5, width)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(factored_encode(cfg, g, offset)),
                               rtol=1e-5, atol=1e-5)


def test_encode_vmap_two_workers():
    """Two workers vmapped, as in the benchmark, at its rows and width."""
    cfg = SketchConfig(rows=5, width=16384, seed=0)
    g = jax.random.normal(jax.random.PRNGKey(3), (2, 6000))
    out = jax.vmap(lambda x: sketch_encode(cfg, x, interpret=True))(g)
    assert out.shape == (2, 5, 16384)
    for w in range(2):
        want = ref.count_sketch_encode(cfg, g[w])
        np.testing.assert_allclose(np.asarray(out[w]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("width,block_w,want", [
    (64, 16384, (16, 16)),        # one hi row, padded to a tile
    (16384, 16384, (128, 128)),   # one pass
    (65536, 16384, (128, 512)),   # four passes
    (16384, 1000, (16, 128)),     # under one tile: one tile a pass
    (16384, 6144, (48, 144)),     # passes pad past the last hi row
])
def test_hi_block_geometry(width, block_w, want):
    assert hi_block(width, block_w) == want


@pytest.mark.parametrize("width,block_w", [(512, 384), (2048, 768)])
def test_decode_width_not_divisible_by_block(width, block_w):
    """Same tail-column-drop regression on the decode gather."""
    cfg = SketchConfig(rows=3, width=width, seed=9)
    d = 3000
    g = jax.random.normal(jax.random.PRNGKey(8), (d,))
    sk = ref.count_sketch_encode(cfg, g)
    out = sketch_decode(cfg, sk, d, block_w=block_w, interpret=True)
    want = ref.count_sketch_decode(cfg, sk, d)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("offsets,sizes", [
    ((0, 1000, 1700), (1000, 700, 1300)),
    ((0, 2048), (2048, 952)),
])
def test_partial_encode_offsets_sum_to_full(offsets, sizes):
    """The fused-pipeline contract: a partial encode at each slice's offset
    matches the ref partial encode, and the partials over a disjoint
    tiling sum to the whole-vector sketch (count-sketch linearity)."""
    cfg = SketchConfig(rows=5, width=512, seed=3)
    d = sum(sizes)
    g = jax.random.normal(jax.random.PRNGKey(0), (d,))
    whole = ref.count_sketch_encode(cfg, g)
    acc = None
    for o, s in zip(offsets, sizes):
        part = sketch_encode(cfg, g[o:o + s], index_offset=o, interpret=True)
        want = ref.count_sketch_encode(cfg, g[o:o + s], offset=o)
        np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        acc = part if acc is None else acc + part
    np.testing.assert_allclose(np.asarray(acc), np.asarray(whole),
                               rtol=1e-4, atol=1e-3)


def test_partial_decode_offset_matches_ref():
    cfg = SketchConfig(rows=5, width=512, seed=3)
    g = jax.random.normal(jax.random.PRNGKey(1), (3000,))
    sk = ref.count_sketch_encode(cfg, g)
    out = sketch_decode(cfg, sk, 700, index_offset=1000, interpret=True)
    want = ref.count_sketch_decode(cfg, sk, 700, offset=1000)
    # one-hot gather sums exact zeros outside the bucket: bit-exact
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("d", [128, 1000, 4096])
@pytest.mark.parametrize("rows", [1, 3, 4, 5])
def test_decode_matches_ref(d, rows):
    cfg = SketchConfig(rows=rows, width=512, seed=3)
    g = jax.random.normal(jax.random.PRNGKey(d + rows), (d,))
    sk = ref.count_sketch_encode(cfg, g)
    out = sketch_decode(cfg, sk, d, interpret=True)
    want = ref.count_sketch_decode(cfg, sk, d)
    assert out.shape == (d,)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("width", [64, 512, 16384, 65536])
@pytest.mark.parametrize("rows", [1, 4, 5])
def test_factored_decode_bit_identical(rows, width):
    """The factored decode (one-hot of the high bits on the MXU, the low
    bits picked on the vector unit) gives the gather's estimates bit for
    bit: under one hi row (64), one tile, one 128-row pass and four
    passes (65536), for a d that is no multiple of the block."""
    cfg = SketchConfig(rows=rows, width=width, seed=rows + width)
    d = 5000
    g = jax.random.normal(jax.random.PRNGKey(width + rows), (d,))
    sk = ref.count_sketch_encode(cfg, g)
    out = sketch_decode(cfg, sk, d, interpret=True)
    want = ref.count_sketch_decode(cfg, sk, d)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_decode_traced_offset_under_jit_and_scan():
    """``index_offset`` may be traced: under ``jit`` and as a scan's
    chunk base, one compiled kernel decodes every chunk as the gather
    does, and ``limit`` zeroes the tail (the last chunk's second block
    lies wholly past it and is skipped)."""
    cfg = SketchConfig(rows=5, width=1024, seed=2)
    d, chunk = 9000, 4096
    g = jax.random.normal(jax.random.PRNGKey(4), (d,))
    sk = ref.count_sketch_encode(cfg, g)

    jitted = jax.jit(lambda s, o: sketch_decode(cfg, s, 700, index_offset=o,
                                                interpret=True))
    np.testing.assert_array_equal(
        np.asarray(jitted(sk, jnp.int32(1000))),
        np.asarray(ref.count_sketch_decode(cfg, sk, 700, offset=1000)))

    def body(_, i):
        base = i * chunk
        return None, (sketch_decode(cfg, sk, chunk, index_offset=base,
                                    limit=d, interpret=True),
                      ref.count_sketch_decode(cfg, sk, chunk, offset=base,
                                              limit=d))

    _, (out, want) = jax.jit(
        lambda: jax.lax.scan(body, None, jnp.arange(3)))()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    flat = np.asarray(out).reshape(-1)
    np.testing.assert_array_equal(
        flat[:d], np.asarray(ref.count_sketch_decode(cfg, sk, d)))
    assert not flat[d:].any()


def test_decode_vmap_two_sketches():
    """Two workers' sketches under ``jax.vmap``: each decodes as alone."""
    cfg = SketchConfig(rows=5, width=2048, seed=7)
    d = 3000
    g = jax.random.normal(jax.random.PRNGKey(6), (2, d))
    sks = jnp.stack([ref.count_sketch_encode(cfg, x) for x in g])
    out = jax.vmap(lambda s: sketch_decode(
        cfg, s, d, index_offset=jnp.int32(77), interpret=True))(sks)
    for w in range(2):
        np.testing.assert_array_equal(
            np.asarray(out[w]),
            np.asarray(ref.count_sketch_decode(cfg, sks[w], d, offset=77)))


def test_encode_decode_roundtrip_recovers_heavy():
    cfg = SketchConfig(rows=5, width=2048, seed=4)
    g = jnp.zeros(16384).at[7777].set(500.0)
    sk = sketch_encode(cfg, g, interpret=True)
    est = sketch_decode(cfg, sk, 16384, interpret=True)
    assert int(jnp.argmax(jnp.abs(est))) == 7777


def test_onehot_formulation_equals_scatter():
    """The kernel's one-hot-matmul math == the scatter/segment-sum math."""
    cfg = SketchConfig(rows=4, width=256, seed=6)
    g = jax.random.normal(jax.random.PRNGKey(2), (3000,))
    a = ref.count_sketch_encode(cfg, g)
    b = ref.count_sketch_encode_onehot(cfg, g)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("k,d", [(16, 2000), (64, 8192)])
def test_heavymix_kernel_matches_oracle(k, d):
    """Fused decode+score kernel + top_k == the greedy heavymix oracle."""
    cfg = SketchConfig(rows=5, width=1024, seed=11)
    g = jax.random.normal(jax.random.PRNGKey(5), (d,))
    g = g.at[:k // 2].set(jnp.sign(g[:k // 2]) * 50.0)  # plant heavies
    sk = ref.count_sketch_encode(cfg, g)
    idx_k, est_k = ops.heavymix_recover(cfg, sk, k, d, use_pallas=True,
                                        interpret=True)
    idx_r, est_r = ref.heavymix_recover(cfg, sk, k, d)
    np.testing.assert_array_equal(np.asarray(idx_k), np.asarray(idx_r))
    np.testing.assert_allclose(np.asarray(est_k), np.asarray(est_r),
                               rtol=1e-6, atol=1e-6)


def test_bucketed_encode_size_mismatch_raises():
    cfgs = [SketchConfig(rows=3, width=256, seed=0)] * 2
    g = jnp.ones(100)
    with pytest.raises(ValueError, match="must sum to the flat gradient"):
        sketch_encode_bucketed(cfgs, g, (50, 60), interpret=True)
    with pytest.raises(ValueError, match="must sum to the flat gradient"):
        ops.encode_buckets(cfgs, g, (50, 60), use_pallas=False)


# ---------------------------------------------------------------------------
# Dispatch policy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["tpu", "cpu", "gpu"])
@pytest.mark.parametrize("use_pallas", [None, True, False])
@pytest.mark.parametrize("interpret", [None, True, False])
def test_dispatch_table(backend, use_pallas, interpret):
    """The full (backend, use_pallas, interpret) policy table: pallas
    defaults to TPU-only; interpret defaults to everything-but-TPU;
    explicit values always win; the ref path ignores interpret."""
    pallas, interp = resolve_dispatch(backend, use_pallas=use_pallas,
                                      interpret=interpret)
    want_pallas = (backend == "tpu") if use_pallas is None else use_pallas
    assert pallas is want_pallas
    if not want_pallas:
        assert interp is False  # ref path: interpret is meaningless
    elif interpret is None:
        assert interp is (backend != "tpu")
    else:
        assert interp is interpret


def test_kernel_default_interpret_matches_ops_policy():
    """Direct kernel callers (interpret=None) and the ops layer derive the
    SAME interpret mode for this process's backend — the hardcoded
    interpret=True default once pinned direct TPU callers to the
    interpreter."""
    backend = jax.default_backend()
    assert default_interpret(None) is (backend != "tpu")
    assert default_interpret(True) is True
    assert default_interpret(False) is False
    _, interp = resolve_dispatch(backend, use_pallas=True)
    assert interp is default_interpret(None)


def test_ops_dispatch_agrees_across_paths():
    """encode/decode give the same numbers whichever dispatch leg runs."""
    cfg = SketchConfig(rows=3, width=512, seed=1)
    g = jax.random.normal(jax.random.PRNGKey(3), (2048,))
    a = ops.encode(cfg, g, use_pallas=False)
    b = ops.encode(cfg, g, use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)
    da = ops.decode(cfg, a, 2048, use_pallas=False)
    db = ops.decode(cfg, a, 2048, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(da), np.asarray(db))


# ---------------------------------------------------------------------------
# Collection guard (tier 1): the oracle sweeps must NOT depend on hypothesis
# ---------------------------------------------------------------------------


def test_kernel_suite_collects_without_hypothesis(tmp_path):
    """Regression for the silently-skipped kernel validation suite: a
    module-scope ``pytest.importorskip('hypothesis')`` skipped EVERY test
    in this file and test_count_sketch.py on containers without the dev
    extras — zero kernel oracle coverage while the suite stayed green.
    Collect both files in a subprocess where importing hypothesis is
    forced to fail and assert the oracle sweeps are still gathered."""
    shim = tmp_path / "hypothesis.py"
    shim.write_text("raise ImportError('hypothesis blocked by "
                    "test_kernel_suite_collects_without_hypothesis')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(tmp_path), os.path.join(REPO_ROOT, "src")])
    env["PYTEST_DISABLE_PLUGIN_AUTOLOAD"] = "1"
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "tests/test_kernels.py", "tests/test_count_sketch.py",
         "tests/test_properties.py"],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    for must_collect in ("test_encode_matches_ref_shapes",
                        "test_decode_matches_ref",
                        "test_heavymix_kernel_matches_oracle",
                        "test_linearity",
                        "test_merge_equals_sum_of_parts"):
        assert must_collect in out.stdout, f"{must_collect} not collected"
    # the property file alone keeps the hypothesis gate
    assert "test_property_encode_any_d" not in out.stdout
