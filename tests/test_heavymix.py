"""HEAVYMIX (Alg. 2): top-k recovery from a summed sketch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import count_sketch as cs
from repro.core import heavymix as hm

CFG = cs.SketchConfig(rows=5, width=2048, seed=1)


def _heavy_vector(d=16384, k=32, scale=50.0, seed=0):
    key = jax.random.PRNGKey(seed)
    g = 0.1 * jax.random.normal(key, (d,))
    hot = jax.random.choice(jax.random.fold_in(key, 1), d, (k,),
                            replace=False)
    vals = scale * (1.0 + jax.random.uniform(jax.random.fold_in(key, 2),
                                             (k,)))
    return g.at[hot].set(vals), set(np.asarray(hot).tolist())


def test_recovers_planted_heavy_set():
    g, hot = _heavy_vector()
    idx, est = hm.heavymix(CFG, cs.encode(CFG, g), k=32, d=g.shape[0])
    got = set(np.asarray(idx).tolist())
    # Count-Sketch recovery is probabilistic (median-of-R under hash
    # collisions): require all but at most one planted coordinate.
    assert len(hot - got) <= 1, sorted(hot - got)
    # estimates at the recovered PLANTED coords are close to true values
    keep = np.asarray([j for j, i in enumerate(np.asarray(idx).tolist())
                       if i in hot])
    np.testing.assert_allclose(np.asarray(est)[keep],
                               np.asarray(g[idx])[keep],
                               rtol=0.3, atol=1.0)


def test_fill_to_k_when_few_heavy():
    g, hot = _heavy_vector(k=4)
    idx, _ = hm.heavymix(CFG, cs.encode(CFG, g), k=64, d=g.shape[0])
    assert len(np.unique(np.asarray(idx))) == 64
    assert hot <= set(np.asarray(idx).tolist())


def test_faithful_random_fill_contains_heavy():
    g, hot = _heavy_vector(k=8)
    idx, _ = hm.heavymix(CFG, cs.encode(CFG, g), k=64, d=g.shape[0],
                         key=jax.random.PRNGKey(7), faithful=True)
    assert hot <= set(np.asarray(idx).tolist())


def test_faithful_fill_is_random_not_greedy():
    g, _ = _heavy_vector(k=8)
    sk = cs.encode(CFG, g)
    i1, _ = hm.heavymix(CFG, sk, 64, g.shape[0],
                        key=jax.random.PRNGKey(1), faithful=True)
    i2, _ = hm.heavymix(CFG, sk, 64, g.shape[0],
                        key=jax.random.PRNGKey(2), faithful=True)
    assert set(np.asarray(i1).tolist()) != set(np.asarray(i2).tolist())


def test_chunked_equals_flat_selection():
    d = hm._CHUNK * 2 + 4097  # force >2 chunks with ragged tail
    key = jax.random.PRNGKey(3)
    g = 0.01 * jax.random.normal(key, (d,))
    hot = jax.random.choice(jax.random.fold_in(key, 4), d, (50,),
                            replace=False)
    g = g.at[hot].set(25.0)
    sk = cs.encode(CFG, g)
    k = 128
    idx_c, est_c = hm._heavymix_chunked(CFG, sk, k, d)
    est_full = cs.decode(CFG, sk, d)
    _, idx_f = jax.lax.top_k(jnp.abs(est_full), k)
    assert set(np.asarray(idx_c).tolist()) == set(np.asarray(idx_f).tolist())
    np.testing.assert_allclose(np.sort(np.asarray(est_c)),
                               np.sort(np.asarray(est_full[idx_f])),
                               rtol=1e-5, atol=1e-5)


def test_workers_select_identical_indices():
    """Every worker holds the same summed sketch -> identical selection
    (the property that lets gs-SGD skip index exchange entirely)."""
    g, _ = _heavy_vector()
    parts = jnp.stack([g * 0.25] * 4)  # 4 workers, sum = g
    sks = [cs.encode(CFG, p) for p in parts]
    summed = cs.merge(*sks)
    sels = [hm.heavymix(CFG, summed, 32, g.shape[0])[0] for _ in range(4)]
    for s in sels[1:]:
        np.testing.assert_array_equal(np.asarray(sels[0]), np.asarray(s))


@pytest.mark.parametrize("path", ["chunked", "flat", "faithful"])
def test_pallas_decode_selects_as_jnp(monkeypatch, path):
    """HEAVYMIX through the Pallas decode kernel (interpret mode) selects
    the indices, with the estimates, of the jnp gather. ``_CHUNK`` is cut
    so that the chunked path runs its traced chunk base and a ragged
    last chunk whose second block lies wholly past d."""
    monkeypatch.setattr(hm, "_CHUNK", 4096)
    d = 3 * 4096 + 1000 if path == "chunked" else 4000
    key = jax.random.PRNGKey(5)
    g = 0.01 * jax.random.normal(key, (d,))
    hot = jax.random.choice(jax.random.fold_in(key, 6), d, (40,),
                            replace=False)
    g = g.at[hot].set(20.0)
    sk = cs.encode(CFG, g)
    kw = dict(key=jax.random.PRNGKey(9), faithful=path == "faithful")
    idx_p, est_p = hm.heavymix(CFG, sk, 64, d, use_pallas=True, **kw)
    idx_j, est_j = hm.heavymix(CFG, sk, 64, d, use_pallas=False, **kw)
    np.testing.assert_array_equal(np.asarray(idx_p), np.asarray(idx_j))
    np.testing.assert_array_equal(np.asarray(est_p), np.asarray(est_j))
