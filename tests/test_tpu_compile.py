"""The sketch kernels compile for a TPU v5e at real widths, without a chip.

Each case lowers a Pallas kernel for a described ``v5e:2x2`` chip
(``jax.experimental.topologies``) at d=2^22 and W=16384 and asserts the
compiled program holds the Mosaic kernel (``tpu_custom_call``) — what the
interpret-mode tests cannot show: Mosaic refuses casts, primitives and
block shapes the interpreter accepts. The topology is described inside a
fixture, never at import, so every test worker collects the same tests and
only the worker that runs this file loads the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import heavymix as hm
from repro.core.count_sketch import SketchConfig
from repro.kernels import dispatch, ops
from repro.kernels.heavymix_topk import heavymix_scores
from repro.kernels.sketch_decode import sketch_decode
from repro.kernels.sketch_encode import sketch_encode

D = 1 << 22
CFG = SketchConfig(rows=5, width=16384, seed=0)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _encode(g):
    return sketch_encode(CFG, g, interpret=False)


CASES = {
    "encode": (_encode, [(D,)]),
    "encode_offset": (
        lambda g: sketch_encode(CFG, g, index_offset=12345, interpret=False),
        [(D,)]),
    "encode_vmap_2_workers": (jax.vmap(_encode), [(2, D)]),
    "decode": (lambda sk: sketch_decode(CFG, sk, D, interpret=False),
               [(CFG.rows, CFG.width)]),
    "heavymix_scores": (
        lambda sk, thr: heavymix_scores(CFG, sk, thr, D, interpret=False),
        [(CFG.rows, CFG.width), ()]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


CELL_D = 75_503_616  # musicgen-large at one layer: the benchmark's flat size


def test_encode_at_cell_size_is_one_named_kernel(one_chip):
    """Two vmapped workers at the benchmark's flat size compile to exactly
    one Mosaic kernel, and its instruction keeps the name ``sketch_encode``,
    which the trace reader matches to time the encode."""
    g = jax.ShapeDtypeStruct((2, CELL_D), jnp.float32, sharding=one_chip)
    text = jax.jit(jax.vmap(_encode)).lower(g).compile().as_text()
    calls = re.findall(r"^\s*(?:ROOT )?%(\S+) = .*custom_call_target="
                       r"\"tpu_custom_call\"", text, flags=re.M)
    assert len(calls) == 1, calls
    assert "sketch_encode" in calls[0], calls


def test_heavymix_at_cell_size_decodes_in_the_kernel(one_chip, monkeypatch):
    """HEAVYMIX for two vmapped workers at the benchmark's flat size takes
    its estimates from the Mosaic decode kernel, which keeps the name
    ``sketch_decode``, and no gather is left in ``recover/decode``. The
    ops layer dispatches as on a TPU (the test process's backend is the
    CPU, whose Pallas default is the interpreter)."""
    monkeypatch.setattr(ops, "_resolve", lambda use_pallas, interpret: (
        dispatch.resolve_dispatch("tpu", use_pallas=use_pallas,
                                  interpret=interpret)))
    sk = jax.ShapeDtypeStruct((2, CFG.rows, CFG.width), jnp.float32,
                              sharding=one_chip)
    fn = jax.vmap(lambda s: hm.heavymix(CFG, s, k=302_014, d=CELL_D,
                                        use_pallas=True))
    text = jax.jit(fn).lower(sk).compile().as_text()
    calls = re.findall(r"^\s*(?:ROOT )?%(\S+) = .*custom_call_target="
                       r"\"tpu_custom_call\"", text, flags=re.M)
    assert any("sketch_decode" in c for c in calls), calls
    gathers = [m.group(1) for m in re.finditer(
        r"= \S+ gather\(.*?op_name=\"([^\"]*)\"", text)]
    assert gathers, "the selection's gathers should be found"
    assert not [g for g in gathers if "recover/decode" in g], gathers
