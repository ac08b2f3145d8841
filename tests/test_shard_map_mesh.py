"""Real-mesh shard_map execution (subprocess with 8 virtual host devices).

Complements test_tp.py's vmap simulation: proves the SAME step functions,
spec builders and gather closures run under ``jax.jit(jax.shard_map(...))``
on an actual (2, 2, 2) ('pod','data','model') mesh — sharded inputs, real
NamedSharding state, donation — and that a (2,2) single-pod mesh produces
the same numbers as the vmap path (collective-semantics equivalence).

Runs in a subprocess because XLA device count is locked at first jax init.
"""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import SMOKES
from repro.core.gs_sgd import MeshAxes, make_state, make_train_step
from repro.launch import specs as sp
from repro.launch.mesh import mesh_axes_of
from repro.models.flatten import SEG_NAMES, init_flat_params
from repro.optim import make as make_opt
import sys
sys.path.insert(0, "tests")
from test_tp import shard_segs

cfg = SMOKES["qwen3-4b"]
opt = make_opt("sgdm", lr=5e-2, momentum=0.9)
GB, S = 4, 16
key = jax.random.PRNGKey(0)
toks = jax.random.randint(jax.random.PRNGKey(1), (GB, S), 0, cfg.vocab_size)
batch = {"tokens": toks, "labels": toks}

# ---- reference: vmap-simulated dp=4 (tp=1) — matches mesh pod*data=4 -----
ma_ref = MeshAxes(tp=1, data=4, tp_axis=None, data_axis="data")
ts_ref = make_train_step(cfg, ma_ref, opt, dp_mode="dp",
                         compressor_name="dense",
                         remat=False, dtype=jnp.float32)
p0 = init_flat_params(cfg, key, 1, ts_ref.fs)
st = make_state(p0, opt, ts_ref.compressor, ts_ref.d_local)
st = jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (4,) + a.shape), st)
vb = jax.tree_util.tree_map(lambda a: a.reshape((4, 1) + a.shape[1:]), batch)
ref_losses = []
fn = jax.jit(jax.vmap(ts_ref.fn, axis_name="data"))
for _ in range(3):
    st, m = fn(st, vb)
    ref_losses.append(float(m["loss"][0]))

# ---- real mesh: (2,2,2) pod x data x model --------------------------------
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
ma = mesh_axes_of(mesh)
# dense exchange: selection-free, so the trajectory must match the sim
# EXACTLY (gs-sgd equivalence is covered by the vmap tests; its per-shard
# top-k makes cross-tp comparisons approximate by construction).
ts = make_train_step(cfg, ma, opt, dp_mode="dp", compressor_name="dense",
                     remat=False, dtype=jnp.float32)
fs2, segs2 = shard_segs(cfg, key, 2)   # per-model-rank locals, stacked
# globals: concat model shards for *_s; rep segs are the full vector
gparams = {}
for k in SEG_NAMES:
    if k.endswith("_r"):
        gparams[k] = jnp.concatenate([segs2[k][r] for r in range(2)],
                                     axis=-1)
    else:
        gparams[k] = jnp.concatenate([segs2[k][r] for r in range(2)],
                                     axis=-1)
pspecs = sp.seg_pspecs(ma, "dp")
gparams = {k: jax.device_put(
    v, jax.NamedSharding(mesh, pspecs[k])) for k, v in gparams.items()}
opt_state = {k: opt.init(v.shape) for k, v in gparams.items()}
opt_state = {k: jax.device_put(v, jax.NamedSharding(mesh, pspecs[k]))
             for k, v in opt_state.items()}
n_dev = 8
ef = jnp.zeros((n_dev * ts.d_local,), jnp.float32)
all_axes = ("pod", "data", "model")
ef = jax.device_put(ef, jax.NamedSharding(mesh, P(all_axes)))
state = {"params": gparams, "opt": opt_state, "ef": ef,
         "step": jnp.int32(0)}
state_specs = {"params": pspecs, "opt": {k: pspecs[k] for k in pspecs},
               "ef": P(all_axes), "step": P()}
batch_specs = {"tokens": P(("pod", "data"), None),
               "labels": P(("pod", "data"), None)}
gbatch = {k: jax.device_put(v, jax.NamedSharding(mesh, batch_specs[k]))
          for k, v in batch.items()}
step = jax.jit(jax.shard_map(
    ts.fn, mesh=mesh, in_specs=(state_specs, batch_specs),
    out_specs=(state_specs, {"loss": P(), "grad_norm": P()}),
    check_vma=False))
mesh_losses = []
with jax.set_mesh(mesh):
    for _ in range(3):
        state, m = step(state, gbatch)
        mesh_losses.append(float(m["loss"]))

print(json.dumps({"ref": ref_losses, "mesh": mesh_losses}))
"""


@pytest.mark.slow
def test_shard_map_multipod_matches_vmap_sim(tmp_path):
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath("src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    # same dp=4 split (pod-major row order == sim worker order): the full
    # 3-step trajectory must agree across execution substrates.
    import numpy as np
    np.testing.assert_allclose(data["ref"], data["mesh"], rtol=2e-4,
                               atol=2e-4)
