"""Chip smoke test: gs-SGD training at a published width on TPU v5e.

    python3 chip_smoke.py             one chip (the default)
    python3 chip_smoke.py --chips 4   the four-chip mesh path only

One chip: the compiled Pallas Count-Sketch encode is checked against the
jnp reference on a small input, then musicgen-large at its published width,
cut to ``LAYERS`` layers, trains with two gs-SGD workers on the chip. The
step is built the way ``repro.launch.train`` builds it (``RunSpec`` ->
``make_train_step`` -> ``make_step_fn``): a warm-up step and three timed
steps, each ending in ``block_until_ready``. Every loss must be finite, the
parameters must change at every step, and the compiled step must hold the
Pallas encode (``tpu_custom_call``).

``--chips 4``: the same model with P=4 workers, one per chip on a
``("data",)`` mesh. gs-SGD with the tree all-reduce and the dense psum run
from the same parameters on the same batches; their first-step losses must
agree, both loss histories must be finite, and the gs-SGD step must hold
the tree's ``collective-permute`` rounds.

The script runs in one process and never falls back to the CPU: without a
TPU it exits non-zero and prints no result. Its last line is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "musicgen-large"
LAYERS = 3          # published width; the deepest cut whose two-worker step
                    # compiles under ~14 GiB for a v5e (11.46 GiB at seq 512)
BATCH, SEQ = 8, 512
STEPS = 3           # timed steps after the warm-up step


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def phase(name: str, fn, *args):
    """Run one phase; any exception fails the whole script."""
    print(f"== {name}", flush=True)
    try:
        return fn(*args)
    except Exception:
        traceback.print_exc()
        fail(f"phase {name!r} raised")


def check_encode_kernel():
    """Compiled Pallas encode (whole, offset, vmapped over 2 workers) vs
    the jnp scatter reference at the default sketch geometry."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.count_sketch import SketchConfig
    from repro.kernels import ref
    from repro.kernels.sketch_encode import sketch_encode

    cfg = SketchConfig(rows=5, width=16384, seed=0)
    g = jax.random.normal(jax.random.PRNGKey(1), (2, 1 << 20), jnp.float32)
    cases = {
        "whole": (jax.jit(lambda x: sketch_encode(cfg, x[0])),
                  lambda x: ref.count_sketch_encode(cfg, x[0])),
        "offset": (jax.jit(lambda x: sketch_encode(cfg, x[1],
                                                   index_offset=777)),
                   lambda x: ref.count_sketch_encode(cfg, x[1], offset=777)),
        "vmap": (jax.jit(jax.vmap(lambda v: sketch_encode(cfg, v))),
                 jax.vmap(lambda v: ref.count_sketch_encode(cfg, v))),
    }
    for name, (kern, oracle) in cases.items():
        compiled = kern.lower(g).compile()
        if "tpu_custom_call" not in compiled.as_text():
            fail(f"encode ({name}) did not compile to a Pallas kernel")
        got = np.asarray(compiled(g))
        want = np.asarray(jax.jit(oracle)(g))
        err = float(np.abs(got - want).max())
        scale = float(np.abs(want).max())
        print(f"encode {name}: max|kernel - ref| {err!r} "
              f"(max|ref| {scale!r})", flush=True)
        if not err <= 1e-4 * scale:
            fail(f"encode ({name}) disagrees with the reference")


def _checksums(state) -> list[int]:
    """Exact per-leaf fingerprints of the params: the wrap-around uint32
    sum of their bit patterns, so any changed coordinate shows."""
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves(state["params"])
    sums = [jnp.sum(jax.lax.bitcast_convert_type(a, jnp.uint32),
                    dtype=jnp.uint32) for a in leaves]
    return [int(x) for x in jax.device_get(sums)]


def build_run(spec):
    """Spec -> (ts, compiled step, state, stream); prints what was built."""
    import jax

    from repro.data import LMStream
    from repro.launch import train

    cfg, opt, _, ts = train.build(spec)
    P = spec.cluster.p
    sk = ts.compressor.sketch if hasattr(ts.compressor, "sketch") else None
    print(f"arch {cfg.name}  layers {cfg.n_layers}  d_model {cfg.d_model}  "
          f"P {P}  d {ts.d_local}  compressor {spec.exchange.compressor}"
          + (f"  sketch rows {sk.rows} width {sk.width} k {ts.compressor.k}"
             f"  allreduce {ts.compressor.allreduce_mode}" if sk else ""),
          flush=True)
    stream = LMStream(vocab_size=cfg.vocab_size, seq_len=spec.seq,
                      global_batch=spec.batch, seed=spec.seed)
    state = train.init_state(spec, cfg, opt, ts)
    batch = train.worker_batch(stream, 0, spec)
    t0 = time.perf_counter()
    compiled = train.make_step_fn(ts, P).lower(state, batch).compile()
    print(f"compile seconds {time.perf_counter() - t0!r}", flush=True)
    state = jax.device_put(state, compiled.input_shardings[0][0])
    return ts, compiled, state, stream


def run_steps(spec, compiled, state, stream, steps: int):
    """Warm-up + ``steps`` timed steps; params must change every step."""
    import jax
    import numpy as np

    from repro.launch import train

    losses, times = [], []
    before = _checksums(state)
    for step in range(steps + 1):
        batch = jax.device_put(train.worker_batch(stream, step, spec),
                               compiled.input_shardings[0][1])
        t0 = time.perf_counter()
        state, m = compiled(state, batch)
        jax.block_until_ready((state, m))
        dt = time.perf_counter() - t0
        loss = np.asarray(m["loss"]).reshape(-1)
        after = _checksums(state)
        tag = "warm-up" if step == 0 else "timed"
        print(f"step {step} ({tag}) seconds {dt!r} loss {float(loss[0])!r}",
              flush=True)
        if not np.all(np.isfinite(loss)):
            fail(f"non-finite loss at step {step}: {loss}")
        if after == before:
            fail(f"parameters did not change at step {step}")
        before = after
        losses.append(float(loss[0]))
        if step:
            times.append(dt)
    return state, losses, times


def one_chip():
    import jax

    from repro.api import ClusterSpec, RunSpec

    phase("compiled encode kernel vs reference", check_encode_kernel)
    spec = RunSpec(arch=ARCH, layers=LAYERS, batch=BATCH, seq=SEQ,
                   cluster=ClusterSpec(p=2))
    spec.validate()
    ts, compiled, state, stream = phase("build and compile", build_run, spec)
    if "tpu_custom_call" not in compiled.as_text():
        fail("the compiled step holds no Pallas kernel")
    print("compiled step holds tpu_custom_call (Pallas encode)", flush=True)
    state, losses, times = phase("train", run_steps, spec, compiled, state,
                                 stream, STEPS)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"losses {losses}", flush=True)
    print(f"step seconds {times}", flush=True)
    print(f"peak_bytes_in_use {peak}", flush=True)


def four_chips():
    import dataclasses

    import jax

    from repro.api import ClusterSpec, ExchangeSpec, RunSpec
    from repro.core.allreduce import tree_allreduce_rounds

    if len(jax.devices()) < 4:
        fail(f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    gs = RunSpec(arch=ARCH, layers=LAYERS, batch=BATCH, seq=SEQ,
                 cluster=ClusterSpec(p=4),
                 exchange=ExchangeSpec(allreduce_mode="tree"))
    dense = dataclasses.replace(gs, exchange=ExchangeSpec(compressor="none"))
    out = {}
    for name, spec in (("gs-sgd", gs), ("dense", dense)):
        spec.validate()
        ts, compiled, state, stream = phase(f"build and compile {name}",
                                            build_run, spec)
        n_perm = len(re.findall(r"collective-permute(?:-start)?\(",
                                compiled.as_text()))
        print(f"{name}: collective-permute ops in HLO {n_perm}", flush=True)
        if name == "gs-sgd" and n_perm < tree_allreduce_rounds(4):
            fail(f"the gs-SGD step holds {n_perm} collective-permutes, "
                 f"fewer than the tree's {tree_allreduce_rounds(4)} rounds")
        leaf = jax.tree_util.tree_leaves(state["params"])[0]
        placed = sorted({sh.device.id for sh in leaf.addressable_shards})
        if len(placed) != 4:
            fail(f"{name}: state is on devices {placed}, not one per chip")
        state, losses, times = phase(f"train {name}", run_steps, spec,
                                     compiled, state, stream, STEPS)
        print(f"{name}: losses {losses}", flush=True)
        print(f"{name}: step seconds {times}", flush=True)
        out[name] = losses
        del state, compiled
    a, b = out["gs-sgd"][0], out["dense"][0]
    print(f"first-step loss gs-sgd {a!r} dense {b!r}", flush=True)
    # same params and batch, so the same forward; the two programs are
    # compiled apart and may sum the loss in another order
    if not math.isclose(a, b, rel_tol=1e-5):
        fail("gs-SGD and dense psum disagree on the first-step loss")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:4]]
    print(f"peak_bytes_in_use per chip {peaks}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform!r}")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import configure_compile_cache
    print(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}  "
          f"jax {jax.__version__}  compile cache {configure_compile_cache()}",
          flush=True)
    four_chips() if args.chips == 4 else one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
