"""Training driver — spec-first (``repro.api.RunSpec``).

The entire run configuration is one typed ``RunSpec``: the argparse block
is GENERATED from the spec fields (one declaration → flag name, type,
default, help — see DESIGN.md §9), ``--spec SPEC.json`` loads a full spec
as the base, and explicitly-passed flags override it. ``--auto-tune``
merges a ``repro.launch.tune`` plan's exchange config into the base spec
through the same path the manual flags take — pinned bit-exact against
passing ``plan.train_argv()`` by hand.

Where the P = ``--workers`` data-parallel workers run follows from the
device count, not from a flag (``make_step_fn``):

  * at least P devices: one worker per device. The step runs under
    ``jax.shard_map`` on a ``("data",)`` mesh of the first P devices, and
    the ``(P, ...)`` state and batch are sharded on their leading axis, so
    the sketch merge is a real cross-device collective.
  * fewer devices: the P workers share device 0 under
    ``jax.vmap(step, axis_name="data")``. The collectives (psum, ppermute
    tree, all_gather) have the same semantics, so the two give the same
    numbers.

Either way the state is donated to the jitted step, so the old and new
state are never live together.

Fault tolerance: checkpoints every --ckpt-every steps (atomic, keep-N,
async), resumes bit-exact with --resume (the data cursor is the step
number); --kill-at simulates a mid-run crash for the restart tests.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --smoke \
      --workers 4 --steps 50 --compressor gs-sgd
  PYTHONPATH=src python -m repro.launch.train --spec examples/specs/qwen3_smoke.json
  PYTHONPATH=src python -m repro.launch.train --resume ...
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro import api, obs
from repro import ckpt as ckpt_lib
from repro.api import RunSpec
from repro.core.gs_sgd import make_state
from repro.data import LMStream
from repro.launch.compile_cache import configure_compile_cache
from repro.models.flatten import init_flat_params


def build(spec: RunSpec):
    """cfg/opt/ma/TrainStep from the spec — the one construction path."""
    cfg = spec.arch_config()
    opt = spec.make_optimizer()
    ma = spec.mesh_axes()
    ts = spec.make_train_step(opt=opt, dtype=jnp.float32)
    if ts.n_buckets > 1:
        sizes = ts.compressor.spec.sizes
        print(f"bucketed exchange: {ts.n_buckets} buckets "
              f"(sizes {list(sizes)}), "
              f"overlap={'on' if spec.exchange.overlap else 'off'}")
    if ts.bwd_chunks:
        ready = list(ts.plan.readiness) if ts.plan is not None else None
        print(f"backward-interleaved readiness: {ts.bwd_chunks} chunk(s), "
              f"bucket readiness {ready}")
    return cfg, opt, ma, ts


def workers_on_mesh(P: int) -> bool:
    """True when P > 1 workers get a device each (module docstring)."""
    return 1 < P <= len(jax.devices())


def make_step_fn(ts, P: int):
    """Jit ``ts.fn`` over P workers with the state donated: one worker per
    device on a ``("data",)`` mesh when ``workers_on_mesh(P)``, else
    vmapped on one device. State, batch and metrics carry a leading P axis
    when P > 1."""
    if P == 1:
        return jax.jit(ts.fn, donate_argnums=0)
    if not workers_on_mesh(P):
        return jax.jit(jax.vmap(ts.fn, axis_name="data"), donate_argnums=0)
    sharded = worker_sharding(P)
    lead = sharded.spec

    def per_device(state, batch):
        # each device holds a (1, ...) block of the (P, ...) layout
        one = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)  # noqa: E731
        out = ts.fn(one(state), one(batch))
        return jax.tree_util.tree_map(lambda a: a[None], out)

    return jax.jit(
        jax.shard_map(per_device, mesh=sharded.mesh, in_specs=(lead, lead),
                      out_specs=(lead, lead), check_vma=False),
        in_shardings=(sharded, sharded), out_shardings=(sharded, sharded),
        donate_argnums=0)


def worker_sharding(P: int) -> NamedSharding:
    """The ``(P, ...)`` layout on the mesh path: leading axis over a
    ``("data",)`` mesh of the first P devices."""
    mesh = Mesh(np.array(jax.devices()[:P]), ("data",))
    return NamedSharding(mesh, PartitionSpec("data"))


def init_state(spec: RunSpec, cfg, opt, ts):
    """Step-0 state from ``spec.seed``: every worker starts from the same
    replica, stacked on a leading P axis when P > 1. On the mesh path each
    device receives only its own worker's block, so no device ever holds
    the P stacked copies."""
    params = init_flat_params(cfg, jax.random.PRNGKey(spec.seed), 1, ts.fs)
    state = make_state(params, opt, ts.compressor, ts.d_local, cfg=cfg)
    P = spec.cluster.p
    if P > 1 and workers_on_mesh(P):
        sharded = worker_sharding(P)
        devices = list(sharded.mesh.devices.flat)

        def stack(a):
            blocks = [jax.device_put(a[None], d) for d in devices]
            return jax.make_array_from_single_device_arrays(
                (P,) + a.shape, sharded, blocks)
        state = jax.tree_util.tree_map(stack, state)
    elif P > 1:
        state = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (P,) + a.shape), state)
    return state


def worker_batch(stream: LMStream, step: int, spec: RunSpec) -> dict:
    """The global batch of ``step``, split into P worker rows when P > 1.
    Host spans ``stream`` and ``reshape`` (cat ``input``) under an active
    ``obs`` tracer."""
    tr = obs.trace.current()
    with tr.span("stream", cat="input"):
        gb = stream.global_batch_at(step)
    P = spec.cluster.p
    if P == 1:
        return gb
    with tr.span("reshape", cat="input"):
        return jax.tree_util.tree_map(
            lambda a: a.reshape((P, spec.batch // P) + a.shape[1:]), gb)


def resolve_spec(args) -> RunSpec:
    """base (--spec file or defaults) <- --auto-tune exchange <- CLI flags."""
    base = RunSpec.load(args.spec) if args.spec else RunSpec()
    if args.auto_tune:
        from repro.tune import TunePlan
        plan = TunePlan.load(args.auto_tune)
        base = dataclasses.replace(
            base, exchange=plan.train_exchange(base.exchange))
        print(f"auto-tune {args.auto_tune}: " + " ".join(plan.train_argv()))
    spec = api.apply_args(base, args, "train")
    if args.auto_tune:
        # only the fields train_exchange() actually merges are "tuned" —
        # flags like --microbatch never shadow the plan
        shadowed = [f for f in ("compressor", "buckets", "bwd_chunks",
                                "sketch")
                    if getattr(spec.exchange, f)
                    != getattr(base.exchange, f)]
        if shadowed:
            print("note: explicit flags override the plan's exchange "
                  "config: " + ", ".join(shadowed))
    spec.validate()
    return spec


def _ef_norm(state, P: int) -> float:
    """l2 norm of the error-feedback residual (worker 0's copy under vmap)."""
    tot = 0.0
    for leaf in jax.tree_util.tree_leaves(state.get("ef", {})):
        if leaf.size == 0:
            continue
        x = leaf[0] if P > 1 else leaf
        tot += float(jnp.vdot(x, x).real)
    return math.sqrt(tot)


def _predicted(spec: RunSpec) -> dict:
    """Sim-priced step for the trace@2 ``predicted`` block: the jitter-free
    ``replay.predict_step`` on this spec's cluster (the pinned single-step
    oracle), so a trace carries its own sim-vs-measured comparison."""
    try:
        from repro.sim import replay
        cfg = spec.sim_config()
        r = replay.predict_step(
            cfg.method, cfg.d, cfg.p, buckets=cfg.buckets,
            bwd_chunks=cfg.bwd_chunks, k=cfg.k, rows=cfg.rows,
            width=cfg.width, shape=cfg.shape, topology=cfg.topology,
            link=cfg.link, intra_link=cfg.intra_link,
            group_size=cfg.group_size, overlap=cfg.overlap,
            fuse_encode=cfg.fuse_encode, t_compute=cfg.compute.mean,
            bwd_frac=cfg.bwd_frac,
            wire_dtype_bytes=cfg.wire_dtype_bytes,
            net=spec.cluster.network())
        return {"step_time": r["step_time"], "exposed_comm": r["comm"],
                "hidden_comm": max(0.0, r["comm_serial"] - r["comm"]),
                "encode": r["encode"], "comm": r["comm"],
                "recover": r["recover"]}
    except Exception as e:  # the trace is still useful without the oracle
        return {"error": str(e)}


def _recovery_probe(ts, seed: int) -> float | None:
    """heavymix recovery-error probe on the run's RESOLVED per-bucket
    sketch geometry: 1 - captured l2 mass on a seeded heavy-tailed probe
    (the ``tune/cost.py`` error proxy, here measuring the run as built).
    None for non-sketch compressors."""
    try:
        import numpy as np

        from repro.core import compression as comp
        from repro.core import count_sketch as cs
        from repro.core import heavymix as hm
        from repro.tune.cost import probe_gradient
        if isinstance(ts.compressor, comp.BucketedCompressor):
            parts = list(zip(ts.compressor.parts, ts.compressor.spec.sizes))
        else:
            parts = [(ts.compressor, ts.d_local)]
        scale = min(1.0, (1 << 14) / max(1, ts.d_local))
        missed = total = 0.0
        for i, (c, d_b) in enumerate(parts):
            if not hasattr(c, "sketch"):
                return None
            d_p = max(64, int(round(d_b * scale)))
            k_p = max(1, min(d_p, int(round(c.k * scale))))
            w_p = min(int(c.sketch.width), max(64, 1 << int(math.floor(
                math.log2(max(c.sketch.width * scale, 64))))))
            u = probe_gradient(d_p, seed=seed + i)
            cfg = cs.SketchConfig(rows=c.sketch.rows, width=w_p,
                                  seed=c.sketch.seed)
            idx, _ = hm.heavymix(cfg, cs.encode(cfg, u), k_p, d_p)
            tot = float(np.sum(u.astype(np.float64) ** 2))
            cap = float(np.sum(np.asarray(u)[np.asarray(idx)]
                               .astype(np.float64) ** 2))
            missed += max(0.0, tot - cap)
            total += tot
        return missed / total if total > 0 else 0.0
    except Exception:
        return None


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description="gs-SGD training driver")
    api.add_spec_args(ap, "train")     # every config flag: repro.api.spec
    ap.add_argument("--spec", default=None, metavar="SPEC.json",
                    help="load a repro.api.RunSpec as the base config "
                         "(explicit flags still override)")
    ap.add_argument("--dump-spec", default=None, metavar="PATH",
                    help="write the fully-resolved RunSpec JSON and "
                         "continue (CI asserts train/simulate/tune "
                         "resolve a shared spec identically)")
    ap.add_argument("--auto-tune", default=None, metavar="PLAN.json",
                    help="merge a repro.launch.tune plan's exchange config "
                         "into the base spec (bit-exact vs passing the "
                         "same flags manually)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a repro.tune/trace@2 calibration trace "
                         "(strict superset of trace@1: + warmup tags, "
                         "quality metrics, provenance), consumable by "
                         "repro.launch.tune --calibrate; a .jsonl path "
                         "streams one record per line")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--kill-at", type=int, default=None,
                    help="simulate a crash after this step (tests)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    configure_compile_cache()

    spec = resolve_spec(args)
    if args.dump_spec:
        spec.save(args.dump_spec)
        print(f"wrote resolved spec to {args.dump_spec}")

    cfg, opt, ma, ts = build(spec)
    P = spec.cluster.p
    watchdog = None
    if spec.watch.enabled:
        from repro.tune.watch import Watchdog
        watchdog = Watchdog(spec)   # raises now if the compressor can't be
        w = spec.watch              # re-planned (sim-replayable methods only)
        print(f"watchdog armed: warmup={w.warmup} delta={w.delta} "
              f"threshold={w.threshold} window={w.window} "
              f"budget={w.replan_budget}")
    stream = LMStream(vocab_size=cfg.vocab_size, seq_len=spec.seq,
                      global_batch=spec.batch, seed=spec.seed)

    state = init_state(spec, cfg, opt, ts)
    step_fn = make_step_fn(ts, P)
    if P > 1:
        print(f"{P} workers: " + ("one per device on a 'data' mesh"
                                  if workers_on_mesh(P)
                                  else "vmapped on one device"))

    start = 0
    saver = None
    if spec.ckpt_dir:
        saver = ckpt_lib.AsyncCheckpointer(spec.ckpt_dir, keep=3)
        if args.resume and ckpt_lib.latest_step(spec.ckpt_dir) is not None:
            state, meta = ckpt_lib.restore(spec.ckpt_dir, state)
            state = jax.tree_util.tree_map(jnp.asarray, state)
            start = meta["step"]
            print(f"resumed from step {start}")

    history = []
    records = []
    stats = None
    if args.json:
        from repro.core import compression as comp
        stats = comp.static_comm_stats(ts.compressor, ts.d_local, P)

    # --trace: the ambient repro.obs tracer. Spans cannot fire inside the
    # jitted step, so the driver runs ONE eager probe step (output
    # discarded — real-run numerics untouched) under the tracer for phase
    # attribution, plus cheap wall-clock "step" umbrella spans around every
    # jitted call. Tracing off → obs.NULL everywhere → the jaxpr and the
    # step outputs are byte-identical to a build without --trace.
    tracer = obs.Tracer() if spec.trace else None
    tnull = tracer if tracer is not None else obs.NULL
    prov = obs.provenance(spec) if (spec.trace or args.json) else None
    met = obs.Metrics() if args.json else None
    # backend compiles by function over the run (trace@2 ``compiles/*``)
    compiles_at = (obs.compile_counts().snapshot()["counters"]
                   if args.json else None)
    probe_at = None
    if tracer is not None:
        # probe AFTER the warmup step when the run is long enough, so the
        # probe's eager dispatch isn't confounded with jit compilation
        probe_at = start + 1 if spec.steps - start > 1 else start

    def save_trace() -> None:
        if tracer is None:
            return
        doc = tracer.save(spec.trace, spec=spec, provenance=prov,
                          source="train")
        print(f"wrote {spec.trace} ({len(doc['traceEvents'])} events)")

    def dump_trace() -> None:
        """repro.tune/trace@2 — per-step wall time + static CommStats +
        warmup tags + quality metrics + provenance; a strict superset of
        trace@1, consumed unchanged by repro.launch.tune --calibrate."""
        if not args.json:
            return
        ex = spec.exchange
        sk = ex.sketch.resolve(ts.d_local)
        model = {"arch": cfg.name, "p": P, "d": ts.d_local,
                 "compressor": ex.compressor,
                 "buckets": ex.buckets,
                 "bwd_chunks": ex.bwd_chunks,
                 "overlap": ex.overlap,
                 "k": sk.k, "rows": sk.rows,
                 "width": sk.width, "seed": spec.seed,
                 "bytes_per_step": stats.bytes_out,
                 "rounds_per_step": stats.rounds}
        pred = _predicted(spec)
        if "step_time" in pred:
            met.gauge("exposed_comm").set(pred["exposed_comm"])
            met.gauge("hidden_comm").set(pred["hidden_comm"])
        per = getattr(stats, "per_bucket", None)
        if per:   # wire bytes per bucket over the whole capture
            for i, s in enumerate(per):
                met.counter(f"bytes_wire/b{i}").inc(
                    s.bytes_out * P * len(records))
        for name, n in obs.compile_counts().snapshot()["counters"].items():
            if n > compiles_at.get(name, 0):
                met.counter(name).inc(n - compiles_at.get(name, 0))
        err = _recovery_probe(ts, spec.seed)
        if err is not None:
            met.gauge("recovery_error_probe").set(err)
        doc = obs.trace2_doc(model=model, records=records, metrics=met,
                             provenance=prov, predicted=pred)
        obs.dump(doc, args.json)
        print(f"wrote {args.json} ({len(records)} records)")

    t0 = time.time()
    replanned_at = None   # next step recompiles -> tag it warmup
    for step in range(start, spec.steps):
        batch = worker_batch(stream, step, spec)
        if step == probe_at:
            # eager (un-jitted) replay of this step's inputs: per-phase
            # spans fire as ops dispatch; the result is DISCARDED, so the
            # real jitted step below sees bit-identical state
            probe_fn = (jax.vmap(ts.fn, axis_name="data") if P > 1
                        else ts.fn)
            with tracer.activate():
                with tracer.span("probe", cat="probe",
                                 args={"step": step}) as sp:
                    sp.sync(probe_fn(state, batch))
        warm = step == start or replanned_at == step - 1
        t_step0 = time.time()
        with tnull.span(f"step{step}", cat="step",
                        args={"step": step, "warmup": warm}):
            state, m = step_fn(state, batch)
            loss = float(m["loss"][0] if P > 1 else m["loss"])
        t_step = time.time() - t_step0
        history.append(loss)
        if args.json:
            bw = stats.bytes_out * P
            records.append({
                "step": step, "t_step": t_step, "loss": loss,
                "rounds": stats.rounds, "bytes": stats.bytes_out,
                "warmup": warm,
                "grad_norm": float(m["grad_norm"][0] if P > 1
                                   else m["grad_norm"]),
                "ef_residual_norm": _ef_norm(state, P),
                "bytes_wire": bw,
                "compression_ratio": (ts.d_local * 4.0 / stats.bytes_out
                                      if stats.bytes_out else None)})
            met.counter("bytes_wire").inc(bw)
            met.counter("rounds").inc(stats.rounds)
            if not warm:
                met.histogram("t_step").observe(t_step)
        if watchdog is not None:
            new = watchdog.on_step(
                {"step": step, "t_step": t_step, "warmup": warm, "p": P},
                now=time.time() - t0)
            if new is not None:
                ev = watchdog.log[-1]
                print(f"watchdog: re-planned at step {step} -> "
                      f"{ev['choice']} (predicted step "
                      f"{ev['predicted'] * 1e3:.2f}ms vs current "
                      f"{ev['current'] * 1e3:.2f}ms, gain {ev['gain']:.1%})")
                spec = new
                cfg, opt, ma, ts = build(spec)
                # error-feedback carries over only when the new exchange
                # keeps its pytree shape; a geometry change (bucket count,
                # sketch size) resets the accumulator
                new_ef = (ts.compressor.init(ts.d_local)
                          if ts.compressor is not None
                          else jnp.zeros((0,), jnp.float32))
                if P > 1:
                    new_ef = jax.tree_util.tree_map(
                        lambda a: jnp.broadcast_to(a, (P,) + a.shape),
                        new_ef)
                old_l = jax.tree_util.tree_leaves(state["ef"])
                new_l = jax.tree_util.tree_leaves(new_ef)
                keep = (jax.tree_util.tree_structure(state["ef"])
                        == jax.tree_util.tree_structure(new_ef)
                        and len(old_l) == len(new_l)
                        and all(a.shape == b.shape and a.dtype == b.dtype
                                for a, b in zip(old_l, new_l)))
                if not keep:
                    print("watchdog: error-feedback reset "
                          "(exchange geometry changed)")
                    state = {**state, "ef": new_ef}
                step_fn = make_step_fn(ts, P)
                if args.json:
                    from repro.core import compression as comp
                    stats = comp.static_comm_stats(ts.compressor,
                                                   ts.d_local, P)
                replanned_at = step
        if step % args.log_every == 0 or step == spec.steps - 1:
            print(f"step {step:5d}  loss {loss:.4f}  "
                  f"({(time.time() - t0):.1f}s)")
        if saver and (step + 1) % spec.ckpt_every == 0:
            saver.save(step + 1, state, {"loss": loss})
        if args.kill_at is not None and step + 1 >= args.kill_at:
            print(f"simulated crash at step {step + 1}")
            if saver:
                saver.wait()
            dump_trace()
            save_trace()
            return {"history": history, "crashed_at": step + 1}
    if saver:
        saver.save(spec.steps, state, {"loss": history[-1]})
        saver.wait()
    dump_trace()
    save_trace()
    out = {"history": history, "final_loss": history[-1]}
    if watchdog is not None:
        out["watch"] = list(watchdog.log)
    print(json.dumps({"final_loss": history[-1],
                      "steps": len(history)}))
    return out


if __name__ == "__main__":
    main()
