"""Serving driver — spec-first (``repro.api.ServeSpec``), engine-backed.

Every knob (batch, prompt/gen lengths, paging, policy, load-test shape)
lives in ``RunSpec.serve`` with generated CLI flags, so ``--dump-spec``/
``--spec`` round-trips carry the full serving config (the old raw
``--batch``/``--prompt-len``/``--gen`` argparse args are these same
flags, now spec-backed). The old demo's tok/s figure silently included
XLA compile time; this driver runs a discarded warmup pass and reports
cold (incl. compile) and steady-state numbers separately.

Modes:

  demo (default)   — submit a batch of identical-shape requests through
                     the continuous-batching ``ServeEngine`` and print
                     the generations + both tok/s numbers.
  --load-test      — replay a seeded Poisson arrival trace (mixed
                     prompt/gen lengths) through CB and the static-batch
                     baseline; write TTFT / per-token latency histograms
                     (p50/p95/p99) + throughput to ``--json`` (default
                     BENCH_serve.json) with provenance stamping.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \
      --batch 4 --prompt-len 32 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --smoke --load-test \
      --requests 24 --rate 100 --json BENCH_serve.json
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.launch.compile_cache import configure_compile_cache
from repro.models.common import ShardCtx
from repro.models.flatten import init_flat_params, make_flat_spec
from repro.serve import Request, ServeEngine
from repro.serve.loadtest import run_load_test
from repro.serve.scheduler import serve_fns


def build(spec):
    cfg = spec.arch_config()
    ctx = ShardCtx(tp=1, tp_axis=None, dtype=jnp.float32)
    fs = make_flat_spec(cfg, 1)
    segs = init_flat_params(cfg, jax.random.PRNGKey(spec.seed), 1, fs)
    return cfg, ctx, fs, segs


def _demo(cfg, ctx, fs, segs, spec) -> dict:
    sv = spec.serve
    rng = np.random.default_rng(spec.seed + 1)
    prompts = [tuple(int(x) for x in
                     rng.integers(1, cfg.vocab_size, sv.prompt_len))
               for _ in range(sv.batch)]
    fns = serve_fns(cfg, ctx, fs)

    def gen_all():
        eng = ServeEngine(cfg, ctx, fs, segs, spec, fns=fns)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new=sv.gen))
        t0 = time.perf_counter()
        comps = eng.run()
        return comps, time.perf_counter() - t0

    # warmup pass pays jit compilation; its timing is reported as "cold"
    # and its outputs discarded — the measured pass is steady-state only
    comps, dt_cold = gen_all()
    comps, dt = gen_all()
    n_tok = sum(len(c.tokens) for c in comps)
    tps, tps_cold = n_tok / dt, n_tok / dt_cold
    print(f"generated {len(comps)}x{sv.gen} tokens: "
          f"steady {dt:.2f}s ({tps:.1f} tok/s), "
          f"cold {dt_cold:.2f}s ({tps_cold:.1f} tok/s incl. compile)")
    for c in comps[:2]:
        print(f"  sample {c.rid}: {c.tokens}")
    return {"tokens": [c.tokens for c in comps], "tok_per_s": tps,
            "tok_per_s_cold": tps_cold}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description="serving driver (DESIGN.md §13)")
    api.add_spec_args(ap, "serve")     # every config flag: repro.api.spec
    ap.add_argument("--spec", default=None, metavar="SPEC.json",
                    help="load a repro.api.RunSpec as the base config "
                         "(explicit flags still override)")
    ap.add_argument("--dump-spec", default=None, metavar="PATH",
                    help="write the fully-resolved RunSpec JSON and "
                         "continue")
    ap.add_argument("--load-test", action="store_true",
                    help="replay a Poisson arrival trace through CB + "
                         "static baseline and write latency histograms")
    ap.add_argument("--json", default="BENCH_serve.json", metavar="PATH",
                    help="load-test report path")
    args = ap.parse_args(argv)
    configure_compile_cache()

    base = api.RunSpec.load(args.spec) if args.spec \
        else api.RunSpec(smoke=True)
    spec = api.apply_args(base, args, "serve")
    spec.validate()
    if args.dump_spec:
        spec.save(args.dump_spec)
        print(f"wrote resolved spec to {args.dump_spec}")

    cfg, ctx, fs, segs = build(spec)
    sv = spec.serve
    print(f"arch {cfg.name}: slots={sv.batch} block_size={sv.block_size} "
          f"max_len={sv.resolved_max_len()} "
          f"cache={'paged' if sv.paged else 'contiguous'} "
          f"policy={sv.policy}")

    if not args.load_test:
        return _demo(cfg, ctx, fs, segs, spec)

    report = run_load_test(cfg, ctx, fs, segs, spec)
    with open(args.json, "w") as f:
        json.dump(report, f, indent=1)
    c, s = report["continuous"], report["static"]
    print(f"wrote {args.json}")
    print(f"  continuous: {c['tokens']} tok in {c['makespan']:.3f}s "
          f"virtual ({c['throughput_tok_per_s']:.1f} tok/s), "
          f"TTFT p99 {c['ttft']['p99']:.4f}s, dropped {c['dropped']}")
    print(f"  static    : {s['tokens']} tok in {s['makespan']:.3f}s "
          f"virtual ({s['throughput_tok_per_s']:.1f} tok/s)")
    print(f"  speedup vs static: {report['speedup_vs_static']:.2f}x, "
          f"tokens match: {report['tokens_match_static']}")
    print(f"  wall: steady {report['wall']['tok_per_s_steady']:.1f} tok/s, "
          f"cold {report['wall']['tok_per_s_cold']:.1f} tok/s "
          f"(incl. compile)")
    return report


if __name__ == "__main__":
    main()
