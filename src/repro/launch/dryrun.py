import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

This is how the distribution config is proven coherent without hardware:
512 placeholder host devices stand in for 2 TPU v5e pods; every cell's step
function is ``jax.jit(shard_map(...)).lower(*abstract_args).compile()`` with
ShapeDtypeStruct stand-ins (no allocation). A sharding mismatch, a
compile-time OOM, or an unsupported collective fails the cell — those are
bugs in the system, not in the dry-run.

Outputs per cell (written to experiments/dryrun/<arch>__<shape>__<mesh>.json):
  memory_analysis  — arg/output/temp/peak bytes (per addressable set)
  cost_analysis    — HLO FLOPs + bytes accessed
  collectives      — per-kind wire bytes parsed from the optimized HLO
                     (the roofline's collective term reads these)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""

import argparse
import json
import math
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, DP_MODE, TRAIN_OVERRIDES
from repro.configs.shapes import SHAPES, applicable, skip_reason
from repro.core.gs_sgd import (MeshAxes, make_serve_fns, make_train_step)
from repro.launch import specs as sp
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_production_mesh, mesh_axes_of
from repro.models.flatten import make_flat_spec
from repro.optim import make as make_opt

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

_COLL_RE = re.compile(
    r"=\s+(?P<ty>\([^)]*\)|[a-z0-9]+\[[^\]]*\][^ ]*)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

# wire bytes per device as a multiple of the RESULT buffer size
_WIRE_FACTOR = {
    "all-reduce": lambda g: 2.0 * (g - 1) / g,
    "all-gather": lambda g: (g - 1) / g,
    "reduce-scatter": lambda g: float(g - 1),
    "all-to-all": lambda g: (g - 1) / g,
    "collective-permute": lambda g: 1.0,
    "collective-broadcast": lambda g: 1.0,
}


def _type_bytes(ty: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(ty):
        n = math.prod(int(x) for x in dims.split(",") if x) if dims else 1
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def parse_collectives(hlo_text: str, pod_boundary: int = 256) -> dict:
    """Sum per-kind wire bytes (per device) from optimized HLO text."""
    per_kind: dict[str, dict] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        rb = _type_bytes(m.group("ty"))
        g = 1
        crosses = None
        gb = _GROUPS_BRACE_RE.search(line)
        gi = _GROUPS_IOTA_RE.search(line)
        if gb:
            ids = [int(x) for x in gb.group(1).split(",")]
            g = len(ids)
            crosses = (min(ids) < pod_boundary <= max(ids))
        elif gi:
            g = int(gi.group(2))
            crosses = g > pod_boundary if "T(" not in line else None
        wire = rb * _WIRE_FACTOR[op](max(g, 1))
        slot = per_kind.setdefault(op, {"count": 0, "result_bytes": 0.0,
                                        "wire_bytes": 0.0,
                                        "pod_crossing_wire_bytes": 0.0,
                                        "group_sizes": {}})
        slot["count"] += 1
        slot["result_bytes"] += rb
        slot["wire_bytes"] += wire
        if crosses:
            slot["pod_crossing_wire_bytes"] += wire
        slot["group_sizes"][str(g)] = slot["group_sizes"].get(str(g), 0) + 1
    total = sum(k["wire_bytes"] for k in per_kind.values())
    cross = sum(k["pod_crossing_wire_bytes"] for k in per_kind.values())
    return {"per_kind": per_kind, "total_wire_bytes": total,
            "pod_crossing_wire_bytes": cross}


# ---------------------------------------------------------------------------
# Cell builders
# ---------------------------------------------------------------------------


def build_train_cell(cfg, mesh, ma: MeshAxes, dp_mode: str):
    ov = TRAIN_OVERRIDES.get(cfg.name, {})
    opt = make_opt(ov.get("optimizer", "adamw"))
    fs = make_flat_spec(cfg, ma.tp)
    case = SHAPES["train_4k"]
    b_loc = case.global_batch // ma.dp_size
    mb = ov.get("microbatch", None)
    if mb is None:  # ~16k tokens per accumulation slice per device
        mb = max(1, min(b_loc, 16384 // case.seq_len))
    ts = make_train_step(
        cfg, ma, opt, dp_mode=dp_mode,
        compressor_name=ov.get("compressor", "gs-sgd"),
        compressor_kw=ov.get("compressor_kw",
                             dict(k=65536, rows=5, width=2 ** 17)),
        remat=True, microbatch=mb, fs=fs)

    state = sp.state_specs_global(
        fs, ma, dp_mode, mesh, opt, ts.d_local,
        with_ef=ts.compressor is not None,
        ef_dtype=jnp.dtype(ov.get("ef_dtype", "float32")))
    batch = sp.batch_specs_global(cfg, ma, mesh,
                                  global_batch=case.global_batch,
                                  seq_len=case.seq_len, with_labels=True)
    in_specs = (sp.shard_map_specs(state), sp.shard_map_specs(batch))
    out_specs = (sp.shard_map_specs(state), {"loss": P(), "grad_norm": P()})
    fn = jax.jit(
        jax.shard_map(ts.fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False),
        donate_argnums=(0,))
    return fn, (state, batch)


def build_serve_cell(cfg, mesh, ma: MeshAxes, dp_mode: str, case):
    fs = make_flat_spec(cfg, ma.tp)
    prefill, decode = make_serve_fns(cfg, ma, dp_mode=dp_mode, fs=fs)
    params = sp.param_specs_global(fs, ma, dp_mode, mesh, dtype=jnp.float32)
    p_specs = sp.shard_map_specs(params)
    cache = sp.cache_specs_global(cfg, ma, mesh,
                                  global_batch=case.global_batch,
                                  t_cache=case.seq_len)
    c_specs = sp.shard_map_specs(cache)
    bp0 = sp._batch_pspec(ma, case.global_batch, 0)   # (GB,) vectors
    bp1 = sp._batch_pspec(ma, case.global_batch, 1)   # (GB, S) matrices
    row_axis = tuple(bp0)[0] if tuple(bp0) else None

    if case.kind == "prefill":
        batch = sp.batch_specs_global(cfg, ma, mesh,
                                      global_batch=case.global_batch,
                                      seq_len=case.seq_len, with_labels=False)
        out_specs = (P(row_axis, "model"), c_specs)
        fn = jax.jit(
            jax.shard_map(prefill, mesh=mesh,
                          in_specs=(p_specs, sp.shard_map_specs(batch),
                                    c_specs),
                          out_specs=out_specs, check_vma=False),
            donate_argnums=(2,))
        return fn, (params, batch, cache)

    # decode: one token against a case.seq_len cache
    toks = sp._sds(mesh, (case.global_batch, 1), jnp.int32, bp1)
    kv_len = sp._sds(mesh, (), jnp.int32, P())
    args = [params, toks, kv_len, cache]
    in_specs = [p_specs, bp1, P(), c_specs]
    if cfg.family == "vlm":
        ck = sp._sds(mesh, (case.global_batch, cfg.n_cross_tokens,
                            cfg.d_model), jnp.bfloat16,
                     sp._batch_pspec(ma, case.global_batch, 2))
        args.append(ck)
        in_specs.append(ck.sharding.spec)

    def dec(p, t, kl, c, *extra):
        return decode(p, t, kl, c, cross_kv=extra[0] if extra else None)

    out_specs = (bp0, c_specs)
    fn = jax.jit(
        jax.shard_map(dec, mesh=mesh, in_specs=tuple(in_specs),
                      out_specs=out_specs, check_vma=False),
        donate_argnums=(3,))
    return fn, tuple(args)


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape: str, mesh_kind: str, *,
             save: bool = True) -> dict:
    cfg = ARCHS[arch]
    case = SHAPES[shape]
    if not applicable(cfg, shape):
        return {"arch": arch, "shape": shape, "mesh": mesh_kind,
                "status": "skipped", "reason": skip_reason(cfg, shape)}
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    ma = mesh_axes_of(mesh)
    dp_mode = DP_MODE[arch]
    t0 = time.time()
    if case.kind == "train":
        fn, args = build_train_cell(cfg, mesh, ma, dp_mode)
    else:
        fn, args = build_serve_cell(cfg, mesh, ma, dp_mode, case)

    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    t1 = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    n_dev = mesh.devices.size
    coll = parse_collectives(compiled.as_text(),
                             pod_boundary=256 if mesh_kind == "multi" else 10**9)
    result = {
        "arch": arch, "shape": shape, "mesh": mesh_kind, "status": "ok",
        "dp_mode": dp_mode, "n_devices": n_dev,
        "compile_seconds": round(t1 - t0, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.peak_memory_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "cost": {
            "flops": cost.get("flops", 0.0),
            "bytes_accessed": cost.get("bytes accessed", 0.0),
        },
        "collectives": coll,
    }
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        path = os.path.join(OUT_DIR, f"{arch}__{shape}__{mesh_kind}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=2)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES),
                    help="shape case (default: all)")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()
    configure_compile_cache()

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                tag = f"{arch} x {shape} x {mesh_kind}"
                try:
                    r = run_cell(arch, shape, mesh_kind)
                except Exception:
                    failures.append(tag)
                    print(f"[FAIL] {tag}\n{traceback.format_exc()}")
                    continue
                if r["status"] == "skipped":
                    print(f"[SKIP] {tag}: {r['reason']}")
                else:
                    mem = r["memory"]  # per-device (SPMD executable) stats
                    print(f"[ OK ] {tag}: compile {r['compile_seconds']}s, "
                          f"flops {r['cost']['flops']:.3e}, "
                          f"peak {mem['peak_bytes'] / 2**30:.2f} GiB/dev "
                          f"(args {mem['argument_bytes'] / 2**30:.2f} "
                          f"temp {mem['temp_bytes'] / 2**30:.2f}), "
                          f"coll {r['collectives']['total_wire_bytes'] / 2**20:.1f} MiB")
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: {failures}")


if __name__ == "__main__":
    main()
