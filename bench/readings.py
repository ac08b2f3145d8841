"""Readings the limits of ``correct`` are set from, for one cell, in one
process (the program is compiled once and driven from many seeds).

    python3 bench/readings.py --workload <cell> --seeds 12 --controls 3 \
        --out <file.jsonl>

For each of ``--seeds`` seeds: the program's first steps against the
reference (the lower reading is the largest gap over sound runs). For the
first ``--controls`` of them besides: the control (the reference in
bfloat16, ``bf16``) and each fault planted in the reference
(``bench/reference/train.py``), in the program's place, against the sound
reference of the same seed (the upper readings). A step that returns its
state unchanged reads ``change_gap`` 1 by construction and is not run.
Every reading is judged against the cell's committed limits as a run
judges it. One JSON line per reading goes to ``--out``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import cell as cells  # noqa: E402
from bench import check  # noqa: E402
from bench import program as prg  # noqa: E402
from bench import run  # noqa: E402
from bench.reference import train as rtrain  # noqa: E402


class CachedProgram(prg.Program):
    """The program, compiled once for every seed."""

    compiled_once = None

    def compile_step(self, ts, state, batch):
        cls = type(self)
        if cls.compiled_once is None:
            cls.compiled_once = super().compile_step(ts, state, batch)
        return cls.compiled_once


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = cells.resolve(cells.load_benchmark(ROOT), args.workload, ROOT)
    devs = run.check_devices(cell.chips, require_tpu=True)
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    ref = cells.reference_module(cell.config)
    model = ref.Decoder.from_config(cell.config)
    faults = ["half_batch"] + (["no_exchange"] if
                               cell.traffic["workers"] > 1 else [])
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as out:
        def emit(kind, seed, gaps, **kw):
            correct, _ = check.judge(gaps, cell.limits)
            rec = {"cell": cell.name, "kind": kind, "seed": seed, **gaps,
                   "correct": correct, **kw}
            out.write(json.dumps(rec) + "\n")
            out.flush()
            run.log(json.dumps(rec))

        for i in range(args.seeds):
            seed = args.first_seed + i
            t = time.perf_counter()
            prog = CachedProgram(cell, seed, ref, model)
            read, fed, _ = run.first_steps(prog, cell, ref, model)
            peak = run.device_memory(devs)["peak_bytes"]
            prog.close()
            t_prog = time.perf_counter() - t
            t = time.perf_counter()
            ref_read = rtrain.follow(ref, model, cell.traffic, seed, fed)
            feed = {"feed_faults": check.feed_faults(fed)}
            emit("program", seed, {**check.gaps(read, ref_read), **feed},
                 program_s=t_prog, reference_s=time.perf_counter() - t,
                 peak_bytes=peak, losses=read["losses"],
                 ref_losses=ref_read["losses"],
                 grad_leaves=[check.leaf_gaps(g, ref_read["grad_norms"])
                              for g in read["grad_norms"]],
                 change_leaves=[check.leaf_gaps(c, ref_read["change_norms"])
                                for c in read["change_norms"]])
            if i >= args.controls:
                continue
            for fault in faults + ["bf16"]:
                f_read = rtrain.follow(ref, model, cell.traffic, seed, fed,
                                       fault=fault)
                emit("control_bf16_reference" if fault == "bf16" else
                     f"fault_{fault}", seed,
                     {**check.gaps(f_read, ref_read), **feed})

if __name__ == "__main__":
    main()
