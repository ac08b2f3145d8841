"""Benchmark harness: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process, one run, on the chips of the machine it starts on:

1. Refuses (non-zero exit, no result) unless JAX finds a TPU and exactly
   the cell's number of chips.
2. Set-up: builds the step through the program's own entry
   (``bench/program.py``), then drives it from the seed through its first
   three steps on the window's own call and feed. Those steps warm every
   shape the window uses and give the numbers ``correct`` compares.
3. Window: the same loop until the first whole step that ends
   ``--seconds`` after the window opened. With ``--trace 1`` the
   profiler records the window, and the host spans ``input``,
   ``dispatch`` and ``sync`` mark what the host was doing.
4. Reads peak device memory, frees the program's state, runs the plain
   reference over the same seed and batches (``bench/reference``) and
   compares (``bench/check.py``).
5. Prints the compared numbers, each beside its limit, as the last lines
   of standard error, and one JSON result as the last line of standard
   output: the cell's end-to-end metrics with ``--trace 0``, its
   per-layer metrics (and a ``breakdown``) with ``--trace 1``.

JAX's compilation cache is kept in ``<checkout>/.jax_cache``, whatever
the environment says, so that only a cell's first run in a checkout
compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SETUP_STEPS = 3


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Refused(RuntimeError):
    """The run cannot be made here; no result is printed."""


def check_devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) != chips:
        raise Refused(f"the cell asks for {chips} chips; JAX sees "
                      f"{len(devs)}")
    return devs


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (the same arithmetic as
    ``repro.obs.metrics.Histogram``)."""
    v = sorted(values)
    return v[min(len(v) - 1, int(math.ceil(q * len(v))) - 1)]


class CompileCounter:
    """Counts XLA backend compiles from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.n += 1


def run_window(prog, first_step: int, seconds: float) -> dict:
    """Steps from ``first_step`` until the first that ends ``seconds``
    after the window opened. Every step: build and hand off the batch,
    run the compiled step, fetch the loss."""
    import jax
    compiles = CompileCounter()
    steps = []
    step = first_step
    t0 = time.perf_counter()
    while True:
        ts = time.perf_counter()
        with jax.profiler.TraceAnnotation("input"):
            batch = prog.batch(step)
        ti = time.perf_counter()
        with jax.profiler.TraceAnnotation("dispatch"):
            m = prog.dispatch(batch)
        with jax.profiler.TraceAnnotation("sync"):
            loss = prog.loss(m)
        te = time.perf_counter()
        steps.append({"step": step, "input_s": ti - ts, "step_s": te - ts,
                      "loss": loss})
        step += 1
        if te - t0 >= seconds:
            return {"steps": steps, "seconds": te - t0,
                    "compiles": compiles.n}


def first_steps(prog, cell, ref, model) -> tuple[dict, list, float]:
    """Drive the program from the seed through its first steps on the
    window's own call and feed; return what the check compares (losses;
    every worker's first applied gradient and change over the steps, by
    leaf), the global batches fed, and the seconds spent copying for the
    check."""
    import jax
    import numpy as np

    from bench import program as prg
    check_s = 0.0
    t = time.perf_counter()
    p0 = prog.params_host()
    check_s += time.perf_counter() - t
    fed, losses = [], []
    for step in range(SETUP_STEPS):
        batch = prog.batch(step)
        losses.append(prog.loss(prog.dispatch(batch)))
        t = time.perf_counter()
        fed.append({k: np.asarray(jax.device_get(v)).reshape(
            cell.traffic["global_batch"], -1) for k, v in batch.items()})
        if step == 0:
            grad_norms = [prg.adam_grad_norms(
                ref, model, m, cell.traffic["optimizer"]["b1"])
                for m in prog.first_moment()]
        check_s += time.perf_counter() - t
    t = time.perf_counter()
    p3 = prog.params_host()
    read = {"losses": losses, "grad_norms": grad_norms,
            "change_norms": [prg.change_norms(ref, model, a, b)
                             for a, b in zip(p0, p3)]}
    check_s += time.perf_counter() - t
    return read, fed, check_s


def device_memory(devs) -> dict:
    stats = [d.memory_stats() or {} for d in devs]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    limits = [s.get("bytes_limit") for s in stats]
    return {"peak_bytes": peaks, "bytes_limit": limits}


def main(argv=None, *, root: str = ROOT, require_tpu: bool = True,
         hooks=None) -> int:
    """Run one cell; return the exit code. ``hooks`` (tests only) may
    replace the program's class to plant a fault underneath."""
    args = parse(argv)
    for p in (os.path.join(root, "src"), root):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import cell as cells
    cell = cells.resolve(cells.load_benchmark(root), args.workload, root)
    try:
        devs = check_devices(cell.chips, require_tpu)
    except Refused as e:
        log(f"bench: refused: {e}")
        return 2
    import jax
    if devs[0].platform != "cpu":   # XLA:CPU programs are not cached
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))

    from bench import check
    from bench import program as prg
    from bench.reference import train as rtrain

    ref = cells.reference_module(cell.config)
    model = ref.Decoder.from_config(cell.config)
    Program = (hooks or {}).get("Program", prg.Program)
    peak_table = None
    if args.trace:   # an unknown device is an error, not a default
        with open(os.path.join(root, "bench", "peaks.json")) as f:
            peak_table = json.load(f)[devs[0].device_kind]

    # -- set-up: build, then the first steps (the check reads them) -------
    prog = Program(cell, args.seed, ref, model)
    prog_read, fed, check_s = first_steps(prog, cell, ref, model)
    setup_s = time.perf_counter() - T_START - check_s
    log(f"bench: {cell.name} seed {args.seed}: set-up {setup_s:.3f} s "
        f"(check copies {check_s:.3f} s not counted), set-up losses "
        f"{prog_read['losses']}")

    # -- window ------------------------------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    win = run_window(prog, SETUP_STEPS, args.seconds)
    if trace_dir:
        jax.profiler.stop_trace()
    mem = device_memory(devs)
    prog.close()
    del prog
    n = len(win["steps"])
    tokens = cell.traffic["global_batch"] * cell.traffic["seq"]
    tokens_per_s = n * tokens / win["seconds"]
    failed = sum(not math.isfinite(s["loss"]) for s in win["steps"])
    mean = lambda k: sum(s[k] for s in win["steps"]) / n  # noqa: E731
    log(f"bench: window {n} steps in {win['seconds']:.3f} s, "
        f"{tokens_per_s:.1f} tokens/s, step {mean('step_s'):.4f} s of which "
        f"input {mean('input_s'):.4f} s, {win['compiles']} compiles, peak "
        f"bytes {mem['peak_bytes']}")

    # -- the check -----------------------------------------------------------
    t = time.perf_counter()
    ref_read = rtrain.follow(ref, model, cell.traffic, args.seed, fed)
    correct, numbers = check.judge(
        dict(check.gaps(prog_read, ref_read),
             feed_faults=check.feed_faults(fed)), cell.limits)
    correct = correct and failed == 0
    log(f"bench: reference {time.perf_counter() - t:.3f} s, losses "
        f"{ref_read['losses']}")

    # -- metrics -------------------------------------------------------------
    dev = devs[0]
    peaks = [p for p in mem["peak_bytes"] if p is not None]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(peaks) if peaks else None}
    result = {"correct": bool(correct), "attempted": n, "failed": failed}
    if not args.trace:
        values = {"tokens_per_s": tokens_per_s,
                  "step_s_p95": percentile([s["step_s"] for s in
                                            win["steps"]], 0.95),
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        from bench import trace_reduce
        try:
            reduced = trace_reduce.reduce_dir(trace_dir, len(devs))
        finally:
            trace_reduce.remove(trace_dir)
        run = {"cell": cell, "window": win, "tokens_per_s": tokens_per_s,
               "reduced": reduced, "memory": mem, "peaks": peak_table,
               "chips": len(devs), "flat_size": ref.flat_size(model)}
        metrics = {}
        for m in cell.per_layer:
            v = cells.metric_reader(m["name"], root)(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["top_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    result["device"] = device
    result["check"] = numbers
    for name, nv in numbers.items():
        log(f"check {name} {nv['value']!r} limit {nv['limit']!r}")
    log(f"check correct {bool(correct)} (failed steps {failed})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
