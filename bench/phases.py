"""Device seconds of each phase of the train step, from a profiler trace.

The program names the phases of its step with ``jax.named_scope``, in the
taxonomy of its ``repro.obs`` spans: ``forward``, ``encode``, ``comm``,
``recover/decode``, ``recover/select``, ``recover/second_round``,
``optimizer``, and ``recover`` around the whole recovery stage;
``backward`` is never entered but is the transpose of ``forward``. XLA
keeps each op's name stack as the ``op_name`` metadata of the compiled
step, where transforms wrap it: ``vmap(transpose(jvp(forward)))/...``,
``.../while/body/recover/select/top_k``. ``phase_of`` maps a name stack to
its innermost phase.

An op event of the device trace is named by its HLO instruction; the
compiled step's text (``scope_paths``) gives that instruction's name
stack. ``reduce_phases`` then takes, on the busiest chip, the union of
each phase's leaf-op intervals; ``unscoped_s`` is the rest of the busy
time (ops of no phase, other programs, loop overhead), so the phases and
``unscoped_s`` sum to the busy time.

The harness reduces the window's trace with ``trace_reduce`` and removes
it before the metrics are read, so ``capture`` profiles one more step of
the cell's program after the window: the same build, compiled step and
feed (``bench/program.py``), with an ``obs`` tracer active so that the
program's host spans name the idle gaps. Its result is cached per run
for the metric readers (``bench/metrics/*_s_per_step.py``,
``unscoped_share``). Where the program names no phase (a build without
the scopes), ``capture`` returns None without running a step; where the
compiled step's names are stale (``stale_scopes``), it reads nothing.
The phases are the program's own list, ``repro.obs.SCOPES``.
"""

from __future__ import annotations

import functools
import glob
import os
import re
import sys
import tempfile
import time
import traceback

from bench import trace_reduce as tr

_WRAP = re.compile(r"^([A-Za-z_][\w.\-]*)\((.*)\)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=%]+)\s*=\s.*?"
                    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_LOC = re.compile(r'loc\("([^"]*)"')


@functools.lru_cache(maxsize=None)
def phases() -> tuple:
    """The program's device scopes (``repro.obs.SCOPES``) and ``recover``,
    the recovery stage around its sub-scopes; none where the program has
    no such list (a build without the scopes)."""
    try:
        from repro.obs import SCOPES
    except ImportError:
        return ()
    return tuple(SCOPES) + ("recover",)


def _components(op_name: str) -> list:
    """``op_name`` split at the ``/`` outside parentheses."""
    out, depth, cur = [], 0, ""
    for ch in op_name:
        if ch == "/" and depth == 0:
            out.append(cur)
            cur = ""
            continue
        depth += (ch == "(") - (ch == ")")
        cur += ch
    return out + [cur]


def phase_of(op_name: str) -> str | None:
    """Innermost phase of an HLO ``op_name``, or None.

    A component ``t1(t2(...(scope)))`` is the scope under transforms
    ``t1, t2, ...``; a scope may itself hold ``/`` (``recover/decode``).
    ``forward`` under a ``transpose`` (in its own component or an outer
    one) is ``backward``. The last match along the path wins.
    """
    sub = {p.split("/")[1] for p in phases() if "/" in p}
    top = {p for p in phases() if "/" not in p} - {"backward"}
    tokens, transposed = [], False
    for comp in _components(op_name):
        m = _WRAP.match(comp)
        while m:
            transposed |= m.group(1) == "transpose"
            comp = m.group(2)
            m = _WRAP.match(comp)
        tokens += [(t, transposed) for t in comp.split("/")]
    found = None
    for i, (t, tp) in enumerate(tokens):
        if t not in top:
            continue
        if t == "forward":
            found = "backward" if tp else "forward"
        elif t == "recover" and i + 1 < len(tokens) \
                and tokens[i + 1][0] in sub:
            found = f"recover/{tokens[i + 1][0]}"
        else:
            found = t
    return found


def scope_paths(hlo_text: str) -> dict:
    """{HLO instruction name: op_name} of a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def stale_scopes(lowered_text: str, compiled_text: str) -> set:
    """Phases that one of a step's lowered module (``as_text(debug_info=
    True)``, each op's name stack in its location) and its compiled text
    (``op_name`` metadata) names and the other does not. JAX's persistent
    compile cache leaves the metadata out of its key, so a step compiled
    before a scope moved comes back from the cache with the old names."""
    def named(paths):
        return {phase_of(p) for p in paths} - {None}
    return named(_LOC.findall(lowered_text)) ^ named(
        scope_paths(compiled_text).values())


def reduce_phases(devices: dict, spans: list) -> dict:
    """devices: {device: [(line, op name, start_s, end_s[, op_name
    path]), ...]} as ``trace_reduce.events_of`` gives them, with the op's
    name stack as an optional fifth field; spans: [(name, start_s,
    end_s), ...] of host spans, the harness's (``trace_reduce.HOST_SPANS``,
    which fix the window) and the program's.

    On the busiest chip: ``phase_s`` (union of each phase's leaf-op
    intervals, each leaf in its innermost phase), ``unscoped_s`` (busy
    time outside every phase) and the idle gaps, each named by the
    innermost host span that overlaps it most.
    """
    window = [s for s in spans if s[0] in tr.HOST_SPANS]
    lo = min(s[1] for s in window)
    hi = max(s[2] for s in window)
    best = None
    for events in devices.values():
        busy, by_phase = [], {p: [] for p in phases()}
        for e in events:
            iv = tr._clip((e[2], e[3]), lo, hi)
            if iv is None or e[0] != "XLA Ops":
                continue
            busy.append(iv)
            ph = phase_of(e[4]) if len(e) > 4 and e[4] else None
            if ph is not None and tr.classify(e[1]) != "container":
                by_phase[ph].append(iv)
        busy = tr.union(busy)
        busy_s = tr.length(busy)
        if best is None or busy_s > best["busy_s"]:
            phase_s = {p: tr.length(tr.union(v))
                       for p, v in by_phase.items()}
            best = {"busy_s": busy_s, "phase_s": phase_s,
                    "unscoped_s": busy_s - sum(phase_s.values()),
                    "gaps": tr.subtract([[lo, hi]], busy)}
    gaps = sorted(([_gap_name(g, spans), g[1] - g[0]] for g in best.pop(
        "gaps")), key=lambda g: -g[1])
    return dict(best, window_s=hi - lo, idle_gaps=gaps)


def _gap_name(gap, spans) -> str:
    """The span that overlaps ``gap`` most; of spans that overlap it
    equally, the shortest (the innermost)."""
    best, name = (0.0, 0.0), "none"
    for s, a, b in spans:
        o = min(b, gap[1]) - max(a, gap[0])
        if o > 0 and (o, a - b) > best:
            best, name = (o, a - b), s
    return name


# ---------------------------------------------------------------------------
# Reading a trace file
# ---------------------------------------------------------------------------


def module_name(hlo_text: str) -> str:
    """``jit_train_step`` from ``HloModule jit_train_step, ...``."""
    return hlo_text.split(None, 2)[1].rstrip(",")


def events_of(profile, n_devices: int, paths: dict, module: str,
              span_names) -> tuple[dict, list]:
    """(devices, spans) of a ``jax.profiler.ProfileData``, as
    ``trace_reduce.events_of`` gives them, with each op's name stack as a
    fifth field: ``paths`` (``scope_paths``) of an op that runs inside an
    event of ``module`` on the plane's ``XLA Modules`` line, else "" (an
    op of another program may share an instruction name; a plane without
    that line maps every op). Host spans are the harness's and those
    named in ``span_names``."""
    devices, spans = {}, []
    wanted = set(tr.HOST_SPANS) | set(span_names)
    for plane in profile.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m and int(m.group(1)) < n_devices:
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = [(e.start_ns, e.start_ns + e.duration_ns)
                    for e in lines.get("XLA Modules", [])
                    if e.name.split("(")[0] == module]
            evs = []
            for ln in tr.OP_LINES:
                for e in lines.get(ln, []):
                    name = tr.op_name(e.name)
                    inside = "XLA Modules" not in lines or any(
                        a <= e.start_ns < b for a, b in mods)
                    evs.append((ln, name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9,
                                paths.get(name, "") if inside else ""))
            devices[int(m.group(1))] = evs
        elif plane.name.startswith("/host:"):
            spans += [(e.name, e.start_ns * 1e-9,
                       (e.start_ns + e.duration_ns) * 1e-9)
                      for line in plane.lines for e in line.events
                      if e.name in wanted]
    return devices, spans


# ---------------------------------------------------------------------------
# One profiled step after the window
# ---------------------------------------------------------------------------

SEED = 0        # the step's program and timing do not depend on the data
_CACHE: dict = {}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def profile_step(cell, chips: int) -> tuple:
    """Build the cell's program (``bench/program.py``) from ``SEED``,
    profile the window's first step (batch build, dispatch, loss fetch)
    with an ``obs`` tracer active, and return (devices, spans, steps) for
    ``reduce_phases``; (None, None, 0) where the compiled step names no
    phase. Raises where the compiled step's names are stale
    (``stale_scopes``)."""
    import jax
    from jax.profiler import ProfileData

    from bench import cell as cells
    from bench import program as prg
    from bench.run import SETUP_STEPS
    from repro import obs

    class Program(prg.Program):
        """The cell's program, keeping its lowered step."""

        def compile_step(self, ts, state, batch):
            self.lowered = self._train.make_step_fn(ts, self.P).lower(
                state, batch)
            return self.lowered.compile()

    ref = cells.reference_module(cell.config)
    prog = Program(cell, SEED, ref, ref.Decoder.from_config(cell.config))
    trace_dir = None
    try:
        text = prog.compiled.as_text()
        paths = scope_paths(text)
        if not any(phase_of(p) for p in paths.values()):
            return None, None, 0
        stale = stale_scopes(prog.lowered.as_text(debug_info=True), text)
        if stale:
            raise ValueError(
                f"the compiled step and the program disagree on the phases "
                f"{sorted(stale)}: the step came from a compile cache "
                f"filled before a scope moved; clear the cache")
        tracer = obs.Tracer()
        compiles = obs.compile_counts()
        before = compiles.snapshot()["counters"]
        trace_dir = tempfile.mkdtemp(prefix="bench-phases-")
        jax.profiler.start_trace(trace_dir)
        try:
            with tracer.activate():
                with jax.profiler.TraceAnnotation("input"):
                    batch = prog.batch(SETUP_STEPS)
                with jax.profiler.TraceAnnotation("dispatch"):
                    m = prog.dispatch(batch)
                with jax.profiler.TraceAnnotation("sync"):
                    prog.loss(m)
        finally:
            jax.profiler.stop_trace()
        after = compiles.snapshot()["counters"]
        log("bench: compiles in the profiled step: " + str(
            {k: v - before.get(k, 0) for k, v in after.items()
             if v > before.get(k, 0)}))
        path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        devices, spans = events_of(
            ProfileData.from_file(path), chips, paths, module_name(text),
            {e["name"] for e in tracer.events})
        if not devices:
            raise ValueError(f"the trace holds no TPU plane of {chips}")
        return devices, spans, 1
    finally:
        prog.close()
        if trace_dir:
            tr.remove(trace_dir)


def capture(run: dict) -> dict | None:
    """``reduce_phases`` of one step of ``run``'s cell profiled after the
    window (module docstring), with ``steps``; None where the program
    names no phase or the capture fails. Computed once per run."""
    hit = _CACHE.get(id(run))
    if hit is not None and hit[0] is run:
        return hit[1]
    out = None
    try:
        t = time.perf_counter()
        devices, spans, steps = profile_step(run["cell"], run["chips"])
        if steps:
            out = dict(reduce_phases(devices, spans), steps=steps)
            log(f"bench: phases of one step, profiled after the window in "
                f"{time.perf_counter() - t:.1f} s: busy {out['busy_s']:.4f} "
                f"s, {out['phase_s']}, unscoped {out['unscoped_s']:.4f} s, "
                f"gaps {out['idle_gaps'][:5]}")
    except Exception as e:   # a metric reads nothing; the run goes on
        log(f"bench: phase capture failed: {e!r}\n"
            + traceback.format_exc())
    _CACHE.clear()
    _CACHE[id(run)] = (run, out)
    return out


def per_step(run: dict, phase: str) -> float | None:
    """Device seconds a step in ``phase``, or None where it does not run."""
    r = capture(run)
    if r is None or r["phase_s"].get(phase, 0) <= 0:
        return None
    return r["phase_s"][phase] / r["steps"]


def main(argv=None) -> int:
    """Profile one step of a cell on the chip and print its phases:

        python3 -m bench.phases --workload <cell> [--save P]

    ``--save`` writes the step's events with ``trace_reduce.save_events``
    (the recorded steps of ``tests/bench/data``)."""
    import argparse
    import json

    ap = argparse.ArgumentParser(description=main.__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)

    from bench import cell as cells
    from bench import run as harness
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    cell = cells.resolve(cells.load_benchmark(harness.ROOT), args.workload,
                         harness.ROOT)
    harness.check_devices(cell.chips, require_tpu=True)
    devices, spans, steps = profile_step(cell, cell.chips)
    if not steps:
        log("bench: the compiled step names no phase")
        return 1
    if args.save:
        tr.save_events(args.save, devices, spans)
    print(json.dumps(dict(reduce_phases(devices, spans), steps=steps)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
