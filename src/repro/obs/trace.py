"""Low-overhead span tracing for the gs-SGD stack (DESIGN.md §10).

One ``Tracer`` collects nested spans (explicit begin/end or context
manager), instant events, and per-track ids, and exports Chrome/Perfetto
trace-event JSON (load the file at https://ui.perfetto.dev). The tracer is
AMBIENT: instrumented code — ``gs_sgd.exchange_interleaved`` /
``exchange_bucketed``, ``allreduce.tree_allreduce`` rounds, the
``runtime`` heartbeat/elastic/straggler policies — calls
``trace.current()``, which returns the active tracer or the module
``NULL`` singleton. The NULL tracer's span is a shared no-op object and
``sync`` is the identity, so with tracing disabled the instrumented
functions trace into *identical jaxprs* and identical step outputs
(pinned by tests/test_obs.py); no tracer is ever threaded through
signatures.

Span boundaries matter on an async backend: a span's ``sync(x)`` calls
``jax.block_until_ready`` on ``x`` (best-effort — a no-op on jax tracers
and non-arrays), so an *eagerly executed* instrumented step measures real
per-phase device time. Inside ``jax.jit`` spans cannot observe anything
(the python body runs once at trace time); the train driver therefore
runs one un-jitted PROBE step for phase attribution and wraps the jitted
steps in driver-level spans (see launch/train.py).

Under ``jit`` the phases are named on the device instead: ``phase(cat)``
enters ``jax.named_scope(cat)`` together with the ambient span, so every
op the phase emits carries the category in its HLO ``op_name`` metadata,
which the profiler's device trace reports per op. Scopes are always on:
they are metadata and change no op. A live span also opens a
``jax.profiler.TraceAnnotation`` of its name, so while a profiler session
captures, the program's host spans sit on the device trace's clock.

Span taxonomy — the ``cat`` field; the audit and the sim export share it:

    step       one whole training step (driver / sim timeline)
    probe      the eager instrumented step the phase spans live under
    input      host batch build (stream generation, worker reshape)
    forward    forward pass (chunked path; monolithic fwd+bwd = backward)
    backward   backward chunk VJPs / monolithic value_and_grad
    encode     per-bucket sketch encode (+ readiness instants)
    comm       per-bucket sketch all-reduce / per-tree-round sends
    recover    per-bucket decode + heavymix recovery
    optimizer  the segment-wise optimizer sweep
    runtime    heartbeat/elastic/straggler instants
    stall      sim-only: barrier + detection waits

Device scopes of the train step (``SCOPES``), the op-level names of the
same taxonomy:

    forward               the model's forward pass (inside autodiff)
    backward              never entered: ops whose name stack holds
                          ``transpose(...forward...)``; remat recompute
                          lands here (``.../checkpoint/rematted_computation``)
    encode                gradient pack, EF add, Count-Sketch encode, cast
    comm                  sketch merge (psum or tree) / dense psum
    recover/decode        HEAVYMIX estimates: hash, gather, median
    recover/select        heavy threshold, scores, top-k
    recover/second_round  exact values of Top_k (gather, psum, scatter)
                          and the EF residual
    optimizer             mean, norm, clip, unpack, optimizer update

Scopes of block kinds, entered only where a model has them (``BLOCK_SCOPES``;
each holds its part of the forward, its remat recompute and its backward):

    mla                   latent attention: projections, rotary, attention,
                          output projection
    moe_route             router, scores, top-k, gates, load count and the
                          selection-bias update
    moe_dispatch          grouping and ungrouping of the routed rows, the
                          combine
    moe_experts           the routed grouped matmuls and the shared experts

``from_sim(result)`` renders a ``sim.cluster.SimResult`` into the same
schema, so a measured trace and a simulated one for the same RunSpec are
structurally identical (schema-equality is a tier-1 test).
"""

from __future__ import annotations

import contextlib
import json
import time
from typing import Any, Callable, Iterable

TRACE_SCHEMA = "repro.obs/trace@1"

# Phase categories shared by the train probe, the sim export, and
# benchmarks/overlap_audit.py.
PHASES = ("forward", "backward", "encode", "comm", "recover")

# Device scopes of the train step (module docstring).
BLOCK_SCOPES = ("mla", "moe_route", "moe_dispatch", "moe_experts")
SCOPES = ("forward", "backward", "encode", "comm", "recover/decode",
          "recover/select", "recover/second_round",
          "optimizer") + BLOCK_SCOPES


# ---------------------------------------------------------------------------
# The disabled path: one shared no-op span, zero per-call allocation
# ---------------------------------------------------------------------------


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def sync(self, x):
        return x


_NULL_SPAN = _NullSpan()


class _NullTracer:
    """``current()`` when no tracer is active. Every method is a no-op."""

    enabled = False
    __slots__ = ()

    def span(self, name, *, cat="", track="main", args=None):
        return _NULL_SPAN

    def begin(self, name, *, cat="", track="main", args=None):
        return _NULL_SPAN

    def end(self, span):
        return None

    def instant(self, name, *, cat="", track="main", args=None, ts=None):
        return None


NULL = _NullTracer()

_CURRENT: "Tracer | None" = None


def current() -> "Tracer | _NullTracer":
    """The ambient tracer — ``NULL`` (all no-ops) unless one is active."""
    return _CURRENT if _CURRENT is not None else NULL


@contextlib.contextmanager
def phase(cat: str, name: str | None = None):
    """Enter one phase of the step: ``jax.named_scope(cat)`` for the ops
    traced inside (their HLO ``op_name``, read from the device trace) and
    the ambient tracer's span ``name`` (default ``cat``) of category
    ``cat``, which fires only in an eager step. Yields the span."""
    import jax
    with jax.named_scope(cat), current().span(name or cat, cat=cat) as sp:
        yield sp


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


class Span:
    """One open span; close with ``tracer.end(span)`` or the with-block."""

    __slots__ = ("_tr", "name", "cat", "track", "args", "t0", "_ann")

    def __init__(self, tr: "Tracer", name: str, cat: str, track: str,
                 args: dict | None):
        self._tr = tr
        self.name = name
        self.cat = cat
        self.track = track
        self.args = args
        # the same span on the profiler's clock; records only while a
        # profiler session captures
        from jax.profiler import TraceAnnotation
        self._ann = TraceAnnotation(name)

    def sync(self, x):
        """Block until ``x``'s arrays are computed, then return it.

        Best-effort: inside a jit/vmap trace (or on non-array pytrees)
        this is the identity, so instrumented code stays jit-safe.
        """
        try:
            import jax
            jax.block_until_ready(x)
        except Exception:
            pass
        return x

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._tr.end(self)
        return False


class Tracer:
    """Collects spans/instants; clock-injectable for tests and the sim.

    Raw events keep times in SECONDS relative to ``epoch``;
    ``to_chrome``/``save`` convert to the trace-event µs convention.
    """

    enabled = True

    def __init__(self, *, clock: Callable[[], float] = time.perf_counter,
                 epoch: float | None = None, pid: int = 0):
        self._clock = clock
        self.pid = pid
        self.epoch = clock() if epoch is None else epoch
        self.events: list[dict] = []
        self._stacks: dict[str, list[Span]] = {}

    # -- recording ----------------------------------------------------------

    def begin(self, name: str, *, cat: str = "", track: str = "main",
              args: dict | None = None) -> Span:
        sp = Span(self, name, cat, track, args)
        sp._ann.__enter__()
        sp.t0 = self._clock() - self.epoch
        self._stacks.setdefault(track, []).append(sp)
        return sp

    def end(self, span: Span) -> None:
        t1 = self._clock() - self.epoch
        stack = self._stacks.get(span.track, [])
        if not stack or stack[-1] is not span:
            open_names = [s.name for s in stack]
            raise ValueError(
                f"span end out of order on track {span.track!r}: closing "
                f"{span.name!r} but the open stack is {open_names}")
        stack.pop()
        span._ann.__exit__(None, None, None)
        self.events.append({"ph": "X", "name": span.name, "cat": span.cat,
                            "track": span.track, "ts": span.t0,
                            "dur": t1 - span.t0, "args": span.args})

    def span(self, name: str, *, cat: str = "", track: str = "main",
             args: dict | None = None) -> Span:
        """``with tracer.span('encode/b0', cat='encode') as sp: ...``"""
        return self.begin(name, cat=cat, track=track, args=args)

    def instant(self, name: str, *, cat: str = "", track: str = "main",
                args: dict | None = None, ts: float | None = None) -> None:
        t = (self._clock() - self.epoch) if ts is None else ts
        self.events.append({"ph": "i", "name": name, "cat": cat,
                            "track": track, "ts": t, "args": args})

    def add_span(self, name: str, t0: float, t1: float, *, cat: str = "",
                 track: str = "main", args: dict | None = None) -> None:
        """Record a closed span directly (sim export path; times are in
        tracer-relative seconds)."""
        self.events.append({"ph": "X", "name": name, "cat": cat,
                            "track": track, "ts": t0, "dur": t1 - t0,
                            "args": args})

    def open_spans(self) -> list[str]:
        return [s.name for st in self._stacks.values() for s in st]

    # -- ambient activation -------------------------------------------------

    @contextlib.contextmanager
    def activate(self):
        """Install as the ambient ``current()`` tracer for the block."""
        global _CURRENT
        prev = _CURRENT
        _CURRENT = self
        try:
            yield self
        finally:
            _CURRENT = prev

    # -- export -------------------------------------------------------------

    def to_chrome(self, *, spec=None, provenance: dict | None = None,
                  source: str = "train") -> dict:
        """Chrome/Perfetto trace-event JSON with the run's identity
        embedded (schema / source / resolved spec / provenance), so a
        trace file alone is enough to re-price its schedule
        (benchmarks/overlap_audit.py)."""
        if self.open_spans():
            raise ValueError(
                f"cannot export with open spans: {self.open_spans()}")
        tids: dict[str, int] = {}
        out: list[dict] = []
        for e in sorted(self.events, key=lambda e: e["ts"]):
            track = e["track"]
            if track not in tids:
                tids[track] = len(tids)
                out.append({"ph": "M", "name": "thread_name", "pid": self.pid,
                            "tid": tids[track],
                            "args": {"name": track}})
            ev = {"name": e["name"], "cat": e["cat"] or "default",
                  "ph": e["ph"], "ts": e["ts"] * 1e6, "pid": self.pid,
                  "tid": tids[track], "args": e.get("args") or {}}
            if e["ph"] == "X":
                ev["dur"] = e["dur"] * 1e6
            else:
                ev["s"] = "t"
            out.append(ev)
        spec_doc = (spec.to_json() if hasattr(spec, "to_json") else spec)
        return {"schema": TRACE_SCHEMA, "source": source,
                "spec": spec_doc, "provenance": provenance,
                "displayTimeUnit": "ms", "traceEvents": out}

    def save(self, path: str, *, spec=None, provenance: dict | None = None,
             source: str = "train") -> dict:
        doc = self.to_chrome(spec=spec, provenance=provenance, source=source)
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        return doc


# ---------------------------------------------------------------------------
# Validation + chrome-doc helpers (shared by tests and overlap_audit)
# ---------------------------------------------------------------------------


def load(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") != TRACE_SCHEMA:
        raise ValueError(f"not a {TRACE_SCHEMA} document: "
                         f"schema={doc.get('schema')!r}")
    return doc


def _norm_events(doc_or_events) -> list[dict]:
    if isinstance(doc_or_events, Tracer):
        return doc_or_events.events
    if isinstance(doc_or_events, dict):
        return doc_or_events["traceEvents"]
    return list(doc_or_events)


def validate(doc_or_events) -> int:
    """Check span well-formedness; returns the number of spans checked.

    Within each track, "X" spans must be properly nested: any two either
    disjoint or one inside the other (a small relative epsilon absorbs
    float µs rounding). Raises ValueError on overlap. Begin/end pairing
    is enforced at record time (``Tracer.end``) and at export
    (``to_chrome`` refuses open spans), so a serialized doc that loads is
    pair-complete by construction.
    """
    by_track: dict[Any, list[tuple[float, float, str]]] = {}
    for e in _norm_events(doc_or_events):
        if e.get("ph") != "X":
            continue
        key = (e.get("pid"), e["tid"]) if "tid" in e else e.get("track")
        by_track.setdefault(key, []).append(
            (float(e["ts"]), float(e["ts"]) + float(e["dur"]), e["name"]))
    n = 0
    for key, spans in by_track.items():
        spans.sort(key=lambda s: (s[0], -(s[1] - s[0])))
        stack: list[tuple[float, float, str]] = []
        for t0, t1, name in spans:
            eps = 1e-6 * max(1.0, abs(t1))
            while stack and stack[-1][1] <= t0 + eps:
                stack.pop()
            if stack and t1 > stack[-1][1] + eps:
                raise ValueError(
                    f"track {key!r}: span {name!r} [{t0}, {t1}] overlaps "
                    f"{stack[-1][2]!r} [{stack[-1][0]}, {stack[-1][1]}] "
                    "without nesting")
            stack.append((t0, t1, name))
            n += 1
    return n


def spans(doc: dict, cat: str | None = None,
          name_prefix: str | None = None) -> list[dict]:
    """"X" events of a chrome doc, ts/dur converted back to seconds."""
    out = []
    for e in doc["traceEvents"]:
        if e.get("ph") != "X":
            continue
        if cat is not None and e.get("cat") != cat:
            continue
        if name_prefix is not None and not e["name"].startswith(name_prefix):
            continue
        out.append({**e, "ts": e["ts"] / 1e6, "dur": e["dur"] / 1e6})
    return out


def instants(doc: dict, name: str | None = None) -> list[dict]:
    return [{**e, "ts": e["ts"] / 1e6} for e in doc["traceEvents"]
            if e.get("ph") == "i"
            and (name is None or e["name"] == name)]


def phase_totals(doc: dict) -> dict[str, float]:
    """Total seconds per span category."""
    out: dict[str, float] = {}
    for e in spans(doc):
        out[e["cat"]] = out.get(e["cat"], 0.0) + e["dur"]
    return out


def bucket_durations(doc: dict, cat: str, prefix: str) -> list[float]:
    """Per-bucket stage durations from '<prefix>{i}'-named spans, in
    bucket order (e.g. cat='comm', prefix='allreduce/b')."""
    got: dict[int, float] = {}
    for e in spans(doc, cat=cat, name_prefix=prefix):
        try:
            i = int(e["name"][len(prefix):])
        except ValueError:
            continue
        got[i] = got.get(i, 0.0) + e["dur"]
    return [got[i] for i in sorted(got)]


# ---------------------------------------------------------------------------
# Sim timeline -> the same span schema
# ---------------------------------------------------------------------------


def from_sim(result) -> Tracer:
    """Render a ``sim.cluster.SimResult`` into a Tracer.

    Each ``StepRecord`` becomes a cat='step' umbrella span with
    sequential forward / backward / stall / encode / comm / recover
    children (compute split by the config's ``bwd_frac``); replans and
    straggler drops become cat='runtime' instants — the exact shape the
    train driver emits, so sim and measured traces diff structurally.
    Duck-typed on the result object: no sim import, no cycle.
    """
    cfg = result.config
    tr = Tracer(epoch=0.0)
    track = "cluster"
    for r in result.records:
        t0 = r.t_start
        tr.add_span(f"step{r.step}", t0, t0 + r.total, cat="step",
                    track=track,
                    args={"step": r.step, "warmup": False, "p": r.p,
                          "generation": r.generation, "t_step": r.total})
        cur = t0
        parts = (("forward", "forward", r.compute * (1.0 - cfg.bwd_frac)),
                 ("backward", "backward", r.compute * cfg.bwd_frac),
                 ("stall", "stall", r.stall),
                 ("encode", "encode", r.encode),
                 ("comm", "comm", r.comm),
                 ("recover", "recover", r.recover))
        for name, cat, dur in parts:
            if dur > 0.0:
                tr.add_span(name, cur, cur + dur, cat=cat, track=track,
                            args={"step": r.step})
            cur += dur
        for w in r.dropped:
            tr.instant("straggler.drop", cat="runtime", track=track,
                       ts=t0 + r.compute + r.stall,
                       args={"worker": int(w), "step": r.step})
    for rp in result.replans:
        tr.instant("elastic.replan", cat="runtime", track=track,
                   ts=rp["time"],
                   args={k: rp.get(k) for k in
                         ("step", "generation", "p", "failed", "joined",
                          "lr_scale")})
    for w in getattr(result, "watch", None) or []:
        tr.instant(w.get("kind", "watch"), cat="runtime", track=track,
                   ts=w.get("time") or 0.0,
                   args={k: v for k, v in w.items()
                         if k not in ("kind", "time")})
    return tr
