"""Reduce a profiler trace of the window to the numbers the metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``. What is taken from it:

- device ops: the events of each TPU plane's ``XLA Ops`` line (and the
  in-flight intervals of its ``Async XLA Ops`` line), each an interval
  named by its HLO instruction;
- host spans: the harness's ``input``, ``dispatch`` and ``sync``
  annotations on the host plane; the traced window runs from the first
  span's start to the last span's end, and every device interval is
  clipped to it.

From those, per device: busy time (the union of op intervals), time by op
class (``classify``), the collective time during which no other op runs
(exposed), and the idle gaps, each named by the host span that overlaps it
most. Per-device numbers are reduced to the mean (``busy_s``, the op
table) or to the busiest device (classes, gaps).

``reduce_events`` works on plain tuples so that the tests can pin it on a
small recorded trace (``tests/bench/data``) without a chip.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import shutil

HOST_SPANS = ("input", "dispatch", "sync")
CLASSES = ("encode", "sort", "collective", "other")
OP_LINES = ("XLA Ops", "Async XLA Ops")

_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|collective-permute|reduce-scatter|all-to-all"
    r"|collective-broadcast)")
_SORT = re.compile(r"^(sort|top-?k)", re.IGNORECASE)
_CONTAINER = re.compile(r"^(while|conditional|call)[.]")


def op_name(text: str) -> str:
    """``fusion.12`` from an op event's HLO text ``%fusion.12 = ...``."""
    if text.startswith("%") and " = " in text:
        return text[1:text.index(" = ")]
    return text


def classify(name: str) -> str:
    """Op class from the op's name (``op_name``):

    - ``encode``: the Pallas Count-Sketch encode kernel, by the name of
      its jitted function (``sketch_encode``);
    - ``sort``: sort and top-k ops (in these cells only HEAVYMIX issues
      them);
    - ``collective``: collective ops, by HLO opcode;
    - ``container``: a while loop, conditional or call, whose body's ops
      are events of their own;
    - ``other``: everything else.
    """
    if "sketch_encode" in name:
        return "encode"
    if _COLLECTIVE.match(name):
        return "collective"
    if _SORT.match(name):
        return "sort"
    if _CONTAINER.match(name):
        return "container"
    return "other"


def union(intervals) -> list:
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(base, cover) -> list:
    """Parts of the disjoint sorted ``base`` not covered by the disjoint
    sorted ``cover``."""
    out, j = [], 0
    for a, b in base:
        cur = a
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                out.append([cur, cover[k][0]])
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            out.append([cur, b])
    return out


def _clip(iv, lo, hi):
    a, b = max(iv[0], lo), min(iv[1], hi)
    return (a, b) if a < b else None


def reduce_events(devices: dict, spans: list) -> dict:
    """devices: {device: [(line, op name, start_s, end_s), ...]} with
    ``line`` one of ``OP_LINES``; spans: [(name, start_s, end_s), ...] of
    the harness's host spans.

    Busy time is the union of the ``XLA Ops`` intervals, loop containers
    included. Class times, the op table and exposure count leaf ops only,
    so that a loop and its body are not counted twice; the collective
    class also takes the in-flight intervals of ``Async XLA Ops``.
    """
    spans = [s for s in spans if s[0] in HOST_SPANS]
    lo = min(s[1] for s in spans)
    hi = max(s[2] for s in spans)
    per_dev, op_time = {}, {}
    for dev, events in devices.items():
        busy, by_class = [], {c: [] for c in CLASSES}
        for line, name, a, b in events:
            iv = _clip((a, b), lo, hi)
            if iv is None:
                continue
            c = classify(name)
            if line != "XLA Ops":
                if c == "collective":
                    by_class[c].append(iv)
                continue
            busy.append(iv)
            if c == "container":
                continue
            by_class[c].append(iv)
            key = f"{c}:{name}"
            op_time[key] = op_time.get(key, 0.0) + iv[1] - iv[0]
        busy = union(busy)
        others = union(iv for c in CLASSES if c != "collective"
                       for iv in by_class[c])
        per_dev[dev] = {
            "busy_s": length(busy),
            "class_s": {c: length(union(by_class[c])) for c in CLASSES},
            "collective_exposed_s": length(subtract(
                union(by_class["collective"]), others)),
            "gaps": [(_gap_name(g, spans), g[1] - g[0])
                     for g in subtract([[lo, hi]], busy)]}
    n = len(per_dev)
    busiest = max(per_dev, key=lambda d: per_dev[d]["busy_s"])
    top = sorted(((k, v / n) for k, v in op_time.items()),
                 key=lambda kv: -kv[1])
    gaps = sorted(per_dev[busiest]["gaps"], key=lambda g: -g[1])
    return {"window_s": hi - lo,
            "busy_s": sum(d["busy_s"] for d in per_dev.values()) / n,
            "busiest": {"busy_s": per_dev[busiest]["busy_s"],
                        "class_s": per_dev[busiest]["class_s"]},
            "class_s_max": {c: max(d["class_s"][c] for d in per_dev.values())
                            for c in CLASSES},
            "collective_exposed_s_max": max(
                d["collective_exposed_s"] for d in per_dev.values()),
            "devices": n,
            "top_ops": [[k, v] for k, v in top],
            "idle_gaps": [[g[0], g[1]] for g in gaps]}


def _gap_name(gap, spans) -> str:
    best, name = 0.0, "none"
    for s, a, b in spans:
        o = min(b, gap[1]) - max(a, gap[0])
        if o > best:
            best, name = o, s
    return name


# ---------------------------------------------------------------------------
# Reading a trace file
# ---------------------------------------------------------------------------


def events_of(profile, n_devices: int) -> tuple[dict, list]:
    """(devices, spans) of a ``jax.profiler.ProfileData``: the op events of
    the first ``n_devices`` TPU planes and the harness's host spans."""
    devices, spans = {}, []
    for plane in profile.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m and int(m.group(1)) < n_devices:
            devices[int(m.group(1))] = [
                (line.name, op_name(e.name), e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9)
                for line in plane.lines if line.name in OP_LINES
                for e in line.events]
        elif plane.name.startswith("/host:"):
            spans += [(e.name, e.start_ns * 1e-9,
                       (e.start_ns + e.duration_ns) * 1e-9)
                      for line in plane.lines for e in line.events
                      if e.name in HOST_SPANS]
    return devices, spans


def reduce_dir(trace_dir: str, n_devices: int) -> dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {paths}")
    devices, spans = events_of(ProfileData.from_file(paths[0]), n_devices)
    if not devices or not spans:
        raise ValueError(f"trace holds {len(devices)} device planes and "
                         f"{len(spans)} host spans")
    return reduce_events(devices, spans)


def remove(trace_dir: str) -> None:
    shutil.rmtree(trace_dir, ignore_errors=True)


def save_events(path: str, devices: dict, spans: list) -> None:
    """Write (devices, spans) as gzipped JSON (the tests' recorded
    trace)."""
    with gzip.open(path, "wt") as f:
        json.dump({"devices": {str(k): v for k, v in devices.items()},
                   "spans": spans}, f)


def load_events(path: str) -> tuple[dict, list]:
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    return ({int(k): [tuple(e) for e in v] for k, v in
             doc["devices"].items()}, [tuple(s) for s in doc["spans"]])
