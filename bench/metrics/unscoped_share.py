"""Share of the busiest chip's busy time in no phase of the program's
device scopes, in percent, from one step profiled after the window
(``bench/phases.py``): ops a change left unnamed, other programs (the
batch build's), loop overhead. Nothing to read where the program names no
phase."""

from bench import phases


def read(run: dict) -> float | None:
    r = phases.capture(run)
    if r is None or r["busy_s"] <= 0:
        return None
    return 100.0 * r["unscoped_s"] / r["busy_s"]
