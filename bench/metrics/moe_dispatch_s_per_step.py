"""Device seconds a step spends moving routed rows (the device scope
``moe_dispatch``: sorting the (token, choice) pairs by held expert,
gathering the rows, ungrouping and the gate-weighted combine), on the
busiest chip, from one step profiled after the window
(``bench/phases.py``). Nothing to read where the program names no such
scope."""

from bench import phases


def read(run: dict) -> float | None:
    return phases.per_step(run, "moe_dispatch")
