"""Device seconds a step spends routing tokens to experts (the device
scope ``moe_route``: router, sigmoid scores, top-k, gates, load count and
the selection-bias update), on the busiest chip, from one step profiled
after the window (``bench/phases.py``). Nothing to read where the
program names no such scope."""

from bench import phases


def read(run: dict) -> float | None:
    return phases.per_step(run, "moe_route")
