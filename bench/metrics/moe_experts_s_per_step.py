"""Device seconds a step spends in the experts' matmuls (the device scope
``moe_experts``: the held experts' grouped SwiGLU over the rows routed to
them and the shared experts), on the busiest chip, from one step
profiled after the window (``bench/phases.py``). Nothing to read where
the program names no such scope."""

from bench import phases


def read(run: dict) -> float | None:
    return phases.per_step(run, "moe_experts")
