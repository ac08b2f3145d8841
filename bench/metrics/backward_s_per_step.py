"""Device seconds a step spends in the backward pass: ops whose name
stack holds the transpose of the device scope ``forward``, remat
recompute included, on the busiest chip, from one step profiled after
the window (``bench/phases.py``). Nothing to read where the program names
no phase."""

from bench import phases


def read(run: dict) -> float | None:
    return phases.per_step(run, "backward")
