"""Device seconds a step spends in the optimizer (mean, norm, clip,
unpack, update: the device scope ``optimizer``), on the busiest chip,
from one step profiled after the window (``bench/phases.py``). Nothing to
read where the program names no phase."""

from bench import phases


def read(run: dict) -> float | None:
    return phases.per_step(run, "optimizer")
