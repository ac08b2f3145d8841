"""Device seconds a step spends in HEAVYMIX's estimates (hash, gather,
median: the device scope ``recover/decode``), on the busiest chip, from
one step profiled after the window (``bench/phases.py``). Nothing to
read where the phase does not run or the program names no phase."""

from bench import phases


def read(run: dict) -> float | None:
    return phases.per_step(run, "recover/decode")
