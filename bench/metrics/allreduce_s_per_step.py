"""Device seconds a step spends merging the workers' sketches (psum or
tree) or dense gradients: the device scope ``comm``, on the busiest
chip, from one step profiled after the window (``bench/phases.py``).
Nothing to read where the phase does not run or the program names no
phase."""

from bench import phases


def read(run: dict) -> float | None:
    return phases.per_step(run, "comm")
