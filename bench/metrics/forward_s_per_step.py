"""Device seconds a step spends in the model's forward pass: ops under
the program's device scope ``forward`` (not its transpose), on the
busiest chip, from one step profiled after the window
(``bench/phases.py``). Nothing to read where the program names no
phase."""

from bench import phases


def read(run: dict) -> float | None:
    return phases.per_step(run, "forward")
