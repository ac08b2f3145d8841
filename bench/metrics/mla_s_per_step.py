"""Device seconds a step spends in latent attention (the device scope
``mla``: latent projections, rotary, attention and output projection,
with their remat recompute and backward), on the busiest chip, from one
step profiled after the window (``bench/phases.py``). Nothing to read
where the program names no such scope."""

from bench import phases


def read(run: dict) -> float | None:
    return phases.per_step(run, "mla")
