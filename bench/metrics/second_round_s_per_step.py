"""Device seconds a step spends in the exact second round (gather, psum
and scatter of the Top_k values) and the EF residual: the device scope
``recover/second_round``, on the busiest chip, from one step profiled
after the window (``bench/phases.py``). Nothing to read where the phase
does not run or the program names no phase."""

from bench import phases


def read(run: dict) -> float | None:
    return phases.per_step(run, "recover/second_round")
