"""XLA compiles a window step triggers (JAX's backend-compile events over
the window, per step). Every shape is warmed before the window, so what
counts here is the program compiling on its own timed path."""


def read(run: dict) -> float | None:
    win = run["window"]
    return win["compiles"] / len(win["steps"])
