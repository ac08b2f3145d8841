"""Device seconds a step spends in the Pallas Count-Sketch encode kernel,
found by its kernel name in the trace, on the busiest chip, over the
window's steps. Nothing to read where the kernel does not run."""


def read(run: dict) -> float | None:
    r = run["reduced"]
    s = r["class_s_max"]["encode"]
    return s / len(run["window"]["steps"]) if s > 0 else None
