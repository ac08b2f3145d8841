"""Host seconds a window step spends building its batch and handing it to
the device (``train.worker_batch(LMStream)`` and the placement), mean over
the window's steps, by the harness's host clock."""


def read(run: dict) -> float | None:
    steps = run["window"]["steps"]
    return sum(s["input_s"] for s in steps) / len(steps) if steps else None
