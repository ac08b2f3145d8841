"""Peak device memory in use over the run, as a share of the device's
memory limit, on the fullest chip, in percent (``memory_stats``)."""


def read(run: dict) -> float | None:
    mem = run["memory"]
    shares = [p / lim for p, lim in zip(mem["peak_bytes"], mem["bytes_limit"])
              if p is not None and lim]
    return 100.0 * max(shares) if shares else None
