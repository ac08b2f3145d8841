"""Share of the traced window in which no op runs on the busiest chip, in
percent: 1 - (union of its op intervals) / window."""


def read(run: dict) -> float | None:
    r = run["reduced"]
    return 100.0 * (1.0 - r["busiest"]["busy_s"] / r["window_s"])
