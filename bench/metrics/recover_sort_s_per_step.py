"""Device seconds a step spends in sort and top-k ops (in these cells only
HEAVYMIX recovery issues them), on the busiest chip, over the window's
steps. The decode gather that precedes them is not counted."""


def read(run: dict) -> float | None:
    s = run["reduced"]["class_s_max"]["sort"]
    return s / len(run["window"]["steps"]) if s > 0 else None
