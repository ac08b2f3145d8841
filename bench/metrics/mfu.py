"""The whole step's share of the chips' peak: model FLOPs a token
(forward and backward, ``bench/work.py``; recomputation not counted) times
the window's tokens a second, over chips times the bf16 peak of the device
kind (``bench/peaks.json``), in percent."""

from bench import work


def read(run: dict) -> float | None:
    cell = run["cell"]
    flops = work.model_flops_per_token(cell.config, cell.traffic["seq"])
    peak = run["chips"] * run["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops * run["tokens_per_s"] / peak
