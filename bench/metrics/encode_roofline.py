"""The Pallas encode's share of its roofline, in percent: the least time
the chip could take for the encodes a step asks of one chip (per worker:
read d f32, write rows x width f32, rows x d multiply-adds;
``bench/work.py``), over the encode's device seconds a step on that
chip."""

from bench import work


def read(run: dict) -> float | None:
    r = run["reduced"]
    s = r["class_s_max"]["encode"]
    if s <= 0:
        return None
    t = run["cell"].traffic
    sk = t["sketch"]
    workers_per_chip = t["workers"] / run["chips"]
    flops, nbytes = work.encode_work(run["flat_size"], sk["rows"],
                                     sk["width"])
    t_min = workers_per_chip * work.roofline_seconds(flops, nbytes,
                                                     run["peaks"])
    return 100.0 * t_min / (s / len(run["window"]["steps"]))
