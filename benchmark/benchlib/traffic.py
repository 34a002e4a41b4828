"""One general traffic generator, driven by a data file.

A traffic mix is a JSON file of parameters under `benchmark/traffic/`:

    loop            "open" (a schedule, whatever the server does) or
                    "closed" (each client sends its next when the last ended)
    arrival         open loop: {"process": "poisson", "rate_per_s": r}
    closed          closed loop: {"clients": n, "pool": m}  (m sizes, cycled)
    prompt_tokens   a length distribution (below)
    output_tokens   a length distribution
    shape_seed      the seed of the *sizes*: lengths and gaps

A length distribution is {"dist": "lognormal", "median", "sigma", "min",
"max"} | {"dist": "uniform", "min", "max"}.

Every `--seed` gets the same sizes and gaps in the same order (drawn from
`shape_seed` and the window's length) with other token ids (and the service
other weights): two seeds do the same work, and a difference between runs
is the system's, not the draw's. A window here holds some sixty requests,
so a tail over them is an order statistic of a handful: their order is
part of the work (PERF.md section 4 has the readings).
"""

from __future__ import annotations

import json
import math
import os
import random
from dataclasses import dataclass, field


@dataclass
class Request:
    index: int
    due_s: float  # open loop: seconds after the window's start; closed: 0
    prompt: list
    max_new_tokens: int


@dataclass
class Schedule:
    loop: str
    clients: int
    requests: list = field(default_factory=list)  # open: by due time; closed: the queue (see refill)


def load(path: str) -> dict:
    with open(path) as f:
        spec = json.load(f)
    if spec.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: 'loop' must be 'open' or 'closed'")
    return spec


def find(traffic_dir: str, name: str) -> str:
    path = os.path.join(traffic_dir, name + ".json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no traffic file {name}.json under {traffic_dir}")
    return path


def draw_length(rng: random.Random, dist: dict) -> int:
    kind = dist["dist"]
    if kind == "uniform":
        return rng.randint(int(dist["min"]), int(dist["max"]))
    if kind == "lognormal":
        x = math.exp(math.log(dist["median"]) + dist["sigma"] * rng.gauss(0.0, 1.0))
        return int(min(dist["max"], max(dist["min"], round(x))))
    raise ValueError(f"unknown length distribution {kind!r}")


def draw_gap(rng: random.Random, arrival: dict) -> float:
    kind = arrival.get("process", "poisson")
    if kind != "poisson":
        raise ValueError(f"unknown arrival process {kind!r}")
    return rng.expovariate(float(arrival["rate_per_s"]))


def _sizes(spec: dict, n: int, rng: random.Random) -> list:
    """n (prompt_len, output_len) pairs from the shape seed."""
    return [(draw_length(rng, spec["prompt_tokens"]), draw_length(rng, spec["output_tokens"])) for _ in range(n)]


def build(spec: dict, seed: int, seconds: float, vocab_size: int, rate_scale: float = 1.0) -> Schedule:
    """The schedule of one run. `rate_scale` multiplies an open loop's rate:
    the sweep that finds the sustained rate uses it; cells leave it at 1."""
    shape = random.Random(int(spec.get("shape_seed", 0)))
    ids = random.Random(int(seed) * 2)

    def make(index: int, due: float, size: tuple) -> Request:
        p_len, o_len = size
        return Request(index, due, [ids.randrange(vocab_size) for _ in range(p_len)], o_len)

    if spec["loop"] == "open":
        arrival = dict(spec["arrival"])
        arrival["rate_per_s"] = float(arrival["rate_per_s"]) * rate_scale
        gaps, t = [], 0.0
        while True:
            g = draw_gap(shape, arrival)
            if t + g >= seconds:
                break
            gaps.append(g)
            t += g
        sizes = _sizes(spec, len(gaps), shape)
        reqs, t = [], 0.0
        for i, (g, size) in enumerate(zip(gaps, sizes)):
            t += g
            reqs.append(make(i, t, size))
        return Schedule("open", 0, reqs)

    closed = spec["closed"]
    sizes = _sizes(spec, int(closed.get("pool", closed["clients"])), shape)
    reqs = [make(i, 0.0, size) for i, size in enumerate(sizes)]
    return Schedule("closed", int(closed["clients"]), reqs)


def refill(schedule: Schedule, spec: dict, seed: int, round_no: int, vocab_size: int) -> list:
    """A closed loop that has used its queue starts over: the same sizes in
    the same order, with new token ids (so nothing is a cached prefix)."""
    ids = random.Random(int(seed) * 2 + 1000003 * round_no)
    base = len(schedule.requests) * round_no
    return [
        Request(base + r.index % len(schedule.requests), 0.0,
                [ids.randrange(vocab_size) for _ in r.prompt], r.max_new_tokens)
        for r in schedule.requests
    ]
