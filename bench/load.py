"""The one traffic generator: a mix file of parameters in, a request
schedule out.

A mix (``bench/traffic/<name>.json``) states its loop, its lengths and,
for an open loop, its arrival process::

    {"loop": "closed", "clients_per_slot": 1.25,
     "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.75,
                "min": 64, "max": 1024},
     "output": {"dist": "uniform", "min": 256, "max": 1024},
     "block": 16, "temperature": 0.0}

    {"loop": "open", "arrivals": "poisson", "rate_per_s": 4.0, ...}

Every seed gets the same work in another order. Requests come in blocks of
``block``; each block holds the same prompt lengths, output lengths and
(open loop) arrival gaps: the inverse CDF of the stated distribution at
the block's ``block`` evenly spaced quantiles. The seed permutes them within
each block and draws the token ids, uniform over the configuration's
vocabulary. So runs with different seeds differ in order and content, not
in the amount of work.

A closed loop opens its window on slots already in the middle of their
answers, as a loop that has run for a while holds them: its first
``in_progress`` requests (one per client) stand for requests caught
part-way. Their answer lengths are the output
distribution weighted by length (a long answer holds its slot longer, so
more of them are caught), each is caught at an evenly spaced share of its
answer, and the part already decoded comes as prompt: prompt length
``p + done``, answer ``o - done``. These are fixed sets too, permuted by
the seed.

The arrival processes are those of the repo's CPU serving benchmark
(Poisson gaps, and bursts of ``burst`` simultaneous arrivals at the same
mean rate), with lengths and ids taken from the mix and the model.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import Iterator, List, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC_DIR = os.path.join(HERE, "traffic")


@dataclasses.dataclass
class Planned:
    """One request of a schedule. ``due_s`` is its offset from the start of
    the window (open loop; 0 for a closed loop, whose clients send on
    completion)."""
    index: int
    prompt: np.ndarray
    max_new: int
    temperature: float
    due_s: float = 0.0


def load_mix(name: str, root: Optional[str] = None) -> dict:
    """The mix ``name``, found by name among the traffic files."""
    path = os.path.join(root or TRAFFIC_DIR, f"{name}.json")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no traffic mix {name!r} (looked for {path})")
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") not in ("closed", "open"):
        raise ValueError(f"mix {name!r}: loop must be 'closed' or 'open'")
    return mix


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles ((i + 0.5) / n) of ``spec``,
    rounded and clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "uniform":
        vals = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def gaps(mix: dict, n: int) -> np.ndarray:
    """One block's arrival gaps, in seconds, in the generator's order."""
    rate = float(mix["rate_per_s"])
    kind = mix.get("arrivals", "poisson")
    if kind == "poisson":
        u = (np.arange(n) + 0.5) / n
        return -np.log1p(-u) / rate
    if kind == "bursty":
        burst = int(mix["burst"])
        g = np.zeros(n)
        g[::burst] = burst / rate
        return g
    raise ValueError(f"unknown arrival process {kind!r}")


def caught(mix: dict, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """Prompt and answer lengths of ``n`` requests caught part-way through
    their answers (see module doc), in the generator's order."""
    grid = np.sort(quantiles(mix["output"], 1024)).astype(np.float64)
    cdf = np.cumsum(grid) / grid.sum()
    u = (np.arange(n) + 0.5) / n
    outs = grid[np.minimum(np.searchsorted(cdf, u), len(grid) - 1)]
    # shares and prompts paired with the answers in one fixed order, the
    # same for every seed
    fixed = np.random.default_rng(0)
    done = np.floor(fixed.permutation(u) * outs).astype(np.int64)
    prompts = fixed.permutation(quantiles(mix["prompt"], n))
    return prompts + done, outs.astype(np.int64) - done


def rng(seed: int, stream: int) -> np.random.Generator:
    # any whole number, however large, maps to its own stream
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def schedule(mix: dict, *, vocab: int, seed: int, max_seq_len: int,
             in_progress: int = 0) -> Iterator[Planned]:
    """Endless request schedule of ``mix`` for ``seed`` (see module doc),
    opening with ``in_progress`` requests caught part-way. Every request
    fits ``max_seq_len`` (prompt + output)."""
    block = int(mix.get("block", 64))
    prompts = quantiles(mix["prompt"], block)
    outputs = quantiles(mix["output"], block)
    if prompts.max() + outputs.max() > max_seq_len:
        raise ValueError(
            f"mix needs {prompts.max() + outputs.max()} positions, the cell "
            f"serves {max_seq_len}")
    arrivals = gaps(mix, block) if mix["loop"] == "open" else None
    order_rng, token_rng = rng(seed, 0), rng(seed, 1)
    temp = float(mix.get("temperature", 0.0))
    t = 0.0
    i = 0
    if in_progress:
        p, o = caught(mix, in_progress)
        order = order_rng.permutation(in_progress)
        for j in order:
            yield Planned(i, token_rng.integers(0, vocab, size=int(p[j]),
                                                dtype=np.int32),
                          int(o[j]), temp)
            i += 1
    while True:
        p = order_rng.permutation(prompts)
        o = order_rng.permutation(outputs)
        a = arrivals                # bursts keep their shape; content moves
        if arrivals is not None and mix.get("arrivals", "poisson") == "poisson":
            a = order_rng.permutation(arrivals)
        for j in range(block):
            if a is not None:
                t += float(a[j])
            yield Planned(i, token_rng.integers(0, vocab, size=int(p[j]),
                                                dtype=np.int32),
                          int(o[j]), temp, t)
            i += 1


def take(mix: dict, n: int, **kw) -> List[Planned]:
    """The first ``n`` requests of ``schedule``."""
    it = schedule(mix, **kw)
    return [next(it) for _ in range(n)]


def clients(mix: dict, slots: int) -> int:
    """Concurrent clients of a closed loop."""
    return max(1, int(math.ceil(mix["clients_per_slot"] * slots)))
