"""The one generator of every traffic mix (`bench/traffic/<mix>.json`).

A mix is data:

- `images`: the image source, `{"source": <name>, ...}`. The source is
  `bench/images/<name>.py`, whose `make(seed, n, **rest)` returns `n`
  inputs of one shape and dtype from the seed; the other keys are its
  arguments. The program is called on a batch of those inputs as they
  are, so a source decides the input's shape and type.
- `batch`: inputs per request.
- `in_flight`: the most requests outstanding at once.
- `rate_per_s` (optional): an open loop, one request arriving every
  1 / `rate_per_s` seconds and timed from its arrival. Without it the
  loop is closed: a request is sent as soon as fewer than `in_flight`
  are outstanding.
- `pool_requests`: how many distinct requests the host pool holds.
- `check_requests`: how many answered requests the correctness check
  draws.

The pool is made on the host in set-up; request i is pool entry
i mod `pool_requests`, so every seed sends the same sizes at the same
times and only the inputs differ.
"""
from __future__ import annotations

import json
import os

import numpy as np

from bench import BENCH, load_module

KEYS = {"images", "batch", "in_flight", "pool_requests", "check_requests"}
OPTIONAL = {"rate_per_s"}


def load(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if not KEYS <= set(mix) <= KEYS | OPTIONAL:
        raise ValueError(f"traffic {name}: keys {sorted(mix)}, expected "
                         f"{sorted(KEYS)} and optionally {sorted(OPTIONAL)}")
    return mix


def make_pool(mix: dict, seed: int) -> np.ndarray:
    """(pool_requests, batch, ...) inputs from `seed`."""
    args = dict(mix["images"])
    source = load_module(os.path.join(BENCH, "images",
                                      args.pop("source") + ".py"))
    n, b = mix["pool_requests"], mix["batch"]
    flat = np.asarray(source.make(seed, n * b, **args))
    return flat.reshape((n, b) + flat.shape[1:])
