"""The one general generator: events from ``--seed`` and a table of column
distributions (the configuration's ``columns``, which a traffic mix may
override per configuration under its own ``columns`` key). ``zipf`` takes
two optional pairs of keys, both applied after the draw so that a spec
without them uses the random stream as it always did: ``shift_every`` with
``shift_by`` moves the hot set through the keys, ``prefix`` turns the keys
into labels (a deployment's partition key is a string).

Every seed draws the same number of events from the same distributions, so
seeds change the order of the work and not its amount. Events are drawn into
a pool of ``pool_events`` and the stream is the pool repeated, event ``i``
carrying the timestamp ``base_ts + i``: a closed loop has no fixed length, and
a pool keeps set-up the same whatever rate a later change reaches.
"""

from __future__ import annotations

import numpy as np


def seed_rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % (1 << 63))


def _labels(prefix: str, n: int) -> np.ndarray:
    return np.array([f"{prefix}{k}" for k in range(n)], dtype=object)


def draw_column(spec: dict, n: int, rng: np.random.Generator):
    dist = spec["dist"]
    if dist == "randint":       # integers in [low, high), times `scale`
        col = rng.integers(spec["low"], spec["high"], n)
        if "scale" in spec:
            col = col * spec["scale"]
    elif dist == "uniform":     # [low, high) rounded to `decimals`
        col = rng.uniform(spec["low"], spec["high"], n)
        if "decimals" in spec:
            col = np.round(col, spec["decimals"])
    elif dist == "zipf":        # rank r in [0, n_keys) with p ~ 1/(r+1)^a
        n_keys = int(spec["n_keys"])
        ranks = np.arange(1, n_keys + 1, dtype=np.float64)
        p = ranks ** -float(spec["a"])
        col = rng.choice(n_keys, size=n, p=p / p.sum())
        if "shift_every" in spec:   # the hot set moves through the pool:
            # event i's rank r is key (r + shift_by * (i // shift_every))
            step = np.arange(n, dtype=np.int64) // int(spec["shift_every"])
            col = (col + int(spec["shift_by"]) * step) % n_keys
        if "prefix" in spec:        # labels `prefix` + key, as `label` gives
            return _labels(spec["prefix"], n_keys)[col]
    elif dist == "label":       # `prefix` + an integer in [0, n_labels)
        codes = rng.integers(0, spec["n_labels"], n)
        return _labels(spec["prefix"], spec["n_labels"])[codes]
    else:
        raise ValueError(f"unknown column distribution '{dist}'")
    return col.astype(spec["dtype"])


def make_pool(config: dict, config_name: str, traffic: dict, seed: int,
              n: int) -> dict:
    """``{column: array of n}`` in the stream's column order."""
    specs = dict(config["columns"])
    specs.update(traffic.get("columns", {}).get(config_name, {}))
    rng = seed_rng(seed)
    return {name: draw_column(specs[name], n, rng)
            for name in config["stream"]["columns"]}


def expand(pool: dict, n: int) -> dict:
    """The first ``n`` events of the stream (the pool repeated)."""
    size = len(next(iter(pool.values())))
    if n <= size:
        return {k: v[:n] for k, v in pool.items()}
    idx = np.arange(n) % size
    return {k: v[idx] for k, v in pool.items()}
