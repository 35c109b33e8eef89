"""The one general generator: events from ``--seed`` and a table of column
distributions (the configuration's ``columns``, which a traffic mix may
override per configuration under its own ``columns`` key).

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
        ranks = np.arange(1, spec["n_keys"] + 1, dtype=np.float64)
        p = ranks ** -float(spec["a"])
        col = rng.choice(spec["n_keys"], size=n, p=p / p.sum())
    elif dist == "label":       # `prefix` + an integer in [0, n_labels)
        codes = rng.integers(0, spec["n_labels"], n)
        names = np.array([f"{spec['prefix']}{k}"
                          for k in range(spec["n_labels"])], dtype=object)
        return names[codes]
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
