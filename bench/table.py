"""The cells' tables, made from the seed by the benchmark itself.

A configuration names a table and its numbers of categorical and
continuous columns; ``make`` draws ``rows`` rows from the seed.  A column's
shape is fixed by the table's name and the column's place: a categorical
column's number of categories (2-19) and Zipf-like frequencies, a
continuous column's Gaussian mixture of 1-4 modes, a quarter of them with
an exponential tail (as a capital-gain column has).  The seed draws the
rows.  This is the system's own stand-in generator
(``repro.tabular.datasets``), copied so that a change to the system
cannot change what a cell runs: the same name, counts and seed give the
same table.  ``partition_quantity_skew`` deals the rows to clients as
Fed-TGAN §5.3.2 does.
"""
from __future__ import annotations

import zlib

import numpy as np


def make(m: dict, seed: int) -> tuple[np.ndarray, list[str]]:
    """``(rows x columns float64, kinds)``: categorical columns first (ids
    ``0 .. categories-1``), then continuous ones."""
    n = m["rows"]
    rng = np.random.default_rng(seed)
    base = zlib.crc32(m["table"].encode()) % (2 ** 31)
    cols, kinds = [], []
    for j in range(m["categorical_columns"]):
        r = np.random.default_rng(base + j)
        c = int(r.integers(2, 20))
        w = 1.0 / np.arange(1, c + 1) ** r.uniform(0.5, 1.5)
        cols.append(rng.choice(c, size=n, p=w / w.sum()).astype(np.float64))
        kinds.append("categorical")
    for j in range(m["continuous_columns"]):
        r = np.random.default_rng(base + 1000 + j)
        k = int(r.integers(1, 5))
        means, stds = r.uniform(-50, 50, size=k), r.uniform(0.5, 8.0, size=k)
        comp = rng.choice(k, size=n, p=r.dirichlet(np.ones(k) * 2.0))
        x = rng.normal(means[comp], stds[comp])
        if r.uniform() < 0.25:
            mask = rng.uniform(size=n) < 0.1
            x = np.where(mask, x + rng.exponential(30.0, size=n), x)
        cols.append(x)
        kinds.append("continuous")
    return np.stack(cols, axis=1), kinds


def partition_quantity_skew(data: np.ndarray, clients: int, small_rows: int,
                            seed: int) -> list[np.ndarray]:
    """Clients ``0 .. P-2`` hold ``small_rows`` rows drawn without
    replacement, the last client the whole table."""
    rng = np.random.default_rng(seed)
    parts = [data[rng.choice(len(data), size=small_rows, replace=False)]
             for _ in range(clients - 1)]
    return parts + [data.copy()]
