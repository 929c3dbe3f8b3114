"""Seeded, vectorised generators of the benchmark's schemas.

Each schema module exposes ``generate(seed, scale) -> Data`` and the
prompt templates its queries name. ``Data`` holds every table as numpy
columns (latent ground-truth fields prefixed ``_``) and loads itself into
the program's ``Database`` through the program's own ``add_table``.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Data:
    """Columns of every table, in the program generator's key order.

    ``tables[name][col]`` is a numpy array (ints int64, floats float64
    already rounded as the payload holds them, latent flags bool) or an
    object array of ``str``. ``text[name]`` names the text columns, which
    live in the payload only. ``prompts`` maps a predicate's name to its
    template and ``truths`` maps that template to the oracle's
    ``callable(ctx) -> bool`` over payload rows."""

    tables: dict[str, dict[str, np.ndarray]]
    text: dict[str, set[str]]
    prompts: dict[str, str]
    truths: dict[str, object] = field(default_factory=dict)

    def records(self, name: str) -> list[dict]:
        """The table as payload rows (Python scalars), as the program's
        generator builds them."""
        cols = self.tables[name]
        keys = list(cols)
        return [dict(zip(keys, row))
                for row in zip(*(cols[k].tolist() for k in keys))]

    def permuted(self, seed: int) -> "Data":
        """The same rows, each table in an order shuffled from ``seed``:
        every seed then holds the same sizes and does the same work."""
        rng = rng_for(seed)
        tables = {}
        for name, cols in self.tables.items():
            order = rng.permutation(self.num_rows(name))
            tables[name] = {c: v[order] for c, v in cols.items()}
        return Data(tables=tables, text=self.text, prompts=self.prompts,
                    truths=self.truths)

    def num_rows(self, name: str) -> int:
        """Rows of table ``name``."""
        return len(next(iter(self.tables[name].values())))

    def load(self, db) -> None:
        """Add every table to the program's ``Database`` ``db``."""
        for name in self.tables:
            db.add_table(name, self.records(name),
                         text_columns=self.text.get(name, set()))
        db.truths.update(self.truths)


def generate(schema: str, seed: int, scale: float) -> Data:
    """``Data`` of ``schema`` (a module of this package) from ``seed``."""
    mod = importlib.import_module(f"{__name__}.{schema}")
    return mod.generate(seed, scale)


def rng_for(seed: int) -> np.random.Generator:
    """numpy generator for any integer seed, negative or above 2**63."""
    return np.random.default_rng(int(seed) % 2**64)


def pick(rng, options, n) -> np.ndarray:
    """``n`` uniform draws from ``options`` as an object array of str."""
    return np.asarray(options, dtype=object)[rng.integers(len(options),
                                                           size=n)]


def money(rng, lo, hi, n) -> np.ndarray:
    """``n`` uniform amounts in [lo, hi), rounded to cents."""
    return np.round(rng.uniform(lo, hi, size=n), 2)


def strings(fmt_true: str, fmt_false: str, flags: np.ndarray
            ) -> np.ndarray:
    """Object array of ``fmt.format(i=i)`` per row, by a boolean flag."""
    return np.asarray([(fmt_true if f else fmt_false).format(i=i)
                       for i, f in enumerate(flags.tolist())], dtype=object)
