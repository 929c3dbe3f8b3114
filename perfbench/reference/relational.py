"""Plain numpy evaluation of a template over the generated data.

A relation is the row ids of each base table it joins (``{table: ids}``);
column values are read from the generated columns on demand. Semantic
predicates are answered by the latent truth (oracle cells) or by the
verdicts the served LM returned for the rendered prompts (LM cells). In
the second case every semantic filter is applied after the relational
operators: for inner joins and deterministic predicates that gives the
same rows whatever placement the planner chose, and it asks only for
prompts of rows that survive every relational operator, which any
placement must also have asked.

Rows are compared as multisets: no template orders its output, so row
order is not part of a query's answer. Under a ``limit`` without an
order, any ``n`` rows of the full answer are correct.
"""
from __future__ import annotations

import re
from collections import Counter
from dataclasses import dataclass
from typing import Optional

import numpy as np

_TEMPLATE_COL = re.compile(r"\{([A-Za-z_][\w]*\.[A-Za-z_][\w]*)\}")
_CMP = {">": np.greater, ">=": np.greater_equal, "<": np.less,
        "<=": np.less_equal, "==": np.equal}


@dataclass
class Answer:
    """A template's full answer: one array per output column, and the
    ``limit`` under which any that many rows of it are correct."""

    cols: list[np.ndarray]
    limit: Optional[int] = None
    missing_verdicts: int = 0

    def __len__(self) -> int:
        return len(self.cols[0]) if self.cols else 0


class Relational:
    """Evaluate templates over ``data`` (``perfbench.data.Data``)."""

    def __init__(self, data, latent: dict):
        self.data = data
        self.latent = latent

    # ------------------------------------------------------------ columns
    def column(self, rel: dict, name: str) -> np.ndarray:
        """Values of qualified column ``name`` for every row of ``rel``,
        in the types the program stores (int32, float32, str)."""
        t, c = name.split(".", 1)
        ids = rel[t]
        if c == "row_id":
            return ids
        vals = self.data.tables[t][c]
        if vals.dtype.kind == "f":
            vals = vals.astype(np.float32)
        elif vals.dtype.kind in "iub":
            vals = vals.astype(np.int32)
        return vals[ids]

    def render(self, phi: str, rel: dict, i: int) -> str:
        """``phi`` rendered for row ``i`` from payload values."""
        def sub(m):
            t, c = m.group(1).split(".", 1)
            return str(_py(self.data.tables[t][c][rel[t][i]]))
        return _TEMPLATE_COL.sub(sub, phi)

    # ---------------------------------------------------------- operators
    def _scan(self, table: str) -> dict:
        return {table: np.arange(self.data.num_rows(table))}

    @staticmethod
    def _take(rel: dict, idx) -> dict:
        return {t: ids[idx] for t, ids in rel.items()}

    def _join(self, left: dict, right: dict, lk: str, rk: str) -> dict:
        lv, rv = self.column(left, lk), self.column(right, rk)
        order = np.argsort(rv, kind="stable")
        sk = rv[order]
        lo = np.searchsorted(sk, lv, "left")
        counts = np.searchsorted(sk, lv, "right") - lo
        li = np.repeat(np.arange(len(lv)), counts)
        first = np.repeat(np.cumsum(counts) - counts, counts)
        ri = order[np.repeat(lo, counts) + np.arange(len(li)) - first]
        return {**self._take(left, li), **self._take(right, ri)}

    @staticmethod
    def _cross(left: dict, right: dict) -> dict:
        nl = len(next(iter(left.values())))
        nr = len(next(iter(right.values())))
        li = np.repeat(np.arange(nl), nr)
        ri = np.tile(np.arange(nr), nl)
        return {**{t: v[li] for t, v in left.items()},
                **{t: v[ri] for t, v in right.items()}}

    def _truth(self, name: str, rel: dict) -> np.ndarray:
        tables, fn = self.latent[name]
        views = [_Rows(self.data.tables[t], rel[t]) for t in tables]
        return np.asarray(fn(*views), dtype=bool)

    def _eval(self, steps, pending: Optional[list]) -> tuple[dict, dict]:
        (_, table), *rest = steps
        rel, meta = self._scan(table), {"limit": None}
        for kind, *args in rest:
            if kind == "where":
                name, op, *vals = args
                v = self.column(rel, name)
                mask = ((v >= vals[0]) & (v <= vals[1]) if op == "between"
                        else _CMP[op](v, vals[0]))
                rel = self._take(rel, np.nonzero(mask)[0])
            elif kind in ("join", "cross", "sem_join"):
                right, _ = self._eval(args[0], pending)
                rel = (self._join(rel, right, args[1], args[2])
                       if kind == "join" else self._cross(rel, right))
                if kind == "sem_join":
                    rel = self._semantic(rel, args[1], pending)
            elif kind == "sem_filter":
                rel = self._semantic(rel, args[0], pending)
            elif kind == "limit":
                meta["limit"] = args[0]
            elif kind != "select":
                raise ValueError(f"unknown plan step {kind!r}")
        return rel, meta

    def _semantic(self, rel, name, pending):
        if pending is None:
            return self._take(rel, np.nonzero(self._truth(name, rel))[0])
        pending.append(name)
        return rel

    # -------------------------------------------------------------- entry
    def answer(self, template: dict,
               verdicts: Optional[dict] = None) -> Answer:
        """Full answer of ``template``. ``verdicts`` (prompt -> bool), when
        given, answers the semantic predicates in place of the latent
        truth; a surviving row whose prompt has no verdict is counted in
        ``missing_verdicts`` and dropped."""
        pending = None if verdicts is None else []
        rel, meta = self._eval(template["plan"], pending)
        missing = 0
        if pending:
            n = len(next(iter(rel.values())))
            keep = np.ones(n, dtype=bool)
            unknown = np.zeros(n, dtype=bool)
            for name in pending:
                phi = self.data.prompts[name]
                for i in np.nonzero(keep)[0]:
                    v = verdicts.get(self.render(phi, rel, i))
                    if v is None:
                        unknown[i] = True
                    elif not v:
                        keep[i] = False
            missing = int(np.sum(unknown & keep))
            rel = self._take(rel, np.nonzero(keep & ~unknown)[0])
        return Answer(cols=[self.column(rel, c) for c in template["out"]],
                      limit=meta["limit"], missing_verdicts=missing)


class _Rows:
    """Column access ``view[col]`` for the rows ``ids`` of one table."""

    def __init__(self, cols: dict, ids: np.ndarray):
        self._cols, self._ids = cols, ids

    def __getitem__(self, c):
        return self._cols[c][self._ids]


def _py(v):
    return v.item() if hasattr(v, "item") else v


def mismatch(rows: list[dict], out: list[str], ans: Answer) -> str:
    """Empty when the program's ``rows`` (dicts keyed by the output
    columns) are a correct answer, else why not."""
    if ans.missing_verdicts:
        return f"{ans.missing_verdicts} rows lack a served verdict"
    n_full = len(ans)
    if ans.limit is None and len(rows) != n_full:
        return f"{len(rows)} rows, reference {n_full}"
    if ans.limit is not None and len(rows) != min(ans.limit, n_full):
        return (f"{len(rows)} rows under limit {ans.limit}, reference "
                f"{min(ans.limit, n_full)}")
    got = Counter(tuple(r.get(c) for c in out) for r in rows)
    keep = np.ones(n_full, dtype=bool)
    if ans.limit is not None:
        # only reference rows whose every value the program returned can
        # match one of its rows: narrow before building tuples
        for j, col in enumerate(ans.cols):
            vals = np.asarray([k[j] for k in got], dtype=col.dtype)
            keep &= np.isin(col, vals)
    idx = np.nonzero(keep)[0]
    ref = Counter(zip(*([_py(v) for v in col[idx]] for col in ans.cols)))
    if ans.limit is None:
        if got != ref:
            return f"{sum((got - ref).values())} rows not in the reference"
        return ""
    extra = got - ref
    if extra:
        return f"{sum(extra.values())} rows not in the reference"
    return ""
