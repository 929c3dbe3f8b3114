"""Build the program's logical plan from a template spec.

A template is data (``queries/<schema>.json``): a list of steps that
mirror the program's ``Q`` builder calls, so the same spec drives both
the system under test and the plain reference
(``reference/relational.py``).
"""
from __future__ import annotations

import json
import operator
from pathlib import Path

from repro.core import Q, col

HERE = Path(__file__).resolve().parent
COMPARE = {">": operator.gt, ">=": operator.ge, "<": operator.lt,
           "<=": operator.le, "==": operator.eq}


def load_templates(schema: str) -> dict:
    """Every template of ``schema``, by name."""
    return json.loads((HERE / "queries" / f"{schema}.json").read_text())


def _builder(steps, prompts) -> Q:
    (kind, table), *rest = steps
    if kind != "scan":
        raise ValueError(f"a plan starts with a scan, not {kind!r}")
    q = Q.scan(table)
    for kind, *args in rest:
        if kind == "where":
            name, op, *vals = args
            pred = (col(name).between(*vals) if op == "between"
                    else COMPARE[op](col(name), vals[0]))
            q = q.where(pred)
        elif kind == "sem_filter":
            q = q.sem_filter(prompts[args[0]])
        elif kind == "join":
            q = q.join(_builder(args[0], prompts), args[1], args[2])
        elif kind == "cross":
            q = q.cross(_builder(args[0], prompts))
        elif kind == "sem_join":
            q = q.sem_join(_builder(args[0], prompts), prompts[args[1]])
        elif kind == "limit":
            q = q.limit(args[0])
        elif kind == "select":
            q = q.select(*args)
        else:
            raise ValueError(f"unknown plan step {kind!r}")
    return q


def build_plan(template: dict, prompts: dict):
    """The unoptimised plan ``Node`` of one template."""
    return _builder(template["plan"], prompts).build()
