"""The system under test, wired from a configuration file.

The program's ``Database`` is loaded from the benchmark's generated data
through the program's own ``add_table``; the backend is the program's
``OracleBackend`` or its ``ModelBackend`` over a ``ServingEngine`` whose
weights the benchmark drew from the seed. ``SpanBackend`` sits between
the program's semantic tier and that backend: it opens a span around
every backend call; ``RecordingEngine`` records the tokens the engine
served for each prompt, from which the checks read the verdicts.
"""
from __future__ import annotations

from dataclasses import dataclass
from types import ModuleType
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.engine import Database, FrontDoor
from repro.semantic import ModelBackend, OracleBackend, SemanticRunner
from repro.serving.engine import ServingEngine
from repro.sharding.policy import ShardingPolicy

from .reference import lm


class RecordingEngine:
    """A ``ServingEngine`` that records (prompt, served token ids) for
    every request it answers; everything else goes to the engine."""

    def __init__(self, engine: ServingEngine):
        self._engine = engine
        self._prompts: dict = {}
        self.served: list = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def submit(self, prompts, weights=None):
        """Enqueue on the engine, remembering the ticket's prompts."""
        ticket = self._engine.submit(prompts, weights=weights)
        self._prompts[ticket.rids] = list(prompts)
        return ticket

    def answers(self, ticket):
        """The engine's answers, recorded with their token ids."""
        raw = self._engine.answers(ticket)
        for p, a in zip(self._prompts.pop(ticket.rids), raw):
            self.served.append((p, lm.answer_ids(a)))
        return raw


class SpanBackend:
    """Backend wrapper: a span around each call into ``inner``."""

    def __init__(self, inner, spans):
        self.inner = inner
        self.spans = spans

    @property
    def calls(self):
        """Prompts the inner backend was sent."""
        return self.inner.calls

    @property
    def preferred_batch_rows(self):
        """The inner backend's dispatch-size hint."""
        return self.inner.preferred_batch_rows

    @property
    def supports_async(self):
        """Whether the inner backend speaks the ticket protocol."""
        return getattr(self.inner, "supports_async", False)

    def reset_counters(self):
        """Reset the inner backend's call count."""
        self.inner.reset_counters()

    def evaluate_batch(self, prompts, contexts):
        """Synchronous batch through the inner backend."""
        with self.spans.span("bench.backend"):
            return self.inner.evaluate_batch(prompts, contexts)

    def submit_batch(self, prompts, contexts, weights=None):
        """Enqueue a batch on the inner backend's ticket protocol."""
        with self.spans.span("bench.backend"):
            return self.inner.submit_batch(prompts, contexts,
                                           weights=weights)

    def collect(self, handles):
        """Collect every ticket's answers from the inner backend."""
        with self.spans.span("bench.backend"):
            return self.inner.collect(handles)


@dataclass
class System:
    """Everything the window drives, and what the checks read back."""

    db: Database
    catalog: object
    front: FrontDoor
    backend: SpanBackend
    engine: Optional[RecordingEngine] = None
    model: Optional[dict] = None
    head: Optional[np.ndarray] = None
    family: Optional[ModuleType] = None


def build(config: dict, data, seed: int, spans,
          family: Optional[ModuleType] = None, longest_prompt: int = 0,
          groups: Sequence[list] = ()) -> System:
    """Load ``data`` and wire the configured backend behind a front door.
    For an LM backend, ``family`` (the module the configuration names,
    ``reference/lm.py``) maps the model group to the program's config and
    draws the weights, ``longest_prompt`` (tokens) sizes ``max_seq`` and
    ``groups`` (sample prompts, one list per predicate) choose the
    verdict head (``reference.lm.verdict_head``)."""
    db = Database()
    data.load(db)
    catalog = db.catalog()
    kind = config["backend"]
    engine = head = None
    if kind == "oracle":
        inner = OracleBackend(truths=db.truths)
    elif kind == "lm":
        model = config["model"]
        weights = family.init_weights(model, seed, dtype=jnp.bfloat16)
        head = lm.verdict_head(family, model, weights, groups, seed,
                               length=longest_prompt + 1)
        engine = RecordingEngine(ServingEngine(
            family.program_config(model, config["name"]),
            lm.with_head(weights, head), ShardingPolicy.single(),
            max_seq=longest_prompt))
        del weights
        inner = ModelBackend.from_engine(engine)
    else:
        raise ValueError(f"unknown backend {kind!r}")
    backend = SpanBackend(inner, spans)
    front = FrontDoor(db, SemanticRunner(backend))
    return System(db=db, catalog=catalog, front=front, backend=backend,
                  engine=engine, model=config.get("model"), head=head,
                  family=family)
