"""Batched serving engine for semantic-operator backends.

The query tier hands the engine *distinct* prompts (function caching
already deduplicated them). Two serving disciplines share one set of
weights and one tokenizer:

* **Continuous** (the default, ``answer`` / ``submit`` / ``poll`` /
  ``drain``): a ``SlotScheduler`` admits queued prompts into freed
  slots *mid-decode* via per-slot prefill-into-cache, decodes over
  whatever slot mix is live, and detects completion on device — one
  host sync per scheduling round (site ``serving_round``). ``answer``
  is a thin submit-all/await-all wrapper over the async API.
* **Drained** (``answer_drained``): the legacy drain-per-batch
  baseline — pad each chunk to ``batch_size``, prefill, decode to
  completion with a per-step host fetch (site ``serving_decode``),
  only then admit the next chunk. Kept as the comparison baseline for
  ``benchmarks/bench_serving.py`` and the equivalence tests; the two
  paths are verdict-for-verdict identical.

Both disciplines account into ``ServingStats``, which tracks slot
occupancy (live vs padded/idle slot-steps in prefill and decode),
queue latency and time-to-verdict alongside the original counters.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.sync import HOST_SYNCS
from ..models import decode_step, prefill
from ..models.config import ModelConfig
from ..sharding.policy import ShardingPolicy
from ..training.data import HashTokenizer
from .scheduler import SlotScheduler, Ticket


@dataclass
class ServingStats:
    """Serving-tier counters; one instance per engine, resettable."""

    prompts: int = 0
    batches: int = 0  # prefill launches (any width)
    prefill_tokens: int = 0  # real prompt tokens only, never padding
    # token positions prefill launches computed, padding included
    prefill_token_slots: int = 0
    decode_steps: int = 0  # decode rounds (one device step each)
    wall_s: float = 0.0
    # --- slot occupancy ---
    prefill_rows: int = 0  # rows prefilled, incl. dead padded slots
    live_prefill_rows: int = 0  # rows that carried a real prompt
    slot_steps: int = 0  # batch_size × decode rounds
    live_slot_steps: int = 0  # slots decoding a live request
    decode_tokens: int = 0  # tokens emitted for live requests
    # --- queue latency / time-to-verdict ---
    queue_wait_s: float = 0.0  # total submit→admit wait
    queue_wait_max_s: float = 0.0
    queued_peak: int = 0
    ttv_s: list = field(default_factory=list)  # submit→done per request

    @property
    def occupancy(self) -> float:
        """Fraction of decode slot-steps spent on live requests."""
        return self.live_slot_steps / max(self.slot_steps, 1)

    @property
    def prefill_occupancy(self) -> float:
        """Fraction of prefilled rows that carried a real prompt."""
        return self.live_prefill_rows / max(self.prefill_rows, 1)

    def snapshot(self) -> dict:
        """JSON-ready view (ttv list summarized as count + p50/p99)."""
        ttv = sorted(self.ttv_s)

        def pct(q):
            if not ttv:
                return 0.0
            return ttv[min(len(ttv) - 1, int(q * (len(ttv) - 1)))]

        return {
            "prompts": self.prompts,
            "batches": self.batches,
            "prefill_tokens": self.prefill_tokens,
            "prefill_token_slots": self.prefill_token_slots,
            "decode_steps": self.decode_steps,
            "decode_tokens": self.decode_tokens,
            "wall_s": self.wall_s,
            "occupancy": self.occupancy,
            "prefill_occupancy": self.prefill_occupancy,
            "queue_wait_s": self.queue_wait_s,
            "queue_wait_max_s": self.queue_wait_max_s,
            "queued_peak": self.queued_peak,
            "ttv_p50_s": pct(0.50),
            "ttv_p99_s": pct(0.99),
        }


def decode_round(cfg: ModelConfig, policy: ShardingPolicy, answer_ids,
                 params, cache, cur, pos, live, rem):
    """One continuous-batching decode step over the live slot mix. Done
    detection (one of the ``answer_ids`` (YES, NO) tokens, or the token
    budget ``rem`` spent) stays on device, and the caller fetches ONE
    packed (emit ‖ finished) vector."""
    yes, no = answer_ids
    logits, cache = decode_step(cfg, policy, params, cache, cur, pos)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    hit = (nxt == yes) | (nxt == no)
    rem = jnp.where(live, rem - 1, rem)
    fin = live & (hit | (rem <= 0))
    emit = jnp.where(live, nxt, -1)
    packed = jnp.concatenate([emit, fin.astype(jnp.int32)])
    return (cache, nxt, jnp.where(live, pos + 1, pos), live & ~fin, rem,
            packed)


def prompt_tokens(prompt: str) -> int:
    """Tokens ``ServingEngine.encode_row`` needs for ``prompt`` untruncated
    (BOS, one per word, SEP): size ``max_seq`` to the longest prompt."""
    return len(prompt.split()) + 2


class ServingEngine:
    """One model, one cache, two serving disciplines (see module doc)."""

    def __init__(self, cfg: ModelConfig, params, policy: ShardingPolicy,
                 tokenizer: Optional[HashTokenizer] = None,
                 batch_size: int = 16, max_seq: int = 128,
                 max_new_tokens: int = 2):
        self.cfg = cfg
        self.params = params
        self.policy = policy
        self.tok = tokenizer or HashTokenizer(cfg.vocab_size)
        self.batch_size = batch_size
        self.max_seq = max_seq
        self.max_new = max_new_tokens
        self.stats = ServingStats()
        self.cache_len = max_seq + max_new_tokens + 1

        cache_len = self.cache_len
        max_new = self.max_new
        yes, no = self.tok.YES, self.tok.NO

        def _prefill(params, tokens):
            return prefill(cfg, policy, params, {"tokens": tokens},
                           max_seq=cache_len)

        def _decode(params, cache, tok, pos):
            return decode_step(cfg, policy, params, cache, tok, pos)

        def _prefill_insert(params, cache, cur, pos, live, rem, adm):
            # per-slot prefill-into-cache: prefill at the admission
            # width, then scatter every cache leaf's rows (batch axis 1)
            # into the shared decode cache at the assigned slots.
            # ``adm`` is the packed admission batch — token rows with
            # the slot index and real length in the last two columns —
            # so each admission pays ONE host->device upload
            toks, slots, lens = adm[:, :-2], adm[:, -2], adm[:, -1]
            _, new = prefill(cfg, policy, params, {"tokens": toks},
                             max_seq=cache_len)
            cache = {k: v.at[:, slots].set(new[k], mode="drop")
                     for k, v in cache.items()}
            width = toks.shape[0]
            last = jnp.maximum(lens - 1, 0)
            first = toks[jnp.arange(width), last]
            cur = cur.at[slots].set(first, mode="drop")
            pos = pos.at[slots].set(last, mode="drop")
            live = live.at[slots].set(True, mode="drop")
            rem = rem.at[slots].set(max_new, mode="drop")
            return cache, cur, pos, live, rem

        def _decode_round(params, cache, cur, pos, live, rem):
            # a named function, so the program is ``jit__decode_round``
            # on a profiler trace
            return decode_round(cfg, policy, (yes, no), params, cache, cur,
                                pos, live, rem)

        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(_decode, donate_argnums=(1,))
        self._prefill_insert = jax.jit(_prefill_insert,
                                       donate_argnums=(1, 2, 3, 4, 5))
        self._decode_round = jax.jit(_decode_round,
                                     donate_argnums=(1, 2, 3, 4, 5))
        self.scheduler = SlotScheduler(self)

    @property
    def preferred_batch_rows(self) -> int:
        """Dispatch-size hint for the semantic tier: one upstream chunk
        fills a handful of serving batches, so a huge pulled-up filter
        streams through as bounded bucket-aligned batches instead of one
        monolithic host-side queue."""
        return self.batch_size * 8

    # --------------------------------------------------------- encoding
    def encode_row(self, prompt: str) -> tuple[np.ndarray, int]:
        """Encode one prompt to a SEP-terminated ``(max_seq,)`` row;
        prompts longer than ``max_seq`` tokens (``prompt_tokens``) are
        truncated."""
        enc = self.tok.encode(prompt + " sep", self.max_seq)
        n = int((enc != 0).sum())
        # terminate with SEP so the model knows to answer
        enc[max(n - 1, 0)] = self.tok.SEP
        return enc, n

    def _encode_batch(self, prompts: Sequence[str]
                      ) -> tuple[np.ndarray, np.ndarray]:
        toks = np.zeros((self.batch_size, self.max_seq), dtype=np.int32)
        lens = np.ones(self.batch_size, dtype=np.int32)
        for i, p in enumerate(prompts):
            toks[i], lens[i] = self.encode_row(p)
        return toks, lens

    # ----------------------------------------------- continuous serving
    def submit(self, prompts: Sequence[str],
               weights: Optional[Sequence[float]] = None) -> Ticket:
        """Enqueue prompts on the continuous scheduler (optionally
        row-weighted for fair admission); returns a ``Ticket``."""
        return self.scheduler.submit(prompts, weights)

    def poll(self) -> int:
        """Run one scheduling round; returns outstanding requests."""
        return self.scheduler.poll()

    def drain(self, ticket: Optional[Ticket] = None) -> None:
        """Run rounds until ``ticket`` (or everything) completes."""
        self.scheduler.drain(ticket)

    def done(self, ticket: Ticket) -> bool:
        """True once every request of ``ticket`` has finished."""
        return self.scheduler.done(ticket)

    def answers(self, ticket: Ticket) -> list[str]:
        """Detokenized answers for a completed ticket, submit order."""
        return [self._detok(ids) for ids in self.scheduler.take(ticket)]

    def answer(self, prompts: Sequence[str]) -> list[str]:
        """Greedy-decode an answer per prompt — a thin submit-all /
        await-all wrapper over the continuous scheduler."""
        t0 = time.perf_counter()
        ticket = self.submit(prompts)
        self.drain(ticket)
        out = self.answers(ticket)
        self.stats.wall_s += time.perf_counter() - t0
        return out

    # -------------------------------------------------- drained serving
    def answer_drained(self, prompts: Sequence[str]) -> list[str]:
        """Drain-per-batch baseline: each fixed batch decodes to
        completion before the next is admitted."""
        t0 = time.perf_counter()
        out: list[str] = []
        for start in range(0, len(prompts), self.batch_size):
            chunk = list(prompts[start: start + self.batch_size])
            out.extend(self._answer_batch(chunk))
        self.stats.prompts += len(prompts)
        self.stats.wall_s += time.perf_counter() - t0
        return out

    def first_step_logits(self, prompts: Sequence[str]) -> jnp.ndarray:
        """(len(prompts), vocab) logits of the drained path's first step
        — prefill, then one decode step at each prompt's last token —
        for comparing the cached path with a full ``models.forward``
        over the same ``encode_row`` tokens (at most ``batch_size``)."""
        toks, lens = self._encode_batch(prompts)
        _, cache = self._prefill(self.params, jnp.asarray(toks))
        cur = toks[np.arange(self.batch_size), lens - 1]
        logits, _ = self._decode(self.params, cache, jnp.asarray(cur),
                                 jnp.asarray(lens - 1))
        return logits[:len(prompts)]

    def _answer_batch(self, chunk: list[str]) -> list[str]:
        toks, lens = self._encode_batch(chunk)
        t_in = time.perf_counter()
        self.stats.batches += 1
        # padded slots past len(chunk) are dead weight the drained
        # shape cannot avoid; count only real prompt tokens and report
        # the waste through the occupancy counters
        self.stats.prefill_tokens += int(lens[:len(chunk)].sum())
        self.stats.prefill_token_slots += toks.size
        self.stats.prefill_rows += self.batch_size
        self.stats.live_prefill_rows += len(chunk)
        logits, cache = self._prefill(self.params, jnp.asarray(toks))
        # positions differ per row: prefill computed the full padded seq;
        # take the logits at each row's last real token instead
        answers = [[] for _ in chunk]
        # first sampled token comes from each row's last real prompt
        # position: one decode step at pos = len - 1 re-derives it
        pos = jnp.asarray(lens - 1)
        # decode loop with slot recycling at batch boundaries only
        done = np.zeros(len(chunk), dtype=bool)
        cur = jnp.asarray(toks[np.arange(self.batch_size),
                               np.maximum(lens - 1, 0)])
        for _step in range(self.max_new + 1):
            logits, cache = self._decode(self.params, cache, cur, pos)
            self.stats.decode_steps += 1
            live = int((~done).sum())
            self.stats.slot_steps += self.batch_size
            self.stats.live_slot_steps += live
            self.stats.decode_tokens += live
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
            HOST_SYNCS.tick(site="serving_decode")  # per-STEP host sync
            pos = pos + 1
            cur = jnp.asarray(nxt)
            # only live slots reach the host loop: finished sequences and
            # padded slots past len(chunk) are masked out entirely
            for i in np.nonzero(~done)[0]:
                answers[i].append(int(nxt[i]))
                if nxt[i] in (self.tok.YES, self.tok.NO) or \
                        len(answers[i]) >= self.max_new:
                    done[i] = True
            if done.all():
                break  # every live slot finished: recycle the batch
        ttv = time.perf_counter() - t_in
        self.stats.ttv_s.extend([ttv] * len(chunk))
        return [self._detok(a) for a in answers]

    def _detok(self, ids: list[int]) -> str:
        words = []
        for t in ids:
            if t == self.tok.YES:
                words.append("YES")
                break
            if t == self.tok.NO:
                words.append("NO")
                break
            words.append(f"<{t}>")
        return " ".join(words)
