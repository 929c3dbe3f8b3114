"""What every served LM shares, whatever its family: the tokens, the
weights drawn from the seed, the linear layer of the plain reference,
the logit check and the verdict head.

A configuration names its model family under ``"family"``: a module,
given as a path from the checkout's root, which the harness loads by
path (``harness.load_family``). It gives

* ``program_config(model, name)``: the program's ``ModelConfig`` for the
  configuration's ``model`` group (the published ``config.json`` keys);
* ``init_weights(model, seed, dtype)``: the program's parameter tree for
  the family, drawn on the device from the seed (``draw_weights``), with
  the output head at ``lm_head`` (d_model, vocab);
* ``forward_hidden(model, weights, tokens, positions, quant=None)``: the
  plain float32 forward at ``HIGHEST`` precision, one layer at a time,
  every linear layer through ``_dense`` so that the controls' int8 and
  fp8 reach every family;
* ``matmul_params(model)`` and ``request_flops(model, n_prompt, n_out)``:
  the parameters a token passes through in a matmul, and a served
  request's model FLOPs.

``perfbench/reference/dense.py`` is the dense decoder's. The harness
hands the weights to the program, and the reference draws the same
weights again from the same seed, with the same head, once the
program's state is freed. ``verdict_head`` chooses the head's YES and
NO columns so that the random model answers every prompt with one of
them, YES to about half.

``quant="int8"`` is the control: every linear layer computed in int8
(weights per output channel, activations per token, int32 sums), the
step below bf16 that would tempt a later change.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PAD, BOS, YES, NO, SEP = 0, 1, 2, 3, 4
RESERVED = 8
HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------- tokens

def word_token(word: str, vocab: int) -> int:
    """The hash tokenizer's id of one lower-cased word (FNV-1a)."""
    h = 2166136261
    for ch in word.encode():
        h = ((h ^ ch) * 16777619) & 0xFFFFFFFF
    return RESERVED + h % (vocab - RESERVED)


def prompt_ids(prompt: str, vocab: int) -> list[int]:
    """BOS, one token per word, then SEP in place of the ``sep`` word
    the serving engine appends."""
    return ([BOS] + [word_token(w, vocab) for w in prompt.lower().split()]
            + [SEP])


def answer_ids(answer: str) -> list[int]:
    """Token ids of a served answer string (``"<id> YES"`` form)."""
    out = []
    for w in answer.split():
        if w == "YES":
            out.append(YES)
        elif w == "NO":
            out.append(NO)
        else:
            out.append(int(w.strip("<>")))
    return out


# ------------------------------------------------------------ weights

# std of the output head's columns over 1/sqrt(d_model): the columns
# other than YES and NO only have to lose to those two
HEAD_SCALE = 0.01


def _key(seed: int):
    seed = int(seed) % 2**64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def draw_weights(shapes: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """A tree of weights from ``seed``, one leaf for each (shape, std)
    of ``shapes``: normal with that std, or ones where the std is None
    (a norm gain). One jitted call; a leaf of more than two axes is
    drawn one slice of its first (stacked layer) axis at a time, so no
    float32 copy of a whole leaf is ever live."""
    flat, tree = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(
        x, tuple) and isinstance(x[0], tuple))

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (shape, std) in zip(keys, flat):
            if std is None:
                out.append(jnp.ones(shape, dtype))
                continue

            def draw(kk, shp=shape[1:] if len(shape) > 2 else shape, std=std):
                return (jax.random.normal(kk, shp) * std).astype(dtype)
            if len(shape) > 2:
                out.append(jax.lax.map(draw, jax.random.split(k, shape[0])))
            else:
                out.append(draw(k))
        return out

    return jax.tree.unflatten(tree, make(_key(seed)))


# ------------------------------------------------------------ forward

def _dense(x, w, quant):
    """``x @ w`` contracting ``x``'s last axis with ``w``'s first: float32
    at highest precision; bf16 operands with float32 sums (``"bf16"``,
    for choosing the verdict head); or the controls' int8 / fp8."""
    out_shape = w.shape[1:]
    if quant == "bf16":
        y = jnp.dot(x.astype(jnp.bfloat16),
                    w.astype(jnp.bfloat16).reshape(w.shape[0], -1),
                    preferred_element_type=jnp.float32)
        return y.reshape(*x.shape[:-1], *out_shape)
    w2 = w.astype(jnp.float32).reshape(w.shape[0], -1)
    if quant is None:
        y = jnp.dot(x, w2, precision=HIGHEST)
    elif quant == "fp8":
        sw = jnp.max(jnp.abs(w2), axis=0, keepdims=True) / 448.0
        sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0
        wq = (w2 / jnp.maximum(sw, 1e-30)).astype(jnp.float8_e4m3fn)
        xq = (x / jnp.maximum(sx, 1e-30)).astype(jnp.float8_e4m3fn)
        y = jnp.dot(xq.astype(jnp.float32), wq.astype(jnp.float32),
                    precision=HIGHEST) * sx * sw
    else:
        sw = jnp.max(jnp.abs(w2), axis=0, keepdims=True) / 127.0
        sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        wq = jnp.round(w2 / jnp.maximum(sw, 1e-30)).astype(jnp.int8)
        xq = jnp.round(x / jnp.maximum(sx, 1e-30)).astype(jnp.int8)
        y = jax.lax.dot_general(
            xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        y = y.astype(jnp.float32) * sx * sw
    return y.reshape(*x.shape[:-1], *out_shape)


@partial(jax.jit, static_argnames=("quant",))
def _head(x, lm_head, *, quant):
    return _dense(x, lm_head, quant)


def forward_logits(family, model: dict, weights: dict, tokens: np.ndarray,
                   positions: tuple[np.ndarray, np.ndarray],
                   quant=None) -> jnp.ndarray:
    """Float32 logits (len(rows), vocab) at ``positions`` = (rows, cols)
    of the (B, S) ``tokens``: ``family``'s final-norm hidden states
    through the output head ``lm_head``."""
    x = family.forward_hidden(model, weights, tokens, positions,
                              quant=quant)
    return _head(x, weights["lm_head"], quant=quant)


def _pad(seqs: list, length: int) -> np.ndarray:
    tokens = np.zeros((len(seqs), max(length, max(map(len, seqs)))),
                      np.int32)
    for r, ids in enumerate(seqs):
        tokens[r, :len(ids)] = ids
    return tokens


def served_gaps(family, model: dict, weights: dict, served: list,
                quant=None, block: int = 64, length: int = 0) -> np.ndarray:
    """For each served token of ``served`` ([(prompt, [token ids])]): how
    far its reference logit, through ``family``'s forward, lies below the
    reference's best at that position. With ``quant``, the gap of the
    token the quantized forward puts first instead (the control). Blocks
    are padded to ``length`` tokens at least, so every block has one
    shape."""
    vocab = model["vocab_size"]
    gaps = []
    for s in range(0, len(served), block):
        chunk = served[s:s + block]
        seqs, rows, cols, picks = [], [], [], []
        for r, (prompt, toks) in enumerate(chunk):
            ids = prompt_ids(prompt, vocab)
            seqs.append(ids + toks[:-1])
            for j, t in enumerate(toks):
                rows.append(r)
                cols.append(len(ids) - 1 + j)
                picks.append(t)
        seqs += [[PAD]] * (block - len(seqs))
        tokens = _pad(seqs, length)
        pos = (np.asarray(rows), np.asarray(cols))
        ref = forward_logits(family, model, weights, tokens, pos)
        if quant is not None:
            picks = np.asarray(jnp.argmax(forward_logits(
                family, model, weights, tokens, pos, quant=quant), -1))
        ref = np.asarray(ref)
        picks = np.asarray(picks)
        gaps.append(ref.max(-1) - ref[np.arange(len(picks)), picks])
    return np.concatenate(gaps) if gaps else np.zeros(0)


# -------------------------------------------------------- verdict head

# least logit of the verdict tokens over the sample's SEP positions (the
# other columns give a normal logit of std HEAD_SCALE there), and the
# spread (std) of YES minus NO within each predicate's prompts
VERDICT_MARGIN = 1.0
VERDICT_SPREAD = 4.0


def verdict_head(family, model: dict, weights: dict, groups: list,
                 seed: int, length: int) -> np.ndarray:
    """The output head's YES and NO columns (d_model, 2), float32.

    Random columns would almost never put YES or NO first, so every
    verdict would be NO and every result nearly empty. From ``family``'s
    final-norm hidden states at SEP of ``groups`` (one list of prompts
    per semantic predicate), computed with bf16 operands: both columns
    lean on the states' mean direction, so one of the two comes first
    at every SEP, and they differ along a direction drawn from ``seed``
    with each group's mean state projected out, so each predicate says
    YES to about half of its prompts."""
    vocab = model["vocab_size"]
    xs = []
    for g in groups:
        seqs = [prompt_ids(p, vocab) for p in g]
        x = family.forward_hidden(model, weights, _pad(seqs, length),
                                  (np.arange(len(seqs)),
                                   np.asarray([len(q) - 1 for q in seqs])),
                                  quant="bf16")
        xs.append(np.asarray(x, np.float64))
    D = xs[0].shape[1]
    mean = np.concatenate(xs).mean(0)
    top = mean / np.linalg.norm(mean)
    lean = VERDICT_MARGIN / np.concatenate(xs).dot(top).min()
    basis, _ = np.linalg.qr(np.stack([top] + [x.mean(0) for x in xs], 1))
    v = np.random.default_rng((int(seed) % 2**64, 0x7E5)).standard_normal(D)
    v -= basis @ (basis.T @ v)
    spread = np.sqrt(np.mean(np.concatenate(
        [(x - x.mean(0)).dot(v) for x in xs]) ** 2))
    v *= VERDICT_SPREAD / spread
    return np.stack([lean * top + v / 2, lean * top - v / 2],
                    1).astype(np.float32)


@partial(jax.jit, donate_argnums=(0,))
def _set_columns(head, cols):
    return head.at[:, jnp.asarray([YES, NO])].set(cols.astype(head.dtype))


def with_head(weights: dict, cols: np.ndarray) -> dict:
    """``weights`` with the YES and NO columns of the head set to
    ``cols``, in the head's type. The old head is given up to the new
    one (donated), so ``weights`` may not be used again."""
    return dict(weights, lm_head=_set_columns(weights["lm_head"],
                                              jnp.asarray(cols)))
