"""The served LM's weights from the seed, and its plain reference.

``init_weights`` draws the weights on the device in one jitted call, in
the type they are served in (bf16), laid out as the program's dense
decoder expects them, and ``verdict_head`` chooses the head's YES and NO
columns so that the random model answers every prompt with one of them,
YES to about half; the harness hands the weights to the program, and the
reference draws the same weights again from the same seed, with the
same two columns, once the program's state is freed.

``forward_logits`` is the plain forward pass: float32 at ``highest``
matmul precision, one layer at a time, with no cache and no batching
across prompts beyond padding at the end (causal, so padding never
reaches an earlier position). It computes what the program's dense
model computes — RMSNorm, rotary embedding over the whole head, SwiGLU
— which departs from the published stablelm-3b (LayerNorm, 25% partial
rotary); the configuration file lists the departures.

``quant="int8"`` is the control: every linear layer computed in int8
(weights per output channel, activations per token, int32 sums), the
step below bf16 that would tempt a later change.
"""
from __future__ import annotations

import math
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


def _shapes(m: dict) -> dict:
    """Each leaf's (shape, std); a std of None is a norm gain of ones."""
    L, D, H = m["num_hidden_layers"], m["hidden_size"], \
        m["num_attention_heads"]
    K, F, V = m["num_key_value_heads"], m["intermediate_size"], \
        m["vocab_size"]
    hd = D // H
    d, f, o = 1 / math.sqrt(D), 1 / math.sqrt(F), 1 / math.sqrt(H * hd)
    return {"embed": ((V, D), d), "final_ln": ((D,), None),
            "lm_head": ((D, V), HEAD_SCALE * d),
            "blocks": {"ln1": ((L, D), None), "ln2": ((L, D), None),
                       "attn": {"wq": ((L, D, H, hd), d),
                                "wk": ((L, D, K, hd), d),
                                "wv": ((L, D, K, hd), d),
                                "wo": ((L, H, hd, D), o)},
                       "mlp": {"w_in": ((L, D, F), d),
                               "w_out": ((L, F, D), f),
                               "w_gate": ((L, D, F), d)}}}


def _key(seed: int):
    seed = int(seed) % 2**64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def init_weights(model: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Weights of ``model`` (the configuration's ``model`` group) from
    ``seed``: normal with std 1/sqrt(fan-in), the output head at
    ``HEAD_SCALE`` times that, norm gains 1. One jitted
    call; stacked layer leaves are drawn a layer at a time so no float32
    copy of a whole leaf is ever live."""
    shapes = _shapes(model)
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


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    S, hd = x.shape[-3], x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("eps", "theta", "quant"))
def _layer(h, lw, *, eps, theta, quant):
    B, S, D = h.shape
    a = lw["attn"]
    x = _rms(h, lw["ln1"], eps)
    q = _rope(_dense(x, a["wq"], quant), theta)
    k = _rope(_dense(x, a["wk"], quant), theta)
    v = _dense(x, a["wv"], quant)
    H, hd = q.shape[-2], q.shape[-1]
    G = H // k.shape[-2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, k, precision=HIGHEST)
    s = s / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhst,bthd->bshd", p, v, precision=HIGHEST)
    h = h + _dense(o.reshape(B, S, H * hd),
                   a["wo"].reshape(H * hd, D), quant)
    m = lw["mlp"]
    x = _rms(h, lw["ln2"], eps)
    g = _dense(x, m["w_gate"], quant)
    u = _dense(x, m["w_in"], quant)
    return h + _dense(jax.nn.silu(g) * u, m["w_out"], quant)


@partial(jax.jit, static_argnames=("eps",))
def _final(h, final_ln, rows, cols, *, eps):
    return _rms(h[rows, cols], final_ln, eps)


def forward_hidden(model: dict, weights: dict, tokens: np.ndarray,
                   positions: tuple[np.ndarray, np.ndarray],
                   quant=None) -> jnp.ndarray:
    """Float32 final-norm hidden states (len(rows), d_model) at
    ``positions`` = (rows, cols) of the (B, S) ``tokens``, layer by
    layer."""
    eps = float(model["layer_norm_eps"])
    theta = float(model["rope_theta"])
    h = jnp.take(weights["embed"], jnp.asarray(tokens), axis=0).astype(
        jnp.float32)
    blocks = weights["blocks"]
    for i in range(model["num_hidden_layers"]):
        lw = jax.tree.map(lambda x: x[i], blocks)
        h = _layer(h, lw, eps=eps, theta=theta, quant=quant)
    rows, cols = positions
    return _final(h, weights["final_ln"], jnp.asarray(rows),
                  jnp.asarray(cols), eps=eps)


@partial(jax.jit, static_argnames=("quant",))
def _head(x, lm_head, *, quant):
    return _dense(x, lm_head, quant)


def forward_logits(model: dict, weights: dict, tokens: np.ndarray,
                   positions: tuple[np.ndarray, np.ndarray],
                   quant=None) -> jnp.ndarray:
    """Float32 logits (len(rows), vocab) at ``positions`` = (rows, cols)
    of the (B, S) ``tokens``, layer by layer."""
    x = forward_hidden(model, weights, tokens, positions, quant=quant)
    return _head(x, weights["lm_head"], quant=quant)


def _pad(seqs: list, length: int) -> np.ndarray:
    tokens = np.zeros((len(seqs), max(length, max(map(len, seqs)))),
                      np.int32)
    for r, ids in enumerate(seqs):
        tokens[r, :len(ids)] = ids
    return tokens


def served_gaps(model: dict, weights: dict, served: list, quant=None,
                block: int = 64, length: int = 0) -> np.ndarray:
    """For each served token of ``served`` ([(prompt, [token ids])]): how
    far its reference logit lies below the reference's best at that
    position. With ``quant``, the gap of the token the quantized forward
    puts first instead (the control). Blocks are padded to ``length``
    tokens at least, so every block has one shape."""
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
        ref = forward_logits(model, weights, tokens, pos)
        if quant is not None:
            picks = np.asarray(jnp.argmax(
                forward_logits(model, weights, tokens, pos, quant=quant), -1))
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


def verdict_head(model: dict, weights: dict, groups: list, seed: int,
                 length: int) -> np.ndarray:
    """The output head's YES and NO columns (d_model, 2), float32.

    Random columns would almost never put YES or NO first, so every
    verdict would be NO and every result nearly empty. From the final-norm
    hidden states at SEP of ``groups`` (one list of prompts per semantic
    predicate), computed with bf16 operands: both columns lean on the
    states' mean direction, so one of the two comes first at every SEP,
    and they differ along a direction drawn from ``seed`` with each
    group's mean state projected out, so each predicate says YES to
    about half of its prompts."""
    vocab, D = model["vocab_size"], model["hidden_size"]
    xs = []
    for g in groups:
        seqs = [prompt_ids(p, vocab) for p in g]
        x = forward_hidden(model, weights, _pad(seqs, length),
                           (np.arange(len(seqs)),
                            np.asarray([len(q) - 1 for q in seqs])),
                           quant="bf16")
        xs.append(np.asarray(x, np.float64))
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
