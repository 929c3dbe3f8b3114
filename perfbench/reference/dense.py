"""The dense decoder family (``models/``'s ``family="dense"``):
stablelm-3b's keys, its weights as the program lays them out, its plain
reference and its FLOP count (the family contract is in
``perfbench/reference/lm.py``).

``forward_hidden`` computes what the program's dense model computes —
RMSNorm, rotary embedding over the whole head, SwiGLU — which departs
from the published stablelm-3b (LayerNorm, 25% partial rotary); the
configuration file lists the departures.

``request_flops`` is the model FLOPs of one served request: 2 x matmul
parameters per token pass, plus causal attention (QK^T and PV, 4 x
d_model FLOPs per key per layer) at the request's real lengths. The
embedding lookup is a gather and counts nothing; the output head counts
for every pass, as the usual model FLOPs convention has it.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.lm import HEAD_SCALE, HIGHEST, _dense, draw_weights


def program_config(model: dict, name: str):
    """The program's ``ModelConfig`` for a configuration's model group.
    The one tie to the program, imported here so that the reference
    below imports nothing of it."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=name, family="dense",
        num_layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        gated_mlp=True, norm_eps=float(model["layer_norm_eps"]),
        rope_theta=float(model["rope_theta"]))


# ------------------------------------------------------------ weights

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


def init_weights(model: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Weights of ``model`` (the configuration's ``model`` group) from
    ``seed``, laid out as the program's dense decoder expects them:
    normal with std 1/sqrt(fan-in), the output head at ``HEAD_SCALE``
    times that, norm gains 1, stacked layer leaves drawn a layer at a
    time (``draw_weights``)."""
    return draw_weights(_shapes(model), seed, dtype)


# ------------------------------------------------------------ forward

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
    layer, with no cache and no batching across prompts beyond padding
    at the end (causal, so padding never reaches an earlier position)."""
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


# -------------------------------------------------------------- FLOPs

def matmul_params(model: dict) -> int:
    """Parameters that take part in a matmul per token."""
    L, D = model["num_hidden_layers"], model["hidden_size"]
    H, K = model["num_attention_heads"], model["num_key_value_heads"]
    F, V = model["intermediate_size"], model["vocab_size"]
    hd = D // H
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F
    return L * per_layer + D * V


def request_flops(model: dict, n_prompt: int, n_out: int) -> float:
    """FLOPs of one request that answers ``n_out`` tokens: prefill of its
    ``n_prompt`` tokens, which gives the first answer token, then
    ``n_out - 1`` decode passes, the ``j``-th at position
    ``n_prompt + j`` over ``n_prompt + j + 1`` keys."""
    L, D = model["num_hidden_layers"], model["hidden_size"]
    per_token = 2 * matmul_params(model)
    attn = 4 * L * D
    prefill = n_prompt * per_token + attn * n_prompt * (n_prompt + 1) / 2
    decode = sum(per_token + attn * (n_prompt + j + 1)
                 for j in range(n_out - 1))
    return prefill + decode
