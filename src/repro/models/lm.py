"""Top-level models: decoder-only LM (dense/MoE/SSM/hybrid/VLM) and
encoder-decoder (Whisper), with scan-over-layers, KV/SSM caches, prefill
and single-token decode.

Entry points
------------
forward_loss(cfg, policy, params, batch)          -> scalar loss (training)
prefill(cfg, policy, params, batch)               -> (logits_last, cache)
decode_step(cfg, policy, params, cache, tok, pos) -> (logits, cache)
init_cache / abstract_cache                       -> cache pytree (+specs)

Batch dict keys: 'tokens' (B,S) int32; VLM adds 'patches' (B,P,D);
enc-dec adds 'frames' (B,Senc,D). The modality frontends are stubs per the
assignment: patches/frames arrive as precomputed embeddings.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..sharding.policy import ShardingPolicy
from .config import ModelConfig
from .layers import (
    attention_block,
    attention_decode,
    mla_block,
    mla_decode,
    mlp,
    moe_block,
    rms_norm,
    ssm_block,
    ssm_decode,
)

# When True, layer scans are fully unrolled. Used ONLY by the dry-run cost
# probe: XLA's HloCostAnalysis visits scan bodies once, so FLOP counting
# requires an unrolled lowering (EXPERIMENTS.md §Dry-run, methodology).
UNROLL_SCANS = False


def _scan(body, init, xs, length: int):
    return jax.lax.scan(body, init, xs,
                        unroll=length if UNROLL_SCANS else 1)

# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def _mixer_train(cfg, policy, bp, x, positions, mode, prefix):
    if cfg.family == "ssm":
        out, state, conv_tail = ssm_block(cfg, policy, bp["ssm"], x)
        return out, {"state": state, "conv": conv_tail}
    if cfg.family == "hybrid":
        a = attention_block(cfg, policy, bp["attn"], x, positions, mode,
                            prefix, window=cfg.attn_window)
        s, state, conv_tail = ssm_block(cfg, policy, bp["ssm"], x)
        out = 0.5 * (rms_norm(a, bp["attn_norm"], cfg.norm_eps)
                     + rms_norm(s, bp["ssm_norm"], cfg.norm_eps))
        return out, {"state": state, "conv": conv_tail}
    if cfg.use_mla:
        return mla_block(cfg, policy, bp["mla"], x, positions, mode), None
    return attention_block(cfg, policy, bp["attn"], x, positions, mode,
                           prefix), None


def _ffn(cfg, policy, bp, x):
    if cfg.family == "ssm":
        return None
    if cfg.num_experts:
        return moe_block(cfg, policy, bp["moe"], x)
    return mlp(cfg, policy, bp["mlp"], x)


def _block_train(cfg, policy, h, bp, positions, mode, prefix,
                 enc_out=None, enc_pos=None):
    mix, aux = _mixer_train(cfg, policy, bp, rms_norm(h, bp["ln1"],
                                                      cfg.norm_eps),
                            positions, mode, prefix)
    h = h + mix
    if enc_out is not None:  # whisper decoder cross-attention
        xa = attention_block(
            cfg, policy, bp["xattn"], rms_norm(h, bp["ln_x"], cfg.norm_eps),
            positions, mode="bidir",
            kv_override=_cross_kv(cfg, bp["xattn"], enc_out, enc_pos))
        h = h + xa
    f = _ffn(cfg, policy, bp, rms_norm(h, bp["ln2"], cfg.norm_eps))
    if f is not None:
        h = h + f
    return h, aux


def _cross_kv(cfg, p, enc_out, enc_pos):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    return (k, v, enc_pos)


def _scan_blocks(cfg, policy, params, h, positions, mode, prefix,
                 enc_out=None, enc_pos=None, remat: Optional[str] = None,
                 collect_kv: bool = False):
    """lax.scan over the stacked layer parameters."""

    def body(hh, bp):
        kv = None
        if collect_kv:
            kv = _collect_kv(cfg, bp, rms_norm(hh, bp["ln1"], cfg.norm_eps),
                             positions)
        hh, aux = _block_train(cfg, policy, hh, bp, positions, mode, prefix,
                               enc_out, enc_pos)
        ys = (kv, aux) if collect_kv else aux
        return hh, ys

    if remat == "full":
        body = jax.checkpoint(body, prevent_cse=False)
    elif remat == "dots":
        body = jax.checkpoint(
            body, prevent_cse=False,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    h, ys = _scan(body, h, params["blocks"], cfg.num_layers)
    return h, ys


def _collect_kv(cfg, bp, x_normed, positions):
    """K/V (or latent) of one layer for prefill cache construction."""
    from .layers import _mla_kv_latent, rope

    if cfg.family == "ssm":
        return None
    if cfg.use_mla:
        ckv, krope = _mla_kv_latent(cfg, bp["mla"], x_normed, positions)
        return {"ckv": ckv, "krope": krope}
    p = bp["attn"]
    k = jnp.einsum("bsd,dhk->bshk", x_normed, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x_normed, p["wv"])
    if "bk" in p:
        k = k + p["bk"]
        v = v + p["bv"]
    k = rope(k, positions, cfg.rope_theta)
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# embedding / heads
# ---------------------------------------------------------------------------


def _embed_tokens(cfg, policy, params, tokens):
    h = jnp.take(params["embed"], tokens, axis=0)
    return policy.shard(h, "batch", None, None)


def _lm_logits(cfg, params, h):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("bsd,dv->bsv", h, w)


def _prepare_inputs(cfg, policy, params, batch):
    """Returns (h, positions, mode, prefix, enc_out, enc_pos, n_prefix)."""
    tokens = batch["tokens"]
    h = _embed_tokens(cfg, policy, params, tokens)
    mode, prefix, n_img = "causal", 0, 0
    enc_out = enc_pos = None
    if cfg.family == "vlm":
        patches = batch["patches"].astype(h.dtype)
        img = jnp.einsum("bpd,de->bpe", patches, params["img_proj"])
        h = jnp.concatenate([img, h], axis=1)
        n_img = patches.shape[1]
        mode, prefix = "prefix", n_img
    if cfg.family == "encdec":
        enc_out, enc_pos = encode(cfg, policy, params, batch["frames"])
    B, S = h.shape[0], h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    return h, positions, mode, prefix, enc_out, enc_pos, n_img


def encode(cfg: ModelConfig, policy: ShardingPolicy, params, frames):
    """Whisper encoder over stub frame embeddings (B, Senc, D)."""
    enc = params["encoder"]
    h = frames + enc["pos_embed"][None, : frames.shape[1]]
    B, S = h.shape[0], h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    def body(hh, bp):
        hh, _ = _block_train(cfg.replace(family="dense", num_experts=0),
                             policy, hh, bp, positions, "bidir", 0)
        return hh, None

    h, _ = _scan(body, h, enc["blocks"], cfg.encoder_layers)
    h = rms_norm(h, enc["final_ln"], cfg.norm_eps)
    return h, positions


# ---------------------------------------------------------------------------
# training forward / loss
# ---------------------------------------------------------------------------


def forward(cfg: ModelConfig, policy: ShardingPolicy, params, batch,
            remat: Optional[str] = None):
    h, positions, mode, prefix, enc_out, enc_pos, n_img = _prepare_inputs(
        cfg, policy, params, batch)
    h, _ = _scan_blocks(cfg, policy, params, h, positions, mode, prefix,
                        enc_out, enc_pos, remat=remat)
    h = rms_norm(h, params["final_ln"], cfg.norm_eps)
    return _lm_logits(cfg, params, h), h, n_img


def forward_loss(cfg: ModelConfig, policy: ShardingPolicy, params, batch,
                 remat: Optional[str] = None):
    """Next-token cross-entropy (+ MTP auxiliary loss when configured)."""
    logits, h, n_img = forward(cfg, policy, params, batch, remat)
    tokens = batch["tokens"]
    B, S = tokens.shape
    # hidden position n_img + t - 1 predicts text token t
    pred = logits[:, n_img: n_img + S - 1]
    labels = tokens[:, 1:]
    weights = (labels != 0).astype(jnp.float32)
    loss = _xent(pred, labels, weights)
    if cfg.mtp_depth:
        loss = loss + 0.3 * _mtp_loss(cfg, policy, params, h, tokens, n_img)
    return loss


def _xent(logits, labels, weights):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - ll) * weights
    return jnp.sum(nll) / jnp.maximum(jnp.sum(weights), 1.0)


def _mtp_loss(cfg, policy, params, h, tokens, n_img):
    """DeepSeek-V3 multi-token prediction: one extra block predicts token
    t+2 from [h_t ; embed(token_{t+1})]."""
    mtp = params["mtp"]
    S = tokens.shape[1]
    h_text = h[:, n_img: n_img + S]
    emb_next = jnp.take(params["embed"], tokens[:, 1:], axis=0)
    x = jnp.concatenate([h_text[:, : S - 1], emb_next], axis=-1)
    x = jnp.einsum("bsk,kd->bsd", x, mtp["proj"])
    B = x.shape[0]
    positions = jnp.broadcast_to(jnp.arange(S - 1)[None, :], (B, S - 1))

    def body(hh, bp):
        hh, _ = _block_train(cfg.replace(num_experts=0, use_mla=False,
                                         family="dense"),
                             policy, hh, bp, positions, "causal", 0)
        return hh, None

    x, _ = _scan(body, x, mtp["blocks"], cfg.mtp_depth)
    x = rms_norm(x, mtp["final_ln"], cfg.norm_eps)
    logits = _lm_logits(cfg, params, x)
    labels = tokens[:, 2:]
    w = (labels != 0).astype(jnp.float32)
    return _xent(logits[:, : S - 2], labels, w)


# ---------------------------------------------------------------------------
# KV / SSM caches
# ---------------------------------------------------------------------------


def build_cache_spec(cfg: ModelConfig, batch_size: int, max_seq: int) -> dict:
    """Nested {name: (shape, logical_axes)} for the decode cache."""
    L = cfg.num_layers
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    spec: dict = {}
    attn_T = max_seq
    if cfg.family == "hybrid" and cfg.attn_window:
        attn_T = min(max_seq, cfg.attn_window)
    if cfg.family == "ssm":
        pass
    elif cfg.use_mla:
        spec["ckv"] = ((L, batch_size, attn_T, cfg.kv_lora_rank),
                       ("layers", "batch", "kv_seq", None))
        spec["krope"] = ((L, batch_size, attn_T, cfg.qk_rope_head_dim),
                         ("layers", "batch", "kv_seq", None))
    else:
        spec["k"] = ((L, batch_size, attn_T, K, hd),
                     ("layers", "batch", "kv_seq", "kv_heads", None))
        spec["v"] = ((L, batch_size, attn_T, K, hd),
                     ("layers", "batch", "kv_seq", "kv_heads", None))
        spec["slot_pos"] = ((L, batch_size, attn_T),
                            ("layers", "batch", "kv_seq"))
    if cfg.family in ("ssm", "hybrid"):
        nh, shd, ns = cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state
        conv_dim = cfg.ssm_d_inner + 2 * ns
        spec["state"] = ((L, batch_size, nh, shd, ns),
                         ("layers", "batch", None, None, None))
        spec["conv"] = ((L, batch_size, cfg.ssm_conv_width - 1, conv_dim),
                        ("layers", "batch", None, None))
    if cfg.family == "encdec":
        Se = cfg.encoder_seq
        spec["xk"] = ((L, batch_size, Se, K, hd),
                      ("layers", "batch", None, "kv_heads", None))
        spec["xv"] = ((L, batch_size, Se, K, hd),
                      ("layers", "batch", None, "kv_heads", None))
    return spec


def init_cache(cfg, batch_size, max_seq, dtype=jnp.float32):
    spec = build_cache_spec(cfg, batch_size, max_seq)
    out = {}
    for name, (shape, axes) in spec.items():
        if name == "slot_pos":
            out[name] = jnp.full(shape, -1, dtype=jnp.int32)
        else:
            out[name] = jnp.zeros(shape, dtype=dtype)
    return out


def abstract_cache(cfg, batch_size, max_seq, dtype=jnp.bfloat16):
    spec = build_cache_spec(cfg, batch_size, max_seq)
    return {
        name: jax.ShapeDtypeStruct(
            shape, jnp.int32 if name == "slot_pos" else dtype)
        for name, (shape, _) in spec.items()
    }


def cache_specs(cfg, batch_size, max_seq, policy: ShardingPolicy):
    """PartitionSpecs per cache leaf; if two logical axes map to the same
    mesh axis (e.g. kv_seq AND kv_heads -> 'model'), the later one is
    dropped — so opting into shard_cache_seq deliberately overrides KV-head
    sharding (flash-decode-style cache streaming)."""
    spec = build_cache_spec(cfg, batch_size, max_seq)
    out = {}
    for name, (shape, axes) in spec.items():
        s = list(policy.spec(*axes))
        seen = set()
        for i, a in enumerate(s):
            names = a if isinstance(a, tuple) else (a,)
            if any(n in seen for n in names if n):
                s[i] = None
            for n in names:
                if n:
                    seen.add(n)
        from jax.sharding import PartitionSpec as P

        out[name] = P(*s)
    return out


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def prefill(cfg: ModelConfig, policy: ShardingPolicy, params, batch,
            max_seq: Optional[int] = None, last=None):
    """Run the full prompt, build the decode cache, return last logits:
    those of the last position, or with ``last`` ((B,) int32) those of
    position ``last[b]`` of each row, e.g. its last real token before
    padding."""
    h, positions, mode, prefix, enc_out, enc_pos, n_img = _prepare_inputs(
        cfg, policy, params, batch)
    B, S = h.shape[0], h.shape[1]
    T = max_seq or S
    h, ys = _scan_blocks(cfg, policy, params, h, positions, mode, prefix,
                         enc_out, enc_pos, collect_kv=True)
    kv_layers, aux_layers = ys
    cache = init_cache(cfg, B, T, dtype=h.dtype)
    if cfg.family == "hybrid" and cfg.attn_window:
        W = min(T, cfg.attn_window)
        # keep the last W positions in ring layout slot = pos % W
        tail = min(W, S)
        pos_tail = jnp.arange(S - tail, S)
        slots = pos_tail % W
        cache["k"] = cache["k"].at[:, :, slots].set(
            kv_layers["k"][:, :, S - tail:])
        cache["v"] = cache["v"].at[:, :, slots].set(
            kv_layers["v"][:, :, S - tail:])
        cache["slot_pos"] = cache["slot_pos"].at[:, :, slots].set(
            jnp.broadcast_to(pos_tail, (cfg.num_layers, B, tail)))
    elif cfg.family != "ssm":
        if cfg.use_mla:
            cache["ckv"] = cache["ckv"].at[:, :, :S].set(kv_layers["ckv"])
            cache["krope"] = cache["krope"].at[:, :, :S].set(
                kv_layers["krope"])
        else:
            cache["k"] = cache["k"].at[:, :, :S].set(kv_layers["k"])
            cache["v"] = cache["v"].at[:, :, :S].set(kv_layers["v"])
            cache["slot_pos"] = cache["slot_pos"].at[:, :, :S].set(
                jnp.broadcast_to(jnp.arange(S), (cfg.num_layers, B, S)))
    if cfg.family in ("ssm", "hybrid"):
        cache["state"] = aux_layers["state"]
        cache["conv"] = aux_layers["conv"]
    if cfg.family == "encdec":
        # cross K/V from encoder output, batched over stacked layer weights
        cache["xk"] = jnp.einsum("bsd,ldhk->lbshk", enc_out,
                                 params["blocks"]["xattn"]["wk"])
        cache["xv"] = jnp.einsum("bsd,ldhk->lbshk", enc_out,
                                 params["blocks"]["xattn"]["wv"])
    h = h[:, -1:] if last is None else jnp.take_along_axis(
        h, last[:, None, None], axis=1)
    logits = _lm_logits(cfg, params,
                        rms_norm(h, params["final_ln"], cfg.norm_eps))
    return logits[:, 0], cache


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def _block_decode(cfg, policy, h, bp, cache_l, pos):
    new_cache = dict(cache_l)
    x = rms_norm(h, bp["ln1"], cfg.norm_eps)
    window = cfg.attn_window if cfg.family == "hybrid" else 0
    if cfg.family == "ssm":
        mix, st, cv = ssm_decode(cfg, policy, bp["ssm"], x,
                                 cache_l["state"], cache_l["conv"])
        new_cache.update(state=st, conv=cv)
    elif cfg.family == "hybrid":
        a, k, v, sp = attention_decode(cfg, policy, bp["attn"], x,
                                       cache_l["k"], cache_l["v"],
                                       cache_l["slot_pos"], pos,
                                       window=window)
        s, st, cv = ssm_decode(cfg, policy, bp["ssm"], x,
                               cache_l["state"], cache_l["conv"])
        mix = 0.5 * (rms_norm(a, bp["attn_norm"], cfg.norm_eps)
                     + rms_norm(s, bp["ssm_norm"], cfg.norm_eps))
        new_cache.update(k=k, v=v, slot_pos=sp, state=st, conv=cv)
    elif cfg.use_mla:
        mix, ckv, krope = mla_decode(cfg, policy, bp["mla"], x,
                                     cache_l["ckv"], cache_l["krope"], pos)
        new_cache.update(ckv=ckv, krope=krope)
    else:
        mix, k, v, sp = attention_decode(cfg, policy, bp["attn"], x,
                                         cache_l["k"], cache_l["v"],
                                         cache_l["slot_pos"], pos)
        new_cache.update(k=k, v=v, slot_pos=sp)
    h = h + mix
    if cfg.family == "encdec":
        xx = rms_norm(h, bp["ln_x"], cfg.norm_eps)
        # cross-attention: every encoder slot is visible (slot_pos = 0 ≤ pos)
        enc_slots = jnp.zeros(cache_l["xk"].shape[:2], jnp.int32)
        xa, _, _, _ = attention_decode(
            cfg, policy, bp["xattn"], xx, cache_l["xk"], cache_l["xv"],
            enc_slots, pos, cross=True)
        h = h + xa
    f = _ffn(cfg, policy, bp, rms_norm(h, bp["ln2"], cfg.norm_eps))
    if f is not None:
        h = h + f
    return h, new_cache


def decode_step(cfg: ModelConfig, policy: ShardingPolicy, params, cache,
                tokens, pos):
    """One decode step. tokens: (B,) int32, pos: (B,) absolute positions.
    Returns (logits (B,V), new cache)."""
    h = _embed_tokens(cfg, policy, params, tokens[:, None])

    def body(hh, inp):
        bp, cache_l = inp
        hh, new_cache_l = _block_decode(cfg, policy, hh, bp, cache_l, pos)
        return hh, new_cache_l

    h, new_cache = _scan(body, h, (params["blocks"], cache), cfg.num_layers)
    h = rms_norm(h, params["final_ln"], cfg.norm_eps)
    logits = _lm_logits(cfg, params, h)
    return logits[:, 0], new_cache
