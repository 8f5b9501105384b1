"""Unified functional transformer covering all six assigned families.

Public API (all pure functions):
    init_params(key, cfg, dtype)                          -> params
    forward(params, cfg, batch, ...)                      -> logits (train path)
    init_cache(cfg, batch, max_len, dtype, long_context)  -> cache
    prefill(params, cfg, batch, max_len, ...)             -> (logits, cache)
    decode_step(params, cfg, cache, tokens, pos, ...)     -> (logits, cache)

``batch`` is a dict: tokens (B,S) int32, plus family extras:
    encdec: frames  (B, src, d)   — stubbed audio frontend output
    vlm:    patches (B, V, d)     — stubbed vision encoder output
            positions (B, S, 3)   — M-RoPE position ids

Layer stacks are scanned (params stacked on a leading L axis) with optional
remat, so compiled HLO stays one-layer-sized for the multi-pod dry-run.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import griffin as gr
from repro.models import rwkv6 as rw
from repro.models.common import (apply_mrope, apply_rope, attention,
                                 decode_attend, dense_init, embed_init,
                                 init_attention, init_mlp, init_rmsnorm, mlp,
                                 rmsnorm)
from repro.models.moe import init_moe, moe_ffn

Params = Dict[str, Any]


# --------------------------------------------------------------------------- #
# helpers
# --------------------------------------------------------------------------- #

def _stack_init(fn, key, n: int):
    """vmap an init over n layer keys -> params stacked on leading axis."""
    return jax.vmap(fn)(jax.random.split(key, n))


def attn_window(cfg: ModelConfig, long_context: bool) -> Optional[int]:
    """Effective sliding window for dense-ish self-attention."""
    if long_context:
        w = cfg.long_context_window
        if cfg.window_size:
            w = min(w, cfg.window_size)
        return w
    return cfg.window_size


def cache_width(cfg: ModelConfig, max_len: int, long_context: bool) -> int:
    w = attn_window(cfg, long_context)
    return min(max_len, w) if w else max_len


def ring_kpos(width: int, pos):
    """Absolute position held by each ring-buffer slot at decode step `pos`.
    slot i holds p = pos - ((pos - i) mod width); p < 0 -> empty."""
    i = jnp.arange(width)
    return pos - jnp.mod(pos - i, width)


# --------------------------------------------------------------------------- #
# generic attention layer (dense / moe / vlm / encdec-self / griffin-local)
# --------------------------------------------------------------------------- #

def _init_attn_layer(key, cfg: ModelConfig, dtype, *, use_moe: bool):
    ks = jax.random.split(key, 4)
    p = {
        "ln1": init_rmsnorm(cfg.d_model, dtype),
        "attn": init_attention(ks[0], cfg, dtype),
        "ln2": init_rmsnorm(cfg.d_model, dtype),
    }
    if use_moe:
        p["moe"] = init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = init_mlp(ks[2], cfg, dtype)
    return p


def _qkv(p, cfg: ModelConfig, x):
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, KV, hd)
    v = (x @ p["wv"]).reshape(B, S, KV, hd)
    return q, k, v


def _rope_qk(cfg: ModelConfig, q, k, positions, mrope_positions=None):
    if cfg.mrope and mrope_positions is not None:
        return (apply_mrope(q, mrope_positions, cfg.rope_theta),
                apply_mrope(k, mrope_positions, cfg.rope_theta))
    return (apply_rope(q, positions, cfg.rope_theta),
            apply_rope(k, positions, cfg.rope_theta))


def _attn_layer_fwd(p, cfg: ModelConfig, x, *, window, q_offset=0,
                    mrope_positions=None, prefix_kv=None, return_kv=False):
    """Residual attention sub-block + FFN sub-block (full sequence)."""
    B, S, _ = x.shape
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    q, k, v = _qkv(p["attn"], cfg, h)
    positions = q_offset + jnp.arange(S)
    q, k = _rope_qk(cfg, q, k, positions, mrope_positions)
    if prefix_kv is not None:                      # cached-context prefill
        k = jnp.concatenate([prefix_kv[0], k], axis=1)
        v = jnp.concatenate([prefix_kv[1], v], axis=1)
    o = attention(q, k, v, q_offset=q_offset, window=window)
    x = x + o.reshape(B, S, -1) @ p["attn"]["wo"]

    h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
    aux = {}
    if "moe" in p:
        y, aux = moe_ffn(p["moe"], h2, cfg)
    else:
        y = mlp(p["mlp"], h2, cfg)
    x = x + y
    if return_kv:
        return x, (k, v), aux
    return x, aux


def _attn_layer_decode(p, cfg: ModelConfig, x_t, k_cache, v_cache, pos, *,
                       window, mrope_positions=None):
    """x_t: (B,1,d); caches: (B,W,KV,hd); pos scalar."""
    B = x_t.shape[0]
    W = k_cache.shape[1]
    h = rmsnorm(p["ln1"], x_t, cfg.norm_eps)
    q, k, v = _qkv(p["attn"], cfg, h)
    pos_arr = jnp.full((1,), pos)
    q, k = _rope_qk(cfg, q, k, pos_arr, mrope_positions)
    slot = jnp.mod(pos, W)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k, (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v, (0, slot, 0, 0))
    kpos = ring_kpos(W, pos)
    o = decode_attend(q, k_cache, v_cache, kpos, pos, window=window)
    x_t = x_t + o.reshape(B, 1, -1) @ p["attn"]["wo"]

    h2 = rmsnorm(p["ln2"], x_t, cfg.norm_eps)
    if "moe" in p:
        y, _ = moe_ffn(p["moe"], h2, cfg)
    else:
        y = mlp(p["mlp"], h2, cfg)
    return x_t + y, k_cache, v_cache


# --------------------------------------------------------------------------- #
# RWKV6 layer
# --------------------------------------------------------------------------- #

def _init_rwkv_layer(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 2)
    return {
        "ln1": init_rmsnorm(cfg.d_model, dtype),
        "tmix": rw.init_time_mix(ks[0], cfg, dtype),
        "ln2": init_rmsnorm(cfg.d_model, dtype),
        "cmix": rw.init_channel_mix(ks[1], cfg, dtype),
    }


def _rwkv_layer_fwd(p, cfg, x, state):
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    o, x_tm, wkv = rw.time_mix(p["tmix"], cfg, h, state["x_tm"], state["wkv"])
    x = x + o
    h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
    o2, x_cm = rw.channel_mix(p["cmix"], h2, state["x_cm"])
    x = x + o2
    return x, {"wkv": wkv, "x_tm": x_tm, "x_cm": x_cm}


def _rwkv_empty_state(cfg: ModelConfig, B: int, dtype):
    H, hd = cfg.num_rwkv_heads, cfg.rwkv_head_dim
    return {"wkv": jnp.zeros((B, H, hd, hd), jnp.float32),
            "x_tm": jnp.zeros((B, cfg.d_model), dtype),
            "x_cm": jnp.zeros((B, cfg.d_model), dtype)}


# --------------------------------------------------------------------------- #
# Griffin unit (rec, rec, local-attn), each with its own MLP
# --------------------------------------------------------------------------- #

def _init_rec_layer(key, cfg, dtype):
    ks = jax.random.split(key, 2)
    return {"ln1": init_rmsnorm(cfg.d_model, dtype),
            "rg": gr.init_rglru_block(ks[0], cfg, dtype),
            "ln2": init_rmsnorm(cfg.d_model, dtype),
            "mlp": init_mlp(ks[1], cfg, dtype)}


def _rec_layer_fwd(p, cfg, x, state):
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    o, state = gr.rglru_block(p["rg"], h, state)
    x = x + o
    x = x + mlp(p["mlp"], rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
    return x, state


def _rec_layer_decode(p, cfg, x_t, state):
    h = rmsnorm(p["ln1"], x_t, cfg.norm_eps)
    o, state = gr.rglru_block_step(p["rg"], h[:, 0], state)
    x_t = x_t + o[:, None]
    x_t = x_t + mlp(p["mlp"], rmsnorm(p["ln2"], x_t, cfg.norm_eps), cfg)
    return x_t, state


def _init_griffin_unit(key, cfg, dtype):
    ks = jax.random.split(key, 3)
    return {"rec1": _init_rec_layer(ks[0], cfg, dtype),
            "rec2": _init_rec_layer(ks[1], cfg, dtype),
            "attn": _init_attn_layer(ks[2], cfg, dtype, use_moe=False)}


def griffin_layout(cfg: ModelConfig):
    """(num_units, num_tail_rec) such that 3*U + tail == num_layers."""
    units = cfg.num_layers // 3
    tail = cfg.num_layers - 3 * units
    return units, tail


# --------------------------------------------------------------------------- #
# enc-dec layers
# --------------------------------------------------------------------------- #

def _init_enc_layer(key, cfg, dtype):
    return _init_attn_layer(key, cfg, dtype, use_moe=False)


def _enc_layer_fwd(p, cfg, x):
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    q, k, v = _qkv(p["attn"], cfg, h)
    positions = jnp.arange(x.shape[1])
    q, k = _rope_qk(cfg, q, k, positions)
    o = attention(q, k, v, causal=False)           # bidirectional
    x = x + o.reshape(x.shape[0], x.shape[1], -1) @ p["attn"]["wo"]
    x = x + mlp(p["mlp"], rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
    return x


def _init_dec_layer(key, cfg, dtype):
    ks = jax.random.split(key, 3)
    return {
        "ln1": init_rmsnorm(cfg.d_model, dtype),
        "self_attn": init_attention(ks[0], cfg, dtype),
        "ln_x": init_rmsnorm(cfg.d_model, dtype),
        "cross_attn": init_attention(ks[1], cfg, dtype),
        "ln2": init_rmsnorm(cfg.d_model, dtype),
        "mlp": init_mlp(ks[2], cfg, dtype),
    }


def _dec_layer_fwd(p, cfg, x, memory, *, window=None, return_kv=False):
    B, S, _ = x.shape
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    q, k, v = _qkv(p["self_attn"], cfg, h)
    positions = jnp.arange(S)
    q, k = _rope_qk(cfg, q, k, positions)
    o = attention(q, k, v, window=window)
    x = x + o.reshape(B, S, -1) @ p["self_attn"]["wo"]

    hx = rmsnorm(p["ln_x"], x, cfg.norm_eps)
    qx = (hx @ p["cross_attn"]["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    ck = (memory @ p["cross_attn"]["wk"]).reshape(
        B, -1, cfg.num_kv_heads, cfg.head_dim)
    cv = (memory @ p["cross_attn"]["wv"]).reshape(
        B, -1, cfg.num_kv_heads, cfg.head_dim)
    ox = attention(qx, ck, cv, causal=False)
    x = x + ox.reshape(B, S, -1) @ p["cross_attn"]["wo"]

    x = x + mlp(p["mlp"], rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
    if return_kv:
        return x, (k, v, ck, cv)
    return x


def _dec_layer_decode(p, cfg, x_t, sk, sv, ck, cv, pos, *, window=None):
    B = x_t.shape[0]
    W = sk.shape[1]
    h = rmsnorm(p["ln1"], x_t, cfg.norm_eps)
    q, k, v = _qkv(p["self_attn"], cfg, h)
    q, k = _rope_qk(cfg, q, k, jnp.full((1,), pos))
    slot = jnp.mod(pos, W)
    sk = jax.lax.dynamic_update_slice(sk, k, (0, slot, 0, 0))
    sv = jax.lax.dynamic_update_slice(sv, v, (0, slot, 0, 0))
    o = decode_attend(q, sk, sv, ring_kpos(W, pos), pos, window=window)
    x_t = x_t + o.reshape(B, 1, -1) @ p["self_attn"]["wo"]

    hx = rmsnorm(p["ln_x"], x_t, cfg.norm_eps)
    qx = (hx @ p["cross_attn"]["wq"]).reshape(B, 1, cfg.num_heads, cfg.head_dim)
    src = ck.shape[1]
    ox = decode_attend(qx, ck, cv, jnp.arange(src), jnp.asarray(src))
    x_t = x_t + ox.reshape(B, 1, -1) @ p["cross_attn"]["wo"]

    x_t = x_t + mlp(p["mlp"], rmsnorm(p["ln2"], x_t, cfg.norm_eps), cfg)
    return x_t, sk, sv


# --------------------------------------------------------------------------- #
# top level: init
# --------------------------------------------------------------------------- #

@functools.partial(jax.jit, static_argnames=("cfg", "dtype"))
def init_params(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    """Random parameters, built as one program: each weight is drawn in
    float32 and cast inside a fusion, so no float32 copy of a whole layer
    stack is ever held (8B-class stacks would not fit on the device)."""
    ks = jax.random.split(key, 8)
    V, d = cfg.padded_vocab, cfg.d_model
    p: Params = {
        "embed": embed_init(ks[0], V, d, dtype),
        "final_ln": init_rmsnorm(d, dtype),
        "unembed": dense_init(ks[1], d, V, dtype),
    }
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        use_moe = fam == "moe"
        p["layers"] = _stack_init(
            lambda k: _init_attn_layer(k, cfg, dtype, use_moe=use_moe),
            ks[2], cfg.num_layers)
        if fam == "vlm":
            p["patch_proj"] = dense_init(ks[3], d, d, dtype)
    elif fam == "ssm":
        p["layers"] = _stack_init(
            lambda k: _init_rwkv_layer(k, cfg, dtype), ks[2], cfg.num_layers)
    elif fam == "hybrid":
        U, tail = griffin_layout(cfg)
        p["units"] = _stack_init(
            lambda k: _init_griffin_unit(k, cfg, dtype), ks[2], U)
        if tail:
            p["tail"] = _stack_init(
                lambda k: _init_rec_layer(k, cfg, dtype), ks[3], tail)
    elif fam == "encdec":
        p["frames_proj"] = dense_init(ks[3], d, d, dtype)
        p["encoder"] = _stack_init(
            lambda k: _init_enc_layer(k, cfg, dtype), ks[4], cfg.encoder_layers)
        p["enc_ln"] = init_rmsnorm(d, dtype)
        p["decoder"] = _stack_init(
            lambda k: _init_dec_layer(k, cfg, dtype), ks[5], cfg.num_layers)
    else:
        raise ValueError(fam)
    return p


# --------------------------------------------------------------------------- #
# top level: full-sequence forward (training / no-cache prefill)
# --------------------------------------------------------------------------- #

def _embed_sequence(params, cfg: ModelConfig, batch):
    """Token (+ modality-stub) embedding -> (B, S, d)."""
    x = params["embed"][batch["tokens"]]
    if cfg.family == "vlm" and "patches" in batch:
        vis = batch["patches"].astype(x.dtype) @ params["patch_proj"]
        x = jnp.concatenate([vis, x], axis=1)
    return x


def _encode(params, cfg: ModelConfig, frames):
    x = frames @ params["frames_proj"]

    def body(carry, lp):
        return _enc_layer_fwd(lp, cfg, carry), None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return rmsnorm(params["enc_ln"], x, cfg.norm_eps)


def forward(params: Params, cfg: ModelConfig, batch, *, long_context=False,
            remat=True, return_hidden=False, with_aux=False):
    """Full-sequence logits (B, S_total, padded_vocab).

    return_hidden: return post-final-norm hidden states instead of logits
    (training computes the vocab projection chunked — see train.steps).
    with_aux: also return dict of per-layer aux (MoE load-balance losses).
    """
    fam = cfg.family
    x = _embed_sequence(params, cfg, batch)
    window = attn_window(cfg, long_context)
    mrope_positions = batch.get("positions") if cfg.mrope else None
    aux_out: Dict[str, Any] = {}

    ck = jax.checkpoint if remat else (lambda f: f)

    if fam in ("dense", "moe", "vlm"):
        def body(carry, lp):
            y, aux = _attn_layer_fwd(lp, cfg, carry, window=window,
                                     mrope_positions=mrope_positions)
            return y, aux
        x, auxs = jax.lax.scan(ck(body), x, params["layers"])
        if with_aux and auxs:
            aux_out = {k: jnp.mean(v) for k, v in auxs.items()}

    elif fam == "ssm":
        B = x.shape[0]
        st = _rwkv_empty_state(cfg, B, x.dtype)

        def body(carry, lp):
            y, _ = _rwkv_layer_fwd(lp, cfg, carry, st)
            return y, None
        x, _ = jax.lax.scan(ck(body), x, params["layers"])

    elif fam == "hybrid":
        B = x.shape[0]
        rst = gr.init_recurrent_state(cfg, B, x.dtype)

        def unit_body(carry, up):
            y = carry
            y, _ = _rec_layer_fwd(up["rec1"], cfg, y, rst)
            y, _ = _rec_layer_fwd(up["rec2"], cfg, y, rst)
            y, _ = _attn_layer_fwd(up["attn"], cfg, y, window=cfg.local_window)
            return y, None
        x, _ = jax.lax.scan(ck(unit_body), x, params["units"])
        if "tail" in params:
            def tail_body(carry, lp):
                y, _ = _rec_layer_fwd(lp, cfg, carry, rst)
                return y, None
            x, _ = jax.lax.scan(ck(tail_body), x, params["tail"])

    elif fam == "encdec":
        memory = _encode(params, cfg, batch["frames"].astype(x.dtype))

        def body(carry, lp):
            return _dec_layer_fwd(lp, cfg, carry, memory, window=window), None
        x, _ = jax.lax.scan(ck(body), x, params["decoder"])

    x = rmsnorm(params["final_ln"], x, cfg.norm_eps)
    out = x if return_hidden else x @ params["unembed"]
    if with_aux:
        return out, aux_out
    return out


# --------------------------------------------------------------------------- #
# top level: cache init / prefill / decode
# --------------------------------------------------------------------------- #

def init_cache(cfg: ModelConfig, batch_size: int, max_len: int,
               dtype=jnp.bfloat16, long_context=False):
    B, L = batch_size, cfg.num_layers
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        W = cache_width(cfg, max_len, long_context)
        return {"k": jnp.zeros((L, B, W, KV, hd), dtype),
                "v": jnp.zeros((L, B, W, KV, hd), dtype)}
    if fam == "ssm":
        H, rhd = cfg.num_rwkv_heads, cfg.rwkv_head_dim
        return {"wkv": jnp.zeros((L, B, H, rhd, rhd), jnp.float32),
                "x_tm": jnp.zeros((L, B, cfg.d_model), dtype),
                "x_cm": jnp.zeros((L, B, cfg.d_model), dtype)}
    if fam == "hybrid":
        U, tail = griffin_layout(cfg)
        Wl = min(max_len, cfg.local_window)
        dr, cw = cfg.rnn_width, cfg.conv_width
        cache = {"units": {
            "rec1_h": jnp.zeros((U, B, dr), jnp.float32),
            "rec1_conv": jnp.zeros((U, B, cw - 1, dr), dtype),
            "rec2_h": jnp.zeros((U, B, dr), jnp.float32),
            "rec2_conv": jnp.zeros((U, B, cw - 1, dr), dtype),
            "k": jnp.zeros((U, B, Wl, KV, hd), dtype),
            "v": jnp.zeros((U, B, Wl, KV, hd), dtype)}}
        if tail:
            cache["tail"] = {
                "h": jnp.zeros((tail, B, dr), jnp.float32),
                "conv": jnp.zeros((tail, B, cw - 1, dr), dtype)}
        return cache
    if fam == "encdec":
        W = cache_width(cfg, max_len, long_context)
        src = cfg.source_len
        return {"self_k": jnp.zeros((L, B, W, KV, hd), dtype),
                "self_v": jnp.zeros((L, B, W, KV, hd), dtype),
                "cross_k": jnp.zeros((L, B, src, KV, hd), dtype),
                "cross_v": jnp.zeros((L, B, src, KV, hd), dtype)}
    raise ValueError(fam)


def _place_kv_in_ring(k_full, W: int):
    """k_full: (B, S, KV, hd) -> ring cache (B, W, KV, hd) holding the last
    min(S, W) tokens at slots pos % W."""
    B, S = k_full.shape[:2]
    if S <= W:
        pad = [(0, 0), (0, W - S), (0, 0), (0, 0)]
        return jnp.pad(k_full, pad)
    last = k_full[:, S - W:]
    ps = jnp.arange(S - W, S) % W
    return jnp.zeros((B, W) + k_full.shape[2:], k_full.dtype).at[:, ps].set(last)


def prefill(params: Params, cfg: ModelConfig, batch, max_len: int, *,
            long_context=False, prefix_cache=None, prefix_len: int = 0):
    """Process a full prompt, returning (logits, cache) ready for decode.

    prefix_cache/prefix_len: reuse a stored KV prefix (the paper's cache-hit
    path) — new tokens attend to prefix keys with q_offset = prefix_len.
    Dense-family only (recurrent families snapshot whole states instead).
    """
    fam = cfg.family
    x = _embed_sequence(params, cfg, batch)
    B, S = x.shape[0], x.shape[1]
    window = attn_window(cfg, long_context)
    mrope_positions = batch.get("positions") if cfg.mrope else None
    W = cache_width(cfg, max_len, long_context)

    if fam in ("dense", "moe", "vlm"):
        if prefix_cache is not None:
            pk = prefix_cache["k"][:, :, :prefix_len]
            pv = prefix_cache["v"][:, :, :prefix_len]
        else:
            pk = pv = None

        def body(carry, xs):
            if pk is not None:
                lp, pkl, pvl = xs
                prefix_kv = (pkl, pvl)
            else:
                lp = xs
                prefix_kv = None
            y, (k, v), _ = _attn_layer_fwd(
                lp, cfg, carry, window=window, q_offset=prefix_len,
                mrope_positions=mrope_positions, prefix_kv=prefix_kv,
                return_kv=True)
            return y, (_place_kv_in_ring(k, W), _place_kv_in_ring(v, W))

        xs = (params["layers"], pk, pv) if pk is not None else params["layers"]
        x, (kc, vc) = jax.lax.scan(body, x, xs)
        cache = {"k": kc, "v": vc}

    elif fam == "ssm":
        st0 = _rwkv_empty_state(cfg, B, x.dtype)

        def body(carry, lp):
            y, st = _rwkv_layer_fwd(lp, cfg, carry, st0)
            return y, st
        x, sts = jax.lax.scan(body, x, params["layers"])
        cache = {"wkv": sts["wkv"], "x_tm": sts["x_tm"], "x_cm": sts["x_cm"]}

    elif fam == "hybrid":
        rst0 = gr.init_recurrent_state(cfg, B, x.dtype)
        Wl = min(max_len, cfg.local_window)

        def unit_body(carry, up):
            y = carry
            y, s1 = _rec_layer_fwd(up["rec1"], cfg, y, rst0)
            y, s2 = _rec_layer_fwd(up["rec2"], cfg, y, rst0)
            y, (k, v), _ = _attn_layer_fwd(up["attn"], cfg, y,
                                           window=cfg.local_window,
                                           return_kv=True)
            out = {"rec1_h": s1["h"], "rec1_conv": s1["conv"],
                   "rec2_h": s2["h"], "rec2_conv": s2["conv"],
                   "k": _place_kv_in_ring(k, Wl),
                   "v": _place_kv_in_ring(v, Wl)}
            return y, out
        x, ucache = jax.lax.scan(unit_body, x, params["units"])
        cache = {"units": ucache}
        if "tail" in params:
            def tail_body(carry, lp):
                y, st = _rec_layer_fwd(lp, cfg, carry, rst0)
                return y, st
            x, tsts = jax.lax.scan(tail_body, x, params["tail"])
            cache["tail"] = {"h": tsts["h"], "conv": tsts["conv"]}

    elif fam == "encdec":
        memory = _encode(params, cfg, batch["frames"].astype(x.dtype))

        def body(carry, lp):
            y, (k, v, ckv, cvv) = _dec_layer_fwd(lp, cfg, carry, memory,
                                                 window=window, return_kv=True)
            return y, (_place_kv_in_ring(k, W), _place_kv_in_ring(v, W),
                       ckv, cvv)
        x, (sk, sv, ck_, cv_) = jax.lax.scan(body, x, params["decoder"])
        cache = {"self_k": sk, "self_v": sv, "cross_k": ck_, "cross_v": cv_}
    else:
        raise ValueError(fam)

    x = rmsnorm(params["final_ln"], x, cfg.norm_eps)
    return x @ params["unembed"], cache


def decode_step(params: Params, cfg: ModelConfig, cache, tokens, pos, *,
                long_context=False, mrope_positions=None):
    """One autoregressive step. tokens: (B,1) int32; pos: scalar int32 —
    the absolute position being written. Returns (logits (B,1,V), cache)."""
    fam = cfg.family
    x = params["embed"][tokens]
    window = attn_window(cfg, long_context)
    if cfg.mrope and mrope_positions is None:
        B = tokens.shape[0]
        mrope_positions = jnp.broadcast_to(
            jnp.full((1, 1, 3), 0, jnp.int32) + pos, (B, 1, 3))

    if fam in ("dense", "moe", "vlm"):
        def body(carry, xs):
            lp, kc, vc = xs
            y, kc, vc = _attn_layer_decode(lp, cfg, carry, kc, vc, pos,
                                           window=window,
                                           mrope_positions=mrope_positions)
            return y, (kc, vc)
        x, (kc, vc) = jax.lax.scan(body, x, (params["layers"],
                                             cache["k"], cache["v"]))
        cache = {"k": kc, "v": vc}

    elif fam == "ssm":
        # single-token time/channel mix via the full-seq path with S=1
        def body(carry, xs):
            lp, wkv, x_tm, x_cm = xs
            st = {"wkv": wkv, "x_tm": x_tm, "x_cm": x_cm}
            y, st = _rwkv_layer_fwd(lp, cfg, carry, st)
            return y, (st["wkv"], st["x_tm"], st["x_cm"])
        x, (wkv, x_tm, x_cm) = jax.lax.scan(
            body, x, (params["layers"], cache["wkv"], cache["x_tm"],
                      cache["x_cm"]))
        cache = {"wkv": wkv, "x_tm": x_tm, "x_cm": x_cm}

    elif fam == "hybrid":
        uc = cache["units"]

        def unit_body(carry, xs):
            up, c = xs
            y = carry
            y, s1 = _rec_layer_decode(up["rec1"], cfg, y,
                                      {"h": c["rec1_h"], "conv": c["rec1_conv"]})
            y, s2 = _rec_layer_decode(up["rec2"], cfg, y,
                                      {"h": c["rec2_h"], "conv": c["rec2_conv"]})
            y, kc, vc = _attn_layer_decode(up["attn"], cfg, y, c["k"], c["v"],
                                           pos, window=cfg.local_window)
            out = {"rec1_h": s1["h"], "rec1_conv": s1["conv"],
                   "rec2_h": s2["h"], "rec2_conv": s2["conv"],
                   "k": kc, "v": vc}
            return y, out
        x, uc = jax.lax.scan(unit_body, x, (params["units"], uc))
        cache = dict(cache, units=uc)
        if "tail" in params:
            def tail_body(carry, xs):
                lp, h, conv = xs
                y, st = _rec_layer_decode(lp, cfg, carry,
                                          {"h": h, "conv": conv})
                return y, (st["h"], st["conv"])
            x, (th, tconv) = jax.lax.scan(
                tail_body, x, (params["tail"], cache["tail"]["h"],
                               cache["tail"]["conv"]))
            cache = dict(cache, tail={"h": th, "conv": tconv})

    elif fam == "encdec":
        def body(carry, xs):
            lp, sk, sv, ckv, cvv = xs
            y, sk, sv = _dec_layer_decode(lp, cfg, carry, sk, sv, ckv, cvv,
                                          pos, window=window)
            return y, (sk, sv)
        x, (sk, sv) = jax.lax.scan(
            body, x, (params["decoder"], cache["self_k"], cache["self_v"],
                      cache["cross_k"], cache["cross_v"]))
        cache = dict(cache, self_k=sk, self_v=sv)
    else:
        raise ValueError(fam)

    x = rmsnorm(params["final_ln"], x, cfg.norm_eps)
    return x @ params["unembed"], cache
