"""Unified decoder stack covering all ten assigned architectures.

A model is a stack of identical *blocks* run under ``lax.scan`` (small
HLO, fast SPMD compile).  Each block is ``attn_every`` layers; a layer is
(mixer, ffn) where mixer in {attention, mamba2, rwkv-time-mix} and ffn in
{dense MLP, MoE, rwkv-channel-mix}.  Whisper adds an encoder stack and
cross-attention; LLaVA swaps the first image-token embeddings for
projected patch embeddings (frontend stubbed per assignment).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..sharding.rules import logical_constraint
from .common import ArchConfig
from .layers import (_normal, apply_rope, attention_apply, attention_decode,
                     attention_init, chunked_xent, linear, linear_init,
                     mlp_apply, mlp_init, rmsnorm, rmsnorm_init)
from .moe import moe_apply, moe_init
from .ssm import ssm_decode_step, ssm_scan_chunked


# ===========================================================================
# mamba2 mixer (jamba's SSM layers; see DESIGN.md §5 hardware adaptation)
# ===========================================================================
def _mamba_dims(cfg: ArchConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    return d_inner, n_heads


def mamba_init(key, cfg: ArchConfig, dtype):
    s = cfg.ssm
    d, (d_inner, H) = cfg.d_model, _mamba_dims(cfg)
    K = s.d_state
    ks = jax.random.split(key, 8)
    scale = 1.0 / math.sqrt(d)
    return {
        "wx": linear_init(ks[0], d, d_inner, dtype),
        "wz": linear_init(ks[1], d, d_inner, dtype),
        "wB": linear_init(ks[2], d, K, dtype),
        "wC": linear_init(ks[3], d, K, dtype),
        "wdt": linear_init(ks[4], d, H, dtype),
        "out": linear_init(ks[5], d_inner, d, dtype,
                           scale=1.0 / math.sqrt(d_inner * 2 * cfg.n_layers)),
        "conv_w": _normal(ks[6], (s.d_conv, d_inner), dtype, 0.5),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "dt_bias": jnp.full((H,), -4.6, jnp.float32),  # softplus ~ 0.01
        "D": jnp.ones((H,), jnp.float32),
        "norm_y": rmsnorm_init(d_inner, dtype),
    }


def _causal_conv(x, w, state=None):
    """Depthwise causal conv via shifts.  x: (B,S,D); w: (k,D).
    state: (B, k-1, D) trailing inputs from the previous segment."""
    kk = w.shape[0]
    y = x * w[kk - 1]
    for i in range(1, kk):
        if state is None:
            shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, :x.shape[1]]
        else:
            ext = jnp.concatenate([state, x], axis=1)
            shifted = lax.dynamic_slice_in_dim(
                ext, state.shape[1] - i, x.shape[1], axis=1)
        y = y + shifted * w[kk - 1 - i]
    return y


def mamba_apply(p, x, cfg: ArchConfig, state=None):
    """x: (B,S,d).  Returns (y, (ssm_state, conv_state))."""
    s = cfg.ssm
    B, S, d = x.shape
    d_inner, H = _mamba_dims(cfg)
    K, dh = s.d_state, s.head_dim
    xz = linear(p["wx"], x)
    z = linear(p["wz"], x)
    conv_state_in = None if state is None else state[1]
    xc = jax.nn.silu(_causal_conv(xz, p["conv_w"].astype(x.dtype),
                                  conv_state_in))
    xc = logical_constraint(xc, "batch", None, "model")
    Bt = linear(p["wB"], x)                     # (B,S,K)
    Ct = linear(p["wC"], x)                     # (B,S,K)
    dt = jax.nn.softplus(linear(p["wdt"], x).astype(jnp.float32)
                         + p["dt_bias"])        # (B,S,H)
    g = (-jnp.exp(p["A_log"]) * dt)[..., None]  # (B,S,H,1) log decay
    v = (xc.reshape(B, S, H, dh)
         * dt.astype(x.dtype)[..., None])       # dt-scaled input
    q = jnp.broadcast_to(Ct[:, :, None, :], (B, S, H, K))
    k = jnp.broadcast_to(Bt[:, :, None, :], (B, S, H, K))
    ssm_state_in = None if state is None else state[0]
    y, ssm_state = ssm_scan_chunked(q, k, v, g, initial_state=ssm_state_in,
                                    chunk=min(s.chunk, S),
                                    subchunk=min(s.subchunk, S),
                                    scalar_decay=True,
                                    unroll=cfg.unroll_scans,
                                    shard_constrain=cfg.ssm_shard_constraints,
                                    io_dtype=jnp.bfloat16 if cfg.ssm_bf16_io
                                    else jnp.float32)
    y = y + p["D"].astype(x.dtype)[None, None, :, None] \
        * xc.reshape(B, S, H, dh)
    y = y.reshape(B, S, d_inner)
    y = rmsnorm(p["norm_y"], y * jax.nn.silu(z), cfg.norm_eps)
    out = linear(p["out"], y)
    conv_state = (xz[:, S - (s.d_conv - 1):, :] if state is None
                  else jnp.concatenate([conv_state_in, xz], axis=1)
                  [:, -(s.d_conv - 1):, :])
    return out, (ssm_state, conv_state)


def mamba_decode(p, x, cfg: ArchConfig, state):
    """One token.  x: (B,1,d); state = (ssm (B,H,K,V), conv (B,k-1,D))."""
    s = cfg.ssm
    B, _, d = x.shape
    d_inner, H = _mamba_dims(cfg)
    K, dh = s.d_state, s.head_dim
    ssm_state, conv_state = state
    xz = linear(p["wx"], x)                     # (B,1,d_inner)
    z = linear(p["wz"], x)
    ext = jnp.concatenate([conv_state, xz], axis=1)  # (B,k,d_inner)
    w = p["conv_w"].astype(x.dtype)
    xc = jax.nn.silu(jnp.einsum("bkd,kd->bd", ext, w))[:, None]
    Bt, Ct = linear(p["wB"], x), linear(p["wC"], x)
    dt = jax.nn.softplus(linear(p["wdt"], x).astype(jnp.float32)
                         + p["dt_bias"])[:, 0]  # (B,H)
    g = -jnp.exp(p["A_log"]) * dt               # (B,H)
    v = xc.reshape(B, H, dh) * dt.astype(x.dtype)[..., None]
    q = jnp.broadcast_to(Ct[:, 0, None, :], (B, H, K))
    k = jnp.broadcast_to(Bt[:, 0, None, :], (B, H, K))
    y, ssm_new = ssm_decode_step(q, k, v, g[..., None] *
                                 jnp.ones((1, 1, K), jnp.float32), ssm_state)
    y = y + p["D"].astype(x.dtype)[None, :, None] * xc.reshape(B, H, dh)
    y = y.reshape(B, 1, d_inner)
    y = rmsnorm(p["norm_y"], y * jax.nn.silu(z), cfg.norm_eps)
    return linear(p["out"], y), (ssm_new, ext[:, 1:, :])


# ===========================================================================
# rwkv6 mixer + channel mix ("Finch": data-dependent decay)
# ===========================================================================
def _rwkv_dims(cfg: ArchConfig):
    dh = cfg.ssm.head_dim
    return cfg.d_model // dh, dh


def rwkv_init(key, cfg: ArchConfig, dtype):
    d = cfg.d_model
    H, dh = _rwkv_dims(cfg)
    r = cfg.ssm.decay_rank
    ks = jax.random.split(key, 10)
    p = {
        "wr": linear_init(ks[0], d, d, dtype),
        "wk": linear_init(ks[1], d, d, dtype),
        "wv": linear_init(ks[2], d, d, dtype),
        "wg": linear_init(ks[3], d, d, dtype),
        "out": linear_init(ks[4], d, d, dtype,
                           scale=1.0 / math.sqrt(d * 2 * cfg.n_layers)),
        "decay_w1": _normal(ks[5], (d, r), dtype, 1.0 / math.sqrt(d)),
        "decay_w2": _normal(ks[6], (r, d), dtype, 1.0 / math.sqrt(r)),
        "decay_bias": jnp.full((d,), -2.0, jnp.float32),
        "u": _normal(ks[7], (H, dh), jnp.float32, 0.5),
        "ln_y": rmsnorm_init(d, dtype),
    }
    for name in ("mu_r", "mu_k", "mu_v", "mu_g", "mu_w"):
        p[name] = jnp.full((d,), 0.5, dtype)
    return p


def _token_shift(x, prev=None):
    """x_{t-1} stream; prev: (B,1,d) carried across segments."""
    if prev is None:
        return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :x.shape[1]]
    return jnp.concatenate([prev, x], axis=1)[:, :x.shape[1]]


def _mix(x, xs, mu):
    return x + (xs - x) * mu.astype(x.dtype)


def rwkv_time_mix(p, x, cfg: ArchConfig, state=None):
    """Returns (y, (ssm_state, last_x)).  x: (B,S,d)."""
    B, S, d = x.shape
    H, dh = _rwkv_dims(cfg)
    prev = None if state is None else state[1]
    xs = _token_shift(x, prev)
    r = linear(p["wr"], _mix(x, xs, p["mu_r"])).reshape(B, S, H, dh)
    k = linear(p["wk"], _mix(x, xs, p["mu_k"])).reshape(B, S, H, dh)
    v = linear(p["wv"], _mix(x, xs, p["mu_v"])).reshape(B, S, H, dh)
    gate = jax.nn.silu(linear(p["wg"], _mix(x, xs, p["mu_g"])))
    if cfg.ssm_shard_constraints:
        # keep head-sharded activations head-sharded through the mixer
        r = logical_constraint(r, "batch", None, "model", None)
        k = logical_constraint(k, "batch", None, "model", None)
        v = logical_constraint(v, "batch", None, "model", None)
        gate = logical_constraint(gate, "batch", None, "model")
    # data-dependent decay (the Finch contribution)
    xw = _mix(x, xs, p["mu_w"])
    lora = jnp.tanh(xw @ p["decay_w1"].astype(x.dtype)) \
        @ p["decay_w2"].astype(x.dtype)
    log_w = -jnp.exp(p["decay_bias"] + lora.astype(jnp.float32))  # (B,S,d) <0
    log_w = log_w.reshape(B, S, H, dh)
    ssm_in = None if state is None else state[0]
    y, ssm_state = ssm_scan_chunked(r, k, v, log_w, u=p["u"],
                                    initial_state=ssm_in,
                                    chunk=min(cfg.ssm.chunk, S),
                                    subchunk=min(cfg.ssm.subchunk, S),
                                    unroll=cfg.unroll_scans,
                                    shard_constrain=cfg.ssm_shard_constraints,
                                    io_dtype=jnp.bfloat16 if cfg.ssm_bf16_io
                                    else jnp.float32)
    y = y.reshape(B, S, d)
    y = rmsnorm(p["ln_y"], y, cfg.norm_eps) * gate
    return linear(p["out"], y), (ssm_state, x[:, -1:, :])


def rwkv_time_mix_decode(p, x, cfg: ArchConfig, state):
    B, _, d = x.shape
    H, dh = _rwkv_dims(cfg)
    ssm_state, prev = state
    xs = prev
    r = linear(p["wr"], _mix(x, xs, p["mu_r"])).reshape(B, H, dh)
    k = linear(p["wk"], _mix(x, xs, p["mu_k"])).reshape(B, H, dh)
    v = linear(p["wv"], _mix(x, xs, p["mu_v"])).reshape(B, H, dh)
    gate = jax.nn.silu(linear(p["wg"], _mix(x, xs, p["mu_g"])))
    xw = _mix(x, xs, p["mu_w"])
    lora = jnp.tanh(xw @ p["decay_w1"].astype(x.dtype)) \
        @ p["decay_w2"].astype(x.dtype)
    log_w = -jnp.exp(p["decay_bias"] + lora.astype(jnp.float32))
    log_w = log_w.reshape(B, H, dh)
    y, ssm_new = ssm_decode_step(r, k, v, log_w, ssm_state, u=p["u"])
    y = y.reshape(B, 1, d)
    y = rmsnorm(p["ln_y"], y, cfg.norm_eps) * gate
    return linear(p["out"], y), (ssm_new, x)


def cmix_init(key, cfg: ArchConfig, dtype):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "wk": linear_init(ks[0], d, f, dtype),
        "wv": linear_init(ks[1], f, d, dtype,
                          scale=1.0 / math.sqrt(f * 2 * cfg.n_layers)),
        "wr": linear_init(ks[2], d, d, dtype),
        "mu_k": jnp.full((d,), 0.5, dtype),
        "mu_r": jnp.full((d,), 0.5, dtype),
    }


def cmix_apply(p, x, cfg: ArchConfig, state=None):
    prev = state
    xs = _token_shift(x, prev)
    kk = jnp.square(jax.nn.relu(linear(p["wk"], _mix(x, xs, p["mu_k"]))))
    if cfg.ssm_shard_constraints:
        # the (B,S,d_ff) hidden must stay sharded over "model": without
        # this pin XLA re-gathers 2x 3.5 GiB per layer (measured)
        kk = logical_constraint(kk, "batch", None, "model")
    rr = jax.nn.sigmoid(linear(p["wr"], _mix(x, xs, p["mu_r"])))
    return rr * linear(p["wv"], kk), x[:, -1:, :]


# ===========================================================================
# block = attn_every x (mixer + ffn)
# ===========================================================================
def _layer_kinds(cfg: ArchConfig) -> list[tuple[str, str]]:
    """[(mixer, ffn)] per layer inside one scan block."""
    out = []
    for i, mixer in enumerate(cfg.block_pattern()):
        if mixer == "rwkv":
            out.append(("rwkv", "cmix"))
        else:
            out.append((mixer, cfg.ffn_kind(i)))
    return out


def block_init(key, cfg: ArchConfig, dtype, cross_attention=False):
    layers = []
    kinds = _layer_kinds(cfg)
    keys = jax.random.split(key, len(kinds))
    for kk, (mixer, ffn) in zip(keys, kinds):
        k1, k2, k3, k4 = jax.random.split(kk, 4)
        layer = {"norm1": rmsnorm_init(cfg.d_model, dtype),
                 "norm2": rmsnorm_init(cfg.d_model, dtype)}
        if mixer == "attn":
            layer["attn"] = attention_init(k1, cfg, dtype)
        elif mixer == "mamba":
            layer["mamba"] = mamba_init(k1, cfg, dtype)
        elif mixer == "rwkv":
            layer["rwkv"] = rwkv_init(k1, cfg, dtype)
        if ffn == "dense":
            layer["mlp"] = mlp_init(k2, cfg, dtype)
        elif ffn == "moe":
            layer["moe"] = moe_init(k2, cfg, dtype)
        elif ffn == "cmix":
            layer["cmix"] = cmix_init(k2, cfg, dtype)
        if cross_attention:
            layer["norm_x"] = rmsnorm_init(cfg.d_model, dtype)
            layer["xattn"] = attention_init(k3, cfg, dtype)
        layers.append(layer)
    return {"layers": layers}


def block_apply(bp, x, cfg: ArchConfig, *, causal=True, enc_out=None,
                collect_cache=False, states=None):
    """Full-sequence pass through one block.  Returns (x, cache, aux)."""
    kinds = _layer_kinds(cfg)
    aux = jnp.float32(0.0)
    cache = {"attn_k": [], "attn_v": [], "ssm": [], "conv": [],
             "shift_t": [], "shift_c": [], "cross_k": [], "cross_v": []}
    for i, (layer, (mixer, ffn)) in enumerate(zip(bp["layers"], kinds)):
        h = rmsnorm(layer["norm1"], x, cfg.norm_eps)
        if mixer == "attn":
            out, (k, v) = attention_apply(layer["attn"], h, cfg,
                                          causal=causal)
            if collect_cache:
                cache["attn_k"].append(k)
                cache["attn_v"].append(v)
        elif mixer == "mamba":
            out, (s_ssm, s_conv) = mamba_apply(layer["mamba"], h, cfg)
            if collect_cache:
                cache["ssm"].append(s_ssm)
                cache["conv"].append(s_conv)
        else:  # rwkv
            out, (s_ssm, last) = rwkv_time_mix(layer["rwkv"], h, cfg)
            if collect_cache:
                cache["ssm"].append(s_ssm)
                cache["shift_t"].append(last)
        x = x + out
        if enc_out is not None:
            h = rmsnorm(layer["norm_x"], x, cfg.norm_eps)
            out, (ck, cv) = attention_apply(layer["xattn"], h, cfg,
                                            causal=False, x_kv=enc_out)
            if collect_cache:
                cache["cross_k"].append(ck)
                cache["cross_v"].append(cv)
            x = x + out
        h = rmsnorm(layer["norm2"], x, cfg.norm_eps)
        if ffn == "dense":
            out = mlp_apply(layer["mlp"], h, cfg)
        elif ffn == "moe":
            out, moe_aux = moe_apply(layer["moe"], h, cfg)
            aux = aux + moe_aux["moe_aux"]
        else:  # cmix
            out, last_c = cmix_apply(layer["cmix"], h, cfg)
            if collect_cache:
                cache["shift_c"].append(last_c)
        x = x + out
        x = logical_constraint(x, "batch", None, None)
    cache = {k: jnp.stack(v) for k, v in cache.items() if v}
    return x, cache, aux


def block_decode(bp, x, pos, cfg: ArchConfig, cache):
    """One-token pass.  cache holds per-layer stacked state tensors."""
    kinds = _layer_kinds(cfg)
    counters = {k: 0 for k in ("attn", "ssm", "shift_t", "shift_c", "cross")}
    new_cache = {k: [] for k in cache}
    for i, (layer, (mixer, ffn)) in enumerate(zip(bp["layers"], kinds)):
        h = rmsnorm(layer["norm1"], x, cfg.norm_eps)
        if mixer == "attn":
            j = counters["attn"]
            out, ck, cv = attention_decode(
                layer["attn"], h, cache["attn_k"][j], cache["attn_v"][j],
                pos, cfg)
            new_cache["attn_k"].append(ck)
            new_cache["attn_v"].append(cv)
            counters["attn"] += 1
        elif mixer == "mamba":
            j = counters["ssm"]
            out, (s_ssm, s_conv) = mamba_decode(
                layer["mamba"], h, cfg, (cache["ssm"][j], cache["conv"][j]))
            new_cache["ssm"].append(s_ssm)
            new_cache["conv"].append(s_conv)
            counters["ssm"] += 1
        else:  # rwkv
            j = counters["ssm"]
            out, (s_ssm, last) = rwkv_time_mix_decode(
                layer["rwkv"], h, cfg, (cache["ssm"][j], cache["shift_t"][j]))
            new_cache["ssm"].append(s_ssm)
            new_cache["shift_t"].append(last)
            counters["ssm"] += 1
        x = x + out
        if "cross_k" in cache and "xattn" in layer:
            j = counters["cross"]
            h = rmsnorm(layer["norm_x"], x, cfg.norm_eps)
            out, _, _ = attention_decode(
                layer["xattn"], h, cache["cross_k"][j], cache["cross_v"][j],
                pos, cfg, cross_kv=(cache["cross_k"][j], cache["cross_v"][j]))
            new_cache["cross_k"].append(cache["cross_k"][j])
            new_cache["cross_v"].append(cache["cross_v"][j])
            counters["cross"] += 1
            x = x + out
        h = rmsnorm(layer["norm2"], x, cfg.norm_eps)
        if ffn == "dense":
            out = mlp_apply(layer["mlp"], h, cfg)
        elif ffn == "moe":
            out, _ = moe_apply(layer["moe"], h, cfg)
        else:
            j = counters["shift_c"]
            out, last_c = cmix_apply(layer["cmix"], h, cfg,
                                     state=cache["shift_c"][j])
            new_cache["shift_c"].append(last_c)
            counters["shift_c"] += 1
        x = x + out
    new_cache = {k: jnp.stack(v) for k, v in new_cache.items() if v}
    return x, new_cache


# ===========================================================================
# full model
# ===========================================================================
def init_params(key, cfg: ArchConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    scale = 1.0 / math.sqrt(cfg.d_model)
    params = {
        "embed": {"table": _normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                   dtype, scale)},
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }
    # stacked decoder blocks (scan axis = 0); vmap keeps one block's init
    # in the program, not n_blocks copies of it
    params["blocks"] = jax.vmap(
        lambda k: block_init(k, cfg, dtype, cross_attention=cfg.is_encdec))(
        jax.random.split(ks[1], cfg.n_blocks))
    if not cfg.tie_embeddings:
        params["lm_head"] = linear_init(ks[2], cfg.d_model, cfg.vocab_size,
                                        dtype, scale=scale)
    if cfg.is_encdec:
        params["enc_blocks"] = jax.vmap(lambda k: block_init(k, cfg, dtype))(
            jax.random.split(ks[3], cfg.encdec.n_encoder_layers))
        params["enc_norm"] = rmsnorm_init(cfg.d_model, dtype)
    if cfg.vlm is not None:
        params["vision_proj"] = linear_init(ks[4], cfg.vlm.patch_dim,
                                            cfg.d_model, dtype)
    return params


def _embed(params, tokens, cfg: ArchConfig, batch=None):
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    x = x.astype(jnp.dtype(cfg.compute_dtype))
    if cfg.vlm is not None and batch is not None and "image_embeds" in batch:
        img = linear(params["vision_proj"], batch["image_embeds"]
                     .astype(x.dtype))
        n_img = img.shape[1]
        x = lax.dynamic_update_slice_in_dim(x, img, 0, axis=1)
    return logical_constraint(x, "batch", None, None)


def _scan_blocks(params, x, cfg: ArchConfig, *, causal=True, enc_out=None,
                 collect_cache=False):
    def body(carry, bp):
        x, aux = carry
        x, cache, aux_i = block_apply(bp, x, cfg, causal=causal,
                                      enc_out=enc_out,
                                      collect_cache=collect_cache)
        return (x, aux + aux_i), cache

    body_fn = body
    if cfg.remat == "block":
        body_fn = jax.checkpoint(body)
    elif cfg.remat == "dots":
        # selective: save matmul outputs, recompute elementwise — avoids
        # re-all-gathering FSDP weights in the backward recompute
        body_fn = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    (x, aux), caches = lax.scan(body_fn, (x, jnp.float32(0.0)),
                                params["blocks"],
                                unroll=cfg.n_blocks if cfg.unroll_blocks
                                else 1)
    return x, aux, caches


def _encode(params, audio_embeds, cfg: ArchConfig):
    x = audio_embeds.astype(jnp.dtype(cfg.compute_dtype))

    def body(carry, bp):
        h, _, _ = block_apply(bp, carry, cfg, causal=False)
        return h, None

    body_fn = jax.checkpoint(body) if cfg.remat == "block" else body
    x, _ = lax.scan(body_fn, x, params["enc_blocks"],
                    unroll=(cfg.encdec.n_encoder_layers
                            if cfg.unroll_blocks else 1))
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def final_hidden(params, batch, cfg: ArchConfig, collect_cache=False):
    enc_out = None
    if cfg.is_encdec:
        enc_out = _encode(params, batch["audio_embeds"], cfg)
    x = _embed(params, batch["tokens"], cfg, batch)
    x, aux, caches = _scan_blocks(params, x, cfg, causal=True,
                                  enc_out=enc_out,
                                  collect_cache=collect_cache)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, aux, caches


def loss_fn(params, batch, cfg: ArchConfig, aux_weight: float = 0.01):
    x, aux, _ = final_hidden(params, batch, cfg)
    table = (params["embed"]["table"] if cfg.tie_embeddings
             else params["lm_head"]["w"].T)
    xent = chunked_xent(table, x, batch["labels"],
                        chunk=min(cfg.logit_chunk, x.shape[1]),
                        unroll=cfg.unroll_scans)
    return xent + aux_weight * aux, {"xent": xent, "moe_aux": aux}


def logits_last(params, x_last, cfg: ArchConfig):
    """x_last: (B, 1, d) -> (B, 1, V) fp32."""
    table = (params["embed"]["table"] if cfg.tie_embeddings
             else params["lm_head"]["w"].T)
    return (x_last @ table.astype(x_last.dtype).T).astype(jnp.float32)


def prefill(params, batch, cfg: ArchConfig, pad_to: int | None = None):
    """Builds a serving cache; returns (last-token logits, cache, pos).

    ``pad_to`` sizes the attention KV cache for subsequent decode."""
    x, aux, caches = final_hidden(params, batch, cfg, collect_cache=True)
    S = batch["tokens"].shape[1]
    if pad_to is not None and "attn_k" in caches and pad_to > S:
        pad = pad_to - S
        for key in ("attn_k", "attn_v"):
            c = caches[key]
            caches[key] = jnp.pad(
                c, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    lg = logits_last(params, x[:, -1:, :], cfg)
    return lg, caches, S


def decode_step(params, cache, token, pos, cfg: ArchConfig):
    """token: (B, 1) int32; pos: scalar int32.  Returns (logits, cache)."""
    x = _embed(params, token, cfg)

    def body(x, inp):
        bp, cache_b = inp
        x, new_cache = block_decode(bp, x, pos, cfg, cache_b)
        return x, new_cache

    x, new_caches = lax.scan(body, x, (params["blocks"], cache),
                             unroll=cfg.n_blocks if cfg.unroll_blocks else 1)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return logits_last(params, x, cfg), new_caches


def make_decode_cache(cfg: ArchConfig, batch: int, max_seq: int,
                      dtype=None, enc_len: int | None = None):
    """Abstract/zero cache for serve_step lowering and serving."""
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    kinds = _layer_kinds(cfg)
    nb = cfg.n_blocks
    n_attn = sum(1 for m, _ in kinds if m == "attn")
    n_mamba = sum(1 for m, _ in kinds if m == "mamba")
    n_rwkv = sum(1 for m, _ in kinds if m == "rwkv")
    dh = cfg.head_dim
    cache = {}
    if n_attn:
        shape = (nb, n_attn, batch, max_seq, cfg.n_kv_heads, dh)
        cache["attn_k"] = jnp.zeros(shape, dtype)
        cache["attn_v"] = jnp.zeros(shape, dtype)
    if n_mamba:
        d_inner, H = _mamba_dims(cfg)
        K, hd = cfg.ssm.d_state, cfg.ssm.head_dim
        cache["ssm"] = jnp.zeros((nb, n_mamba, batch, H, K, hd), jnp.float32)
        cache["conv"] = jnp.zeros((nb, n_mamba, batch, cfg.ssm.d_conv - 1,
                                   d_inner), dtype)
    if n_rwkv:
        H, hd = _rwkv_dims(cfg)
        cache["ssm"] = jnp.zeros((nb, n_rwkv, batch, H, hd, hd), jnp.float32)
        cache["shift_t"] = jnp.zeros((nb, n_rwkv, batch, 1, cfg.d_model),
                                     dtype)
        cache["shift_c"] = jnp.zeros((nb, n_rwkv, batch, 1, cfg.d_model),
                                     dtype)
    if cfg.is_encdec:
        el = enc_len or cfg.encdec.n_audio_ctx
        shape = (nb, len(kinds), batch, el, cfg.n_kv_heads, dh)
        cache["cross_k"] = jnp.zeros(shape, dtype)
        cache["cross_v"] = jnp.zeros(shape, dtype)
    return cache
