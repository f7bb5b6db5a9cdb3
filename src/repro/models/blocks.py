"""Transformer / RWKV / hybrid block bodies + parameter initialisation.

Parameters are dicts of arrays **stacked over layers** (leading L dim) so the
forward pass can `lax.scan` over layers (small HLO, fast 512-way SPMD
compiles) with `jax.checkpoint` remat.  Hybrid archs with per-layer
exceptions (hymba's global-attention layers) unroll a python loop instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import ssm as ssm_lib
from repro.models.attention import decode_attention, gqa_attention
from repro.models.common import ModelConfig
from repro.models.layers import dense_init, rms_norm
from repro.models.rope import apply_mrope, apply_rope
from repro.models.moe import moe_ffn, moe_ffn_sharded
from repro.sharding.specs import constrain


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_block_params(key: jax.Array, cfg: ModelConfig) -> dict:
    """Stacked (L, …) parameter dict for all layers."""
    l, d, f = cfg.n_layers, cfg.d_model, cfg.d_ff
    dt = cfg.jnp_dtype
    keys = iter(jax.random.split(key, 64))
    p: dict = {}

    def mat(*shape, scale_axis=-2):
        return dense_init(next(keys), shape, in_axis=scale_axis, dtype=dt)

    p["ln1"] = jnp.ones((l, d), dt)
    p["ln2"] = jnp.ones((l, d), dt)

    if cfg.block_kind in ("attn", "hybrid"):
        p["wq"] = mat(l, d, cfg.q_dim)
        p["wk"] = mat(l, d, cfg.kv_dim)
        p["wv"] = mat(l, d, cfg.kv_dim)
        p["wo"] = mat(l, cfg.q_dim, d)

    if cfg.block_kind == "rwkv":
        r = cfg.ssm.lora_rank
        h, hd = d // cfg.ssm.head_dim, cfg.ssm.head_dim
        p["mu"] = jnp.full((l, 5, d), 0.5, dt)
        for nm in ("wr", "wk_t", "wv_t", "wg_t", "wo_t"):
            p[nm] = mat(l, d, d)
        p["w0"] = jnp.full((l, d), -1.0, jnp.float32)
        p["wlA"] = mat(l, d, r)
        p["wlB"] = (jax.random.normal(next(keys), (l, r, d)) * 0.01).astype(jnp.float32)
        p["u"] = jnp.zeros((l, h, hd), jnp.float32)
        p["ln_x"] = jnp.ones((l, d), jnp.float32)
        p["mu_ck"] = jnp.full((l, d), 0.5, dt)
        p["mu_cr"] = jnp.full((l, d), 0.5, dt)
        p["c_wk"] = mat(l, d, f)
        p["c_wv"] = mat(l, f, d)
        p["c_wr"] = mat(l, d, d)
        return p

    if cfg.block_kind == "hybrid" and cfg.ssm is not None:
        di = cfg.ssm.expand * d
        n = cfg.ssm.state_dim
        dtr = cfg.ssm.dt_rank or -(-d // 16)
        cw = cfg.ssm.conv_dim
        p["m_in"] = mat(l, d, 2 * di)
        p["m_conv"] = (jax.random.normal(next(keys), (l, di, cw)) * 0.2).astype(dt)
        p["m_Alog"] = jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (l, di, n)
        ).copy()
        p["m_x"] = mat(l, di, dtr + 2 * n)
        p["m_dtw"] = mat(l, dtr, di)
        p["m_dtb"] = jnp.full((l, di), -4.6, jnp.float32)  # softplus ≈ 0.01
        p["m_D"] = jnp.ones((l, di), dt)
        p["m_out"] = mat(l, di, d)

    if cfg.moe is not None:
        e, fe = cfg.moe.n_experts, cfg.moe.d_ff_expert
        p["router"] = (jax.random.normal(next(keys), (l, d, e)) * 0.02).astype(
            jnp.float32
        )
        p["e_wg"] = mat(l, e, d, fe)
        p["e_wu"] = mat(l, e, d, fe)
        p["e_wd"] = mat(l, e, fe, d)
    if cfg.moe is None or cfg.moe.dense_residual:
        if cfg.act == "swiglu":
            p["wg_f"] = mat(l, d, f)
        p["wu_f"] = mat(l, d, f)
        p["wd_f"] = mat(l, f, d)
    return p


# ---------------------------------------------------------------------------
# FFN / MoE sublayer
# ---------------------------------------------------------------------------

def _dense_ffn(h, lp, cfg: ModelConfig):
    if cfg.act == "swiglu":
        g = h @ lp["wg_f"]
        u = h @ lp["wu_f"]
        z = jax.nn.silu(g) * u
    else:
        z = jax.nn.gelu(h @ lp["wu_f"])
    return z @ lp["wd_f"]


def ffn_sublayer(x, lp, cfg: ModelConfig):
    """Pre-norm FFN/MoE with residual. Returns (x, aux_loss)."""
    from repro.sharding.specs import current_mesh

    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    out = 0.0
    if cfg.moe is not None:
        b, s, d = h.shape
        flat = h.reshape(b * s, d)
        ctx = current_mesh()
        use_sharded = False
        if ctx is not None:
            mesh, axes = ctx
            fsdp_size = 1
            for a in axes.fsdp:
                fsdp_size *= mesh.shape[a]
            use_sharded = (
                (b * s) % fsdp_size == 0
                and cfg.moe.n_experts % mesh.shape[axes.tp] == 0
            )
        if use_sharded:
            moe_out, aux = moe_ffn_sharded(
                flat, lp["router"], lp["e_wg"], lp["e_wu"], lp["e_wd"],
                cfg.moe, mesh, axes.fsdp, axes.tp,
            )
        else:
            flat = constrain(flat, "batch", None)
            moe_out, aux = moe_ffn(
                flat, lp["router"], lp["e_wg"], lp["e_wu"], lp["e_wd"],
                cfg.moe,
            )
        out = out + moe_out.reshape(b, s, d)
        if cfg.moe.dense_residual:
            out = out + _dense_ffn(h, lp, cfg)
    else:
        out = _dense_ffn(h, lp, cfg)
    return x + out, aux


# ---------------------------------------------------------------------------
# Attention sublayer (sequence path)
# ---------------------------------------------------------------------------

def _apply_pos(q, k, positions, cfg: ModelConfig):
    if cfg.rope_kind == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope_kind == "mrope":
        q = apply_mrope(q, positions, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.rope_theta)
    return q, k


def attn_sublayer(x, lp, cfg: ModelConfig, positions, *, window, q_offset=0,
                  collect_kv=False):
    """Pre-norm GQA attention with residual.  positions: (B,S) or (B,S,3)."""
    b, s, d = x.shape
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    q = (h @ lp["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = (h @ lp["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ lp["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q, k = _apply_pos(q, k, positions, cfg)
    q = constrain(q, "batch", None, "heads", None)
    o = gqa_attention(q, k, v, causal=True, window=window, q_offset=q_offset)
    o = o.reshape(b, s, cfg.q_dim) @ lp["wo"]
    x = x + o
    if collect_kv:
        from repro.sharding.specs import constrain_kv_collect

        k, v = constrain_kv_collect(k, v)
        return x, (k, v)
    return x, None


# ---------------------------------------------------------------------------
# Full block bodies (sequence path)
# ---------------------------------------------------------------------------

def attn_block(x, lp, cfg: ModelConfig, positions, *, window,
               collect_kv=False):
    x, kv = attn_sublayer(
        x, lp, cfg, positions, window=window, collect_kv=collect_kv
    )
    x, aux = ffn_sublayer(x, lp, cfg)
    return x, kv, aux


def rwkv_block(x, lp, cfg: ModelConfig, state: ssm_lib.RWKVState,
               chunk: int = 16):
    h_heads = cfg.d_model // cfg.ssm.head_dim
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    mix, state = ssm_lib.rwkv6_time_mix(
        h, state, lp, h_heads, cfg.ssm.head_dim, chunk=chunk
    )
    x = x + mix
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    cm, state = ssm_lib.rwkv6_channel_mix(h2, state, lp)
    return x + cm, state


def hybrid_block(x, lp, cfg: ModelConfig, positions, mamba_state, *,
                 window, collect_kv=False):
    """Hymba: attention and mamba heads run in parallel on the same
    pre-norm input; outputs are summed into the residual (the paper's
    per-branch normalisation is folded into the output projections)."""
    b, s, d = x.shape
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    q = (h @ lp["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = (h @ lp["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ lp["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q, k = _apply_pos(q, k, positions, cfg)
    attn_o = gqa_attention(q, k, v, causal=True, window=window)
    attn_o = attn_o.reshape(b, s, cfg.q_dim) @ lp["wo"]
    mamba_o, mamba_state = ssm_lib.mamba_mix(h, mamba_state, lp,
                                             cfg.ssm.state_dim)
    x = x + attn_o + mamba_o
    x, aux = ffn_sublayer(x, lp, cfg)
    if collect_kv:
        from repro.sharding.specs import constrain_kv_collect

        k, v = constrain_kv_collect(k, v)
        return x, (k, v), mamba_state, aux
    return x, None, mamba_state, aux


# ---------------------------------------------------------------------------
# Decode (single-token) attention sublayer against a cache
# ---------------------------------------------------------------------------

def _cache_write(cache, new_row, write_pos):
    """Write one token row into a (B, T, Hkv, hd) cache.

    Under a mesh with the seq dim sharded over tp, a plain
    dynamic_update_slice at a traced position forces GSPMD into
    "involuntary full rematerialization" copies of the whole cache per
    layer (measured: 25 GiB/device temp on llama3-405b decode_32k).  The
    sharded path runs the write inside shard_map: each shard clamps the
    position into its local slice and either writes the new row or
    rewrites the existing row (a no-op) — fully local and aliasable.
    """
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from repro.sharding.specs import current_mesh

    ctx = current_mesh()
    t = cache.shape[1]
    if ctx is not None:
        mesh, axes = ctx
        tp_n = mesh.shape[axes.tp]
        if t % tp_n == 0 and cache.shape[0] % _fsdp_size(mesh, axes) == 0:
            spec_c = P(axes.fsdp, axes.tp, None, None)
            spec_r = P(axes.fsdp, None, None, None)

            @partial(
                jax.shard_map, mesh=mesh,
                in_specs=(spec_c, spec_r, P()), out_specs=spec_c,
                check_vma=False,
            )
            def upd(c_loc, r_loc, p):
                t_loc = c_loc.shape[1]
                m = jax.lax.axis_index(axes.tp).astype(p.dtype)
                slot = p - m * t_loc
                ok = (slot >= 0) & (slot < t_loc)
                slot_c = jnp.clip(slot, 0, t_loc - 1)
                # literal 0 indices weakly type to int64 under x64; keep
                # every index in the traced position's dtype
                zero = jnp.zeros((), slot_c.dtype)
                old = jax.lax.dynamic_slice(
                    c_loc, (zero, slot_c, zero, zero), r_loc.shape
                )
                val = jnp.where(ok, r_loc, old)
                return jax.lax.dynamic_update_slice(
                    c_loc, val, (zero, slot_c, zero, zero)
                )

            return upd(cache, new_row, write_pos)
    write_pos = jnp.asarray(write_pos)
    zero = jnp.zeros((), write_pos.dtype)
    return jax.lax.dynamic_update_slice(
        cache, new_row, (zero, write_pos, zero, zero)
    )


def _fsdp_size(mesh, axes) -> int:
    n = 1
    for a in axes.fsdp:
        n *= mesh.shape[a]
    return n


def attn_decode_sublayer(x, lp, cfg: ModelConfig, k_cache, v_cache, pos,
                         positions, *, window=None, ring=False,
                         slot=None):
    """x (B,1,D); k_cache/v_cache (B,T,Hkv,hd). Returns x, new k/v rows."""
    b = x.shape[0]
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    q = (h @ lp["wq"]).reshape(b, 1, cfg.n_heads, cfg.head_dim)
    k = (h @ lp["wk"]).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ lp["wv"]).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    q, k = _apply_pos(q, k, positions, cfg)
    write = pos if slot is None else slot
    k_cache = _cache_write(k_cache, k, write)
    v_cache = _cache_write(v_cache, v, write)
    o = decode_attention(q, k_cache, v_cache, pos, window=window, ring=ring)
    x = x + o.reshape(b, 1, cfg.q_dim) @ lp["wo"]
    return x, k_cache, v_cache
