"""Top-k routed mixture-of-experts with sort-based capacity dispatch.

Covers both assigned MoE archs:
  * granite-moe-1b-a400m — 32 experts, top-8
  * arctic-480b          — 128 experts, top-2 **+ dense residual FFN**

Dispatch: tokens are argsorted by expert id, ranked within expert, and
scattered into an (E, C, D) buffer (drop-on-overflow, capacity
C = ceil(T·k/E·cf)).  Expert matmuls are grouped einsums with E sharded over
the `model` (tp) axis — expert parallelism; GSPMD materialises the
token⇄expert regrouping as collectives, which the roofline attributes and
§Perf optimises.

Aux load-balance loss (Switch-style E·Σ f_e·p̄_e) is returned for training.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import MoEConfig
from repro.sharding.specs import constrain


def moe_ffn(
    x: jax.Array,  # (T, D) flattened tokens
    router_w: jax.Array,   # (D, E)
    e_wg: jax.Array,       # (E, D, Fe)
    e_wu: jax.Array,       # (E, D, Fe)
    e_wd: jax.Array,       # (E, Fe, D)
    cfg: MoEConfig,
) -> tuple[jax.Array, jax.Array]:
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = int(-(-t * k // e) * cfg.capacity_factor)
    cap = max(cap, 1)

    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, topk_idx = jax.lax.top_k(probs, k)                    # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9
    )

    # ---- dispatch: sort (token,k) pairs by expert, rank within expert ----
    flat_e = topk_idx.reshape(-1)                        # (T·k,)
    flat_t = jnp.repeat(jnp.arange(t), k)                # (T·k,)
    sort_idx = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sort_idx]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    rank_sorted = jnp.arange(t * k) - starts[sorted_e]
    rank = jnp.zeros((t * k,), jnp.int32).at[sort_idx].set(
        rank_sorted.astype(jnp.int32)
    )

    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[flat_e, rank].set(x[flat_t], mode="drop")
    buf = constrain(buf, "experts", None, None)

    # ---- expert compute (grouped einsum, E over tp) ----
    h_g = jnp.einsum("ecd,edf->ecf", buf, e_wg.astype(x.dtype))
    h_u = jnp.einsum("ecd,edf->ecf", buf, e_wu.astype(x.dtype))
    h = jax.nn.silu(h_g) * h_u
    y_e = jnp.einsum("ecf,efd->ecd", h, e_wd.astype(x.dtype))
    y_e = constrain(y_e, "experts", None, None)

    # ---- combine: gather back, weight, sum over k ----
    kept = rank < cap
    gathered = y_e[flat_e, jnp.minimum(rank, cap - 1)]   # (T·k, D)
    gathered = jnp.where(kept[:, None], gathered, 0)
    w = gate_vals.reshape(-1).astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype).at[flat_t].add(gathered * w[:, None])

    # ---- Switch aux loss: E · Σ_e f_e · p̄_e ----
    f_e = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0) / (t * k)
    p_e = probs.mean(axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return out, aux


# ---------------------------------------------------------------------------
# Explicit-SPMD MoE (shard_map): production path under a mesh
# ---------------------------------------------------------------------------
#
# GSPMD auto-propagation replicates the sort/scatter dispatch (measured:
# 95 GiB/device temp for granite train_4k).  The manual mapping is simple
# and optimal-by-construction here:
#   * tokens are sharded over fsdp, replicated over tp;
#   * experts are sharded over tp — device (d, m) dispatches *its local
#     tokens* to *its local experts* only, computes, and the combine is one
#     psum over tp (exactly a row-parallel matmul's collective);
#   * no all-to-all, no replication; per-device buffer is
#     (E/tp, C_local, D) with C_local = ceil(T_local·k/E · cf).
# Capacity drops become per-(expert × data-shard) — noted in DESIGN.md.

def _local_dispatch_compute(x_loc, router_w, e_wg, e_wu, e_wd, cfg: MoEConfig,
                            m_idx, e_loc: int):
    t_loc, d = x_loc.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = max(int(-(-t_loc * k // e) * cfg.capacity_factor), 1)

    logits = x_loc.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, topk_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    flat_e = topk_idx.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t_loc), k)
    local_e = flat_e - m_idx * e_loc
    mine = (local_e >= 0) & (local_e < e_loc)
    key = jnp.where(mine, local_e, e_loc)          # sentinel sorts last
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    starts = jnp.searchsorted(sorted_key, jnp.arange(e_loc), side="left")
    rank_sorted = jnp.arange(t_loc * k) - starts[
        jnp.minimum(sorted_key, e_loc - 1)
    ]
    rank = jnp.zeros((t_loc * k,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32)
    )

    buf = jnp.zeros((e_loc, cap, d), x_loc.dtype)
    write_e = jnp.where(mine, local_e, e_loc)      # OOB → dropped
    buf = buf.at[write_e, rank].set(x_loc[flat_t], mode="drop")

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, e_wg.astype(x_loc.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, e_wu.astype(x_loc.dtype))
    y_e = jnp.einsum("ecf,efd->ecd", h, e_wd.astype(x_loc.dtype))

    kept = mine & (rank < cap)
    gathered = y_e[jnp.minimum(write_e, e_loc - 1), jnp.minimum(rank, cap - 1)]
    gathered = jnp.where(kept[:, None], gathered, 0)
    w = gate_vals.reshape(-1).astype(x_loc.dtype)
    y = jnp.zeros((t_loc, d), x_loc.dtype).at[flat_t].add(
        gathered * w[:, None]
    )

    f_e = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0) / (t_loc * k)
    aux = e * jnp.sum(f_e * probs.mean(axis=0))
    return y, aux


def moe_ffn_sharded(
    x: jax.Array,          # (T, D) tokens
    router_w, e_wg, e_wu, e_wd,
    cfg: MoEConfig,
    mesh,
    fsdp: tuple[str, ...],
    tp: str,
):
    """shard_map MoE: tokens×fsdp, experts×tp, combine = psum(tp)."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    e_loc = cfg.n_experts // mesh.shape[tp]

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(fsdp, None), P(None, None), P(tp, None, None),
                  P(tp, None, None), P(tp, None, None)),
        out_specs=(P(fsdp, None), P()),
        check_vma=False,
    )
    def run(x_loc, router, wg, wu, wd):
        m_idx = jax.lax.axis_index(tp)
        y, aux = _local_dispatch_compute(
            x_loc, router, wg, wu, wd, cfg, m_idx, e_loc
        )
        y = jax.lax.psum(y, tp)
        aux = jax.lax.pmean(aux, fsdp) if fsdp else aux
        return y, aux

    return run(x, router_w, e_wg, e_wu, e_wd)
