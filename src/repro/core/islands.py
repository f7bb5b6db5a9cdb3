"""Distributed island-model evolution via shard_map (DESIGN.md §4).

Production mapping (the multi-pod scale-out of the paper's technique):

  * ``island`` mesh axis (``model``, 16-way) — independent 1+λ parents with
    periodic ring migration of each island's best-discovered solution;
  * ``data`` axes (``data`` ×16 and, multi-pod, ``pod`` ×2) — dataset rows
    (packed words) are sharded; per-class confusion counts are ``psum``ed, so
    fitness is *exactly* the single-device value (no approximation).

Engineering notes:
  * All islands iterate in lockstep; termination is collective (loop while
    any island is alive), finished islands freeze their state but keep
    participating in collectives — this avoids divergent collective schedules
    inside ``lax.while_loop``.
  * Migration is an unconditional ring ``ppermute`` each generation whose
    *acceptance* is gated on ``t % migrate_every == 0`` — collectives under
    ``lax.cond`` with a replicated predicate are a known SPMD footgun; a few
    hundred bytes of genome per step are free at ICI bandwidth.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import runtime
from repro.core import fitness as F
from repro.core.encoding import PackedDataset
from repro.core.evolve import (
    EvolveConfig,
    EvolveState,
    generation_step,
    init_state,
    not_terminated,
)
from repro.core.genome import CircuitSpec, Genome, opcodes


@dataclasses.dataclass(frozen=True)
class IslandConfig:
    migrate_every: int = 32
    island_axis: str = "model"
    data_axes: tuple[str, ...] = ("data",)


def _make_psum_eval_fn(
    spec: CircuitSpec,
    data: PackedDataset,
    mask_train: jax.Array,
    mask_val: jax.Array,
    data_axes: tuple[str, ...],
    backend: "str | runtime.EvalBackend" = "ref",
):
    """Batched eval over a *local word shard*; confusion counts are psum'ed
    over the data axes, making fitness exact under row sharding."""
    be = runtime.resolve_backend(backend)

    def eval_fn(genomes: Genome):
        out = be.eval_population(
            opcodes(genomes, spec), genomes.edge_src, genomes.out_src,
            data.x_words,
        )

        def counts(o, m):
            c, n = jax.vmap(lambda ow: F.confusion_counts(ow, data, m))(o)
            if data_axes:
                c = jax.lax.psum(c, data_axes)
                n = jax.lax.psum(n, data_axes)
            return c, n

        ct, nt = counts(out, mask_train)
        cv, nv = counts(out, mask_val)
        ft = jax.vmap(F.balanced_accuracy_from_counts)(ct, nt)
        fv = jax.vmap(F.balanced_accuracy_from_counts)(cv, nv)
        return ft, fv

    return eval_fn


def _ring_perm(k: int):
    return [(i, (i + 1) % k) for i in range(k)]


def evolve_islands(
    keys: jax.Array,          # PRNG keys, shape (n_islands,)
    spec: CircuitSpec,
    cfg: EvolveConfig,
    icfg: IslandConfig,
    data: PackedDataset,
    mask_train: jax.Array,
    mask_val: jax.Array,
    mesh: Mesh,
    backend: "str | runtime.EvalBackend" = "ref",
):
    """Run island evolution on `mesh`. Returns per-island final EvolveStates
    stacked on a leading island axis (host then argmaxes best_val)."""
    n_islands = mesh.shape[icfg.island_axis]
    assert keys.shape[0] == n_islands, (keys.shape, n_islands)
    # resolve once at the boundary; the shard_map'd body closes over it
    be = runtime.resolve_backend(backend)

    w_axes = P(None, icfg.data_axes)   # (rows, W) arrays: shard word axis
    v_axes = P(icfg.data_axes)         # (W,) arrays

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            P(icfg.island_axis),        # keys
            w_axes, w_axes, w_axes,     # x_words, y_words, class_words
            v_axes, v_axes, v_axes,     # mask_words, mask_train, mask_val
        ),
        out_specs=P(icfg.island_axis),
        check_vma=False,
    )
    def run(keys, x_w, y_w, c_w, m_w, m_tr, m_va):
        local = PackedDataset(x_w, y_w, c_w, m_w)
        eval_fn = _make_psum_eval_fn(
            spec, local, m_tr, m_va, icfg.data_axes, be
        )
        state = init_state(keys[0], spec, eval_fn)
        t0 = jnp.zeros((), jnp.int32)

        def cond(carry):
            t, s = carry
            live = not_terminated(s, cfg).astype(jnp.int32)
            return jax.lax.psum(live, icfg.island_axis) > 0

        def body(carry):
            t, s = carry
            live = not_terminated(s, cfg)
            s2 = generation_step(s, spec, cfg, eval_fn)
            s2 = jax.tree.map(lambda a, b: jnp.where(live, a, b), s2, s)

            # --- ring migration (unconditional collective, gated accept) ---
            perm = _ring_perm(n_islands)
            inc_best, inc_train = jax.lax.ppermute(
                (s2.best, s2.best_train), icfg.island_axis, perm
            )
            do_mig = (t % icfg.migrate_every == icfg.migrate_every - 1) & live
            accept = do_mig & (inc_train >= s2.parent_fit)
            parent = jax.tree.map(
                lambda i, p: jnp.where(accept, i, p), inc_best, s2.parent
            )
            s2 = s2._replace(
                parent=parent,
                parent_fit=jnp.where(accept, inc_train, s2.parent_fit),
            )
            return (t + 1, s2)

        _, final = jax.lax.while_loop(cond, body, (t0, state))
        # stack the local island's scalars/genome on a size-1 leading axis
        return jax.tree.map(lambda x: x[None], final)

    return run(keys, data.x_words, data.y_words, data.class_words,
               data.mask_words, mask_train, mask_val)


def best_island(states: EvolveState) -> EvolveState:
    """Host-side: pick the island with the best validation fitness."""
    i = int(jnp.argmax(states.best_val))
    return jax.tree.map(lambda x: x[i], states)


def pad_words_for(mesh: Mesh, data_axes: Sequence[str]) -> int:
    """Word-axis padding multiple so every data shard is equal-sized."""
    n = 1
    for a in data_axes:
        n *= mesh.shape[a]
    return n
