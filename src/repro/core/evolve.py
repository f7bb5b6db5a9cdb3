"""The 1+λ evolutionary loop with neutral drift (paper §3).

Selection uses ``>=`` (a child with *equal* training fitness replaces the
parent) — the neutral-drift random walk over equivalent solutions that lets
the search escape local optima (paper §3, Kimura's neutral theory).

Best-solution tracking and termination follow §3.3–3.4:
  * training fitness selects the next parent;
  * validation fitness picks the best-discovered solution;
  * terminate when validation fitness has not improved by ≥ γ within κ
    generations, or after G generations.

Hyper-parameter defaults are the paper's: λ=4, p=1/n, γ=0.01 (§3.5); the
evaluation settings n=300 gates, κ=300, G=8000 (§5.4) live in configs.

Fitness evaluation is *batched over the population* (λ children evaluated in
one pass) so the same code path drives the pure-jnp oracle, the Pallas
kernel, and the shard_map'd distributed islands (repro.core.islands).
"""
from __future__ import annotations

import dataclasses
import functools
import sys
import types
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import runtime
from repro.core import fitness as F
from repro.core import genome as genome_module
from repro.core import mutate as mutate_module
from repro.core.encoding import PackedDataset
from repro.core.genome import CircuitSpec, Genome, init_genome, opcodes
from repro.core.mutate import mutate_children
from repro.kernels import circuit_eval
from repro.kernels import ref as ref_kernels
from repro.observability.trace import NULL_TRACER
from repro.runtime import backends as runtime_backends

# Batched eval: stacked genomes (leading λ axis) → (train_fits, val_fits).
BatchEvalFn = Callable[[Genome], tuple[jax.Array, jax.Array]]


@dataclasses.dataclass(frozen=True)
class EvolveConfig:
    lam: int = 4
    p: float | None = None   # mutation rate; None → 1/n (paper §3.5)
    gamma: float = 0.01
    kappa: int = 300
    max_gens: int = 8000
    # execution backend for fitness eval (name or repro.runtime.EvalBackend)
    backend: "str | runtime.EvalBackend" = "ref"

    def rate(self, spec: CircuitSpec) -> float:
        return self.p if self.p is not None else 1.0 / spec.n_nodes


class EvolveState(NamedTuple):
    key: jax.Array
    parent: Genome
    parent_fit: jax.Array   # f32 training fitness of S
    best: Genome            # best-discovered solution (by validation fitness)
    best_val: jax.Array     # f32
    best_train: jax.Array   # f32 training fitness of `best` (reporting)
    ref_val: jax.Array      # γ-improvement reference (§3.4)
    since: jax.Array        # generations since the last ≥γ val improvement
    gen: jax.Array          # generation counter


def make_eval_fn(
    spec: CircuitSpec,
    data: PackedDataset,
    mask_train: jax.Array,
    mask_val: jax.Array,
    backend: "str | runtime.EvalBackend" = "ref",
) -> BatchEvalFn:
    """Single forward pass over *all* packed rows; train and val fitness are
    two masked confusion reductions over the same circuit outputs."""
    be = runtime.resolve_backend(backend)

    def eval_fn(genomes: Genome):
        out = be.eval_population(
            opcodes(genomes, spec), genomes.edge_src, genomes.out_src,
            data.x_words,
        )  # (λ, O, W)
        ft = jax.vmap(lambda o: F.balanced_accuracy(o, data, mask_train))(out)
        fv = jax.vmap(lambda o: F.balanced_accuracy(o, data, mask_val))(out)
        return ft, fv

    return eval_fn


def _stack1(genome: Genome) -> Genome:
    return jax.tree.map(lambda x: x[None], genome)


def _select(key, fits: jax.Array) -> jax.Array:
    """argmax with uniform tie-breaking (paper §3: ties at random)."""
    m = fits.max()
    u = jax.random.uniform(key, fits.shape)
    return jnp.argmax(jnp.where(fits == m, u, -1.0))


def init_state(
    key: jax.Array,
    spec: CircuitSpec,
    eval_fn: BatchEvalFn,
    seed_genome: "Genome | None" = None,
) -> EvolveState:
    """Initial 1+λ state.  ``seed_genome`` (when given) becomes the first
    parent instead of a random genome — the warm-start used by online
    refits that continue evolving a circuit already serving traffic."""
    k_init, key = jax.random.split(key)
    parent = init_genome(k_init, spec) if seed_genome is None else seed_genome
    ft, fv = eval_fn(_stack1(parent))
    zero = jnp.zeros((), jnp.int32)
    return EvolveState(
        key=key, parent=parent, parent_fit=ft[0],
        best=parent, best_val=fv[0], best_train=ft[0],
        ref_val=fv[0], since=zero, gen=zero,
    )


def generation_step(
    state: EvolveState, spec: CircuitSpec, cfg: EvolveConfig, eval_fn: BatchEvalFn
) -> EvolveState:
    key, k_mut, k_sel = jax.random.split(state.key, 3)
    children = mutate_children(k_mut, state.parent, spec, cfg.rate(spec), cfg.lam)
    ft, fv = eval_fn(children)  # (λ,), (λ,)

    # --- parent replacement: any child with f_i >= f_S; highest wins ---
    sel = _select(k_sel, ft)
    accept = ft[sel] >= state.parent_fit
    parent = jax.tree.map(
        lambda c, p: jnp.where(accept, c[sel], p), children, state.parent
    )
    parent_fit = jnp.where(accept, ft[sel], state.parent_fit)

    # --- best-discovered solution by validation fitness ---
    bidx = jnp.argmax(fv)
    improved = fv[bidx] > state.best_val
    best = jax.tree.map(
        lambda c, b: jnp.where(improved, c[bidx], b), children, state.best
    )
    best_val = jnp.maximum(state.best_val, fv[bidx])
    best_train = jnp.where(improved, ft[bidx], state.best_train)

    # --- γ/κ termination bookkeeping ---
    big_improve = best_val >= state.ref_val + cfg.gamma
    ref_val = jnp.where(big_improve, best_val, state.ref_val)
    since = jnp.where(big_improve, 0, state.since + 1)

    return EvolveState(
        key=key, parent=parent, parent_fit=parent_fit,
        best=best, best_val=best_val, best_train=best_train,
        ref_val=ref_val, since=since, gen=state.gen + 1,
    )


def not_terminated(state: EvolveState, cfg: EvolveConfig) -> jax.Array:
    return (state.gen < cfg.max_gens) & (state.since < cfg.kappa)


def _run_loop(
    state: EvolveState, spec: CircuitSpec, cfg: EvolveConfig,
    eval_fn: BatchEvalFn,
) -> EvolveState:
    def body(s):
        NULL_TRACER.count("evolve.loop_traces")
        return generation_step(s, spec, cfg, eval_fn)

    return jax.lax.while_loop(lambda s: not_terminated(s, cfg), body, state)


def evolve(
    key: jax.Array, spec: CircuitSpec, cfg: EvolveConfig, eval_fn: BatchEvalFn,
    seed_genome: "Genome | None" = None,
) -> EvolveState:
    """Run to termination (lax.while_loop — early exit, no history).

    Uncached: ``eval_fn`` closes over the caller's data, so every call
    traces, lowers and loads the loop anew (`evolve_packed` runs the same
    search through cached programs).  Spans ``evolve.init`` (the eager
    initial state) and ``evolve.loop`` (tracing, lowering, compile or
    cache load, and enqueue of the loop), and the count
    ``evolve.loop_traces`` (once per trace of the body), reach a JAX
    profiler capture (repro.observability.trace)."""
    with NULL_TRACER.span("evolve.init"):
        state = init_state(key, spec, eval_fn, seed_genome=seed_genome)
    with NULL_TRACER.span("evolve.loop"):
        return _run_loop(state, spec, cfg, eval_fn)


def evolve_with_history(
    key: jax.Array, spec: CircuitSpec, cfg: EvolveConfig, eval_fn: BatchEvalFn
):
    """Fixed-length scan variant recording per-generation curves (used by the
    Fig. 8 benchmarks).  Terminated states pass through unchanged."""
    state = init_state(key, spec, eval_fn)

    def body(s, _):
        live = not_terminated(s, cfg)
        s2 = generation_step(s, spec, cfg, eval_fn)
        s = jax.tree.map(lambda a, b: jnp.where(live, a, b), s2, s)
        return s, (s.parent_fit, s.best_val, live)

    final, hist = jax.lax.scan(body, state, None, length=cfg.max_gens)
    return final, hist


# Modules whose functions the search programs reach through module
# attributes while they are traced.
_TRACED_MODULES = (
    sys.modules[__name__], F, genome_module, mutate_module,
    runtime_backends, circuit_eval, ref_kernels,
)


def _traced_functions() -> tuple:
    """The functions of `_TRACED_MODULES` as their attributes hold them
    now: part of the search programs' cache key, so that a function
    replaced since a program was traced (a patch, a reload) keys a new
    trace instead of reusing a program built from the old one."""
    return tuple(v for m in _TRACED_MODULES for v in vars(m).values()
                 if isinstance(getattr(v, "__wrapped__", v), types.FunctionType))


# The cached search programs.  Static: spec, config, resolved backend and
# `_traced_functions()`; the data, masks, key and state are arguments, so
# one program serves every search of its shapes in the process.

@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _init_program(spec, cfg, backend, functions, key, data, mask_train,
                  mask_val, seed_genome):
    del functions  # cache key only
    eval_fn = make_eval_fn(spec, data, mask_train, mask_val, backend)
    return init_state(key, spec, eval_fn, seed_genome=seed_genome)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _loop_program(spec, cfg, backend, functions, state, data, mask_train,
                  mask_val):
    del functions  # cache key only
    eval_fn = make_eval_fn(spec, data, mask_train, mask_val, backend)
    return _run_loop(state, spec, cfg, eval_fn)


def evolve_packed(
    key: jax.Array,
    spec: CircuitSpec,
    cfg: EvolveConfig,
    data: PackedDataset,
    mask_train: jax.Array,
    mask_val: jax.Array,
    seed_genome: "Genome | None" = None,
) -> EvolveState:
    """`evolve` directly on a PackedDataset, through two cached jitted
    programs: the initial state, and the loop.  Each is traced and
    compiled once per distinct key — array shapes and dtypes, ``spec``,
    ``cfg``, the resolved backend, whether ``seed_genome`` is given, and
    the functions of the modules it traces — and reused by every later
    search in the process; a new shape, spec, config or backend (or a
    replaced function) traces again.  Same random stream, arithmetic and
    termination as `evolve`.  ``seed_genome`` warm-starts the search
    from an existing circuit (online refit).

    Spans ``evolve.init`` and ``evolve.loop`` time the two programs'
    dispatch (their trace and compile too, where the key is new); the
    count ``evolve.loop_traces`` counts real traces of the loop body."""
    static = (spec, cfg, runtime.resolve_backend(cfg.backend),
              _traced_functions())
    with NULL_TRACER.span("evolve.init"):
        state = _init_program(*static, key, data, mask_train, mask_val,
                              seed_genome)
    with NULL_TRACER.span("evolve.loop"):
        return _loop_program(*static, state, data, mask_train, mask_val)
