"""Search cells: `AutoTinyClassifier(backend="pallas").fit` at the
paper's §5.4 settings, one fit after another, on a Table-1 shape.

Every fit of every run does the same search: the same table and the same
fit seed, so the same generations.  ``--seed`` permutes the table's rows,
and only within the groups of rows that share their place in every
encoding's train/validation split (the program's documented rule: rows
drawn with ``RandomState(fit_seed + encoding index).rand(rows) <
val_fraction`` are validation rows).  A permutation inside those groups
leaves every split's class counts, so every fitness and the whole search,
unchanged, while the bits the chip packs and reads differ.

Set-up compiles the search program of each distinct input width once,
running it on the real shapes with empty masks (the loop then stops after
``kappa`` generations).  The window runs fits until ``--seconds`` have
passed and ends at that fit's end.  After the window one more fit runs,
untimed, on a table and a fit seed drawn from ``--seed``, so that every
seed checks a search of its own.  Then every fit is checked against the
numpy reference: its encoder's edges, the fitness it reports for the
circuit it chose (train and validation), that it chose the best encoding,
that the circuit is well formed; and, over the run, that the share of
encoding searches that made no gamma gain stays below its limit.
"""
from __future__ import annotations

import gc
import os
import shutil
import threading
import time

import jax
import numpy as np

from harness import env, reference, result, tabular, trace

FITNESS_LIMIT = 1e-5   # see PERF.md: readings of sound runs and control
# share of a run's encoding searches that made no gamma gain: sound runs
# read up to 0.31 (higgs at 2 bits never gains), a search that keeps its
# parent reads 1; see PERF.md
NO_GAIN_LIMIT = 0.75


def split_masks(n_rows: int, n_encodings: int, fit_seed: int,
                val_fraction: float) -> np.ndarray:
    """bool[E, R]: validation rows of each encoding's split."""
    return np.stack([
        np.random.RandomState(fit_seed + e).rand(n_rows) < val_fraction
        for e in range(n_encodings)])


def permutation(groups: np.ndarray, rng) -> np.ndarray:
    """Row order that keeps every row inside its split group."""
    perm = np.arange(len(groups))
    for g in np.unique(groups):
        idx = np.flatnonzero(groups == g)
        perm[idx] = rng.permutation(idx)
    return perm


def watchdog(limit_s: float, on_expire) -> threading.Timer:
    """A search that has not returned within ``limit_s`` never will:
    ``on_expire`` ends the run not correct.  Cancel it on return."""
    timer = threading.Timer(limit_s, on_expire)
    timer.daemon = True
    timer.start()
    return timer


def classifier(config: dict, fit_seed: int):
    from repro.core.api import AutoTinyClassifier
    from repro.core.encoding import EncodingConfig

    return AutoTinyClassifier(
        n_gates=config["n_gates"], fn_set=config["fn_set"],
        encodings=[EncodingConfig(s, b) for s, b in config["encodings"]],
        lam=config["lam"], gamma=config["gamma"], kappa=config["kappa"],
        max_gens=config["max_gens"], val_fraction=config["val_fraction"],
        seed=fit_seed, backend=config["backend"])


def warm(config: dict, x: np.ndarray, y: np.ndarray, n_classes: int) -> None:
    """Compile (or load) the search program of each input width on the
    real shapes; empty masks make each loop stop after ``kappa``."""
    import jax.numpy as jnp
    from repro.core import encoding as E
    from repro.core.evolve import evolve_packed
    from repro.core.genome import CircuitSpec

    clf = classifier(config, 0)
    n_out = max(1, int(np.ceil(np.log2(max(n_classes, 2)))))
    seen = set()
    for ecfg in clf.encodings:
        if ecfg.bits in seen:
            continue
        seen.add(ecfg.bits)
        enc = E.fit_encoder(x, ecfg)
        bits = E.encode(enc, x)
        data = E.pack_dataset(bits, y, n_classes, n_out)
        # two arrays, as a fit passes: one array twice is one operand,
        # and the program would differ from the fit's
        train, val = (jnp.zeros(data.x_words.shape[1], jnp.uint32)
                      for _ in range(2))
        spec = CircuitSpec(n_inputs=bits.shape[1], n_nodes=clf.n_gates,
                           n_outputs=n_out, fn_set=clf.fn_set)
        final = evolve_packed(jax.random.key(0), spec, clf.cfg, data,
                              train, val)
        jax.block_until_ready(final)


def seed_fit_seed(seed: int) -> int:
    """The fit seed of a run's seed-drawn fit: the program keys each
    encoding's search with ``fit seed * 1000 + encoding``, in 32 bits."""
    return 1 + seed % 999_983


def chance_floors(y, val_masks, n_classes: int) -> list[float]:
    """Each split's validation fitness of a constant circuit (class 0 on
    every row), from the reference: what a search that found nothing
    reads.  Reported beside each search's fitness; not a check, since no
    fault separates it from sound runs (PERF.md)."""
    n_out = max(1, int(np.ceil(np.log2(max(n_classes, 2)))))
    const = np.zeros((len(y), n_out), bool)
    return [reference.balanced_accuracy(const, y, m, n_classes)
            for m in val_masks]


def chosen_encoding(config: dict, clf) -> int:
    encs = [tuple(e) for e in config["encodings"]]
    return encs.index((clf.encoder_.strategy, clf.encoder_.bits))


def invalid_genes(config: dict, clf) -> int:
    """Genes out of range: a function outside the set, an edge to a
    later signal, an output past the last gate."""
    n_fns = len(reference.FUNCTION_SETS[config["fn_set"]])
    gate_fn = np.asarray(clf.genome_.gate_fn)
    edge_src = np.asarray(clf.genome_.edge_src)
    out_src = np.asarray(clf.genome_.out_src)
    n_in = clf.encoder_.thresholds.shape[0] * clf.encoder_.bits
    limit = n_in + np.arange(len(gate_fn))
    return int((gate_fn < 0).sum() + (gate_fn >= n_fns).sum()
               + (edge_src < 0).sum() + (edge_src >= limit[:, None]).sum()
               + (out_src < 0).sum() + (out_src >= n_in + len(gate_fn)).sum())


def reference_fitness(config: dict, clf, x, y, n_classes, val_masks,
                      dtype=np.float64) -> tuple[float, float]:
    """(validation, train) balanced accuracy of the fit's chosen circuit
    on its chosen encoding's split, from the reference alone."""
    strategy, bits = clf.encoder_.strategy, clf.encoder_.bits
    edges = reference.thresholds(x, strategy, bits)
    fn_set = np.asarray(reference.FUNCTION_SETS[config["fn_set"]])
    out = reference.gate_walk(
        fn_set[np.asarray(clf.genome_.gate_fn)],
        np.asarray(clf.genome_.edge_src), np.asarray(clf.genome_.out_src),
        reference.encode(x, edges, reference.code_table(strategy, bits)))
    val = val_masks[chosen_encoding(config, clf)]
    return (reference.balanced_accuracy(out, y, val, n_classes, dtype),
            reference.balanced_accuracy(out, y, ~val, n_classes, dtype))


def check_fit(config: dict, clf, x, y, n_classes, val_masks) -> dict:
    """The numbers one fit is judged by: each 0 when sound, but the
    fitness gap, which is float32 rounding, and the searches without a
    gain, which the run judges as a share of all its searches."""
    records = clf.records_
    chosen = chosen_encoding(config, clf)
    vals = [r.val_fitness for r in records]
    edges = reference.thresholds(x, clf.encoder_.strategy, clf.encoder_.bits)
    invalid = invalid_genes(config, clf)
    gap = float("inf")
    if not invalid:
        ref_val, ref_train = reference_fitness(config, clf, x, y, n_classes,
                                               val_masks)
        gap = max(abs(records[chosen].val_fitness - ref_val),
                  abs(records[chosen].train_fitness - ref_train))
    return {
        "fitness_gap": gap,
        "encoder_mismatches":
            int((np.asarray(clf.encoder_.thresholds) != edges).sum()),
        "selection_mismatches": int(chosen != int(np.argmax(vals))),
        "invalid_genes": invalid,
        # the loop ends after kappa generations without a gamma gain: one
        # that ends within kappa never gained (or never ran)
        "searches_without_gain":
            sum(r.generations <= int(config["kappa"]) for r in records),
    }


def fit_tables(cell, seed: int, seconds: float):
    """The cell's table, its classes, each encoding's validation rows, and
    the seed's row orders for the window's fits: ``[(x, y), ...]``."""
    config, params = cell.config, cell.traffic
    x, y, n_classes = tabular.table(params["dataset"], params["table_seed"])
    val_masks = split_masks(len(y), len(config["encodings"]),
                            int(params["fit_seed"]), config["val_fraction"])
    groups = (val_masks.astype(np.int64)
              << np.arange(len(val_masks))[:, None]).sum(axis=0)
    rng = np.random.default_rng(seed)
    orders = []
    for _ in range(int(np.ceil(seconds / params["min_fit_s"])) + 1):
        perm = permutation(groups, rng)
        orders.append((np.ascontiguousarray(x[perm]), y[perm]))
    return x, y, n_classes, val_masks, orders


def run(cell, seed: int, seconds: float, traced: bool, *,
        require_chip: bool = True, control=None) -> dict:
    """One run of a search cell.  ``control(config, clf, x, y, n_classes,
    val_masks)``, where given, rewrites each fit's result before the
    check: the reference put in the program's place."""
    config, params = cell.config, cell.traffic
    devices = env.chips(cell.chips, require_chip=require_chip)
    compiles = env.CompileCounter()
    watch = env.GcWatch()
    fit_seed = int(params["fit_seed"])
    x, y, n_classes, val_masks, tables = fit_tables(cell, seed, seconds)
    fits, times = [], []
    state = {"open": "set-up's"}

    def expired():
        env.log(f"the {state['open']} search has not returned after "
                f"{params['fit_timeout_s']} s")
        result.emit(correct=False, attempted=len(fits) + 1,
                    failed=1, metrics={}, device=env.device_report(devices),
                    info={"fits": len(fits)},
                    checks={"fits_never_done": (1, 0)})
        os._exit(3)

    def timed_fit(what, fseed, xk, yk):
        state["open"] = what
        dog = watchdog(float(params["fit_timeout_s"]), expired)
        clf = classifier(config, fseed).fit(xk, yk, n_classes)
        jax.block_until_ready(clf.genome_)
        dog.cancel()
        return clf

    dog = watchdog(float(params["fit_timeout_s"]), expired)
    warm(config, x, y, n_classes)
    dog.cancel()
    tracer_dir = None
    if traced:
        tracer_dir = os.path.join(env.OUT_DIR, "trace", cell.name)
        shutil.rmtree(tracer_dir, ignore_errors=True)
        trace.start(tracer_dir)
        annotation = jax.profiler.TraceAnnotation(trace.WINDOW_ANNOTATION)
    gc.collect()
    gc.freeze()
    setup_s = env.process_age_s()
    c0 = compiles.snapshot()
    watch.start()
    if traced:
        annotation.__enter__()
    t0 = time.perf_counter()
    t0_monotonic = time.monotonic()
    while True:
        xk, yk = tables[len(fits) % len(tables)]
        t_fit = time.perf_counter()
        clf = timed_fit(f"window's fit {len(fits)}", fit_seed, xk, yk)
        times.append(time.perf_counter() - t_fit)
        fits.append((clf, xk, yk, val_masks))
        if time.perf_counter() - t0 >= seconds:
            break
    t1 = time.perf_counter()
    if traced:
        annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
    c1 = compiles.snapshot()
    process = watch.report(t0)
    device = env.device_report(devices)
    gens = sum(r.generations for clf, *_ in fits for r in clf.records_)
    window = len(fits)
    # untimed: a search of this seed's own, on its own table and split
    sx, sy, _ = tabular.table(params["dataset"], seed)
    s_seed = seed_fit_seed(seed)
    s_masks = split_masks(len(sy), len(config["encodings"]), s_seed,
                          config["val_fraction"])
    fits.append((timed_fit("seed's fit", s_seed, sx, sy), sx, sy, s_masks))
    c2 = compiles.snapshot()
    info = {
        "fits": window,
        "generations": gens,
        "window_s": t1 - t0,
        # on the machine's monotonic clock, to line fits up with other
        # processes' records
        "window_start_monotonic_s": t0_monotonic,
        "fit_s": times,
        "window_compiles": (c1[0] - c0[0]) - (c1[1] - c0[1]),
        "window_cache_loads": c1[1] - c0[1],
        "window_compile_s": c1[2] - c0[2],
        "seed_fit_compiles": (c2[0] - c1[0]) - (c2[1] - c1[1]),
        "seed_fit_seed": s_seed,
        **process,
    }
    out = {"setup_s": setup_s, "device": device, "info": info,
           "attempted": len(fits),
           "metrics": {"search_gens_per_s": (gens / (t1 - t0), "gens/s")}}
    if traced:
        summary = trace.reduce_trace(trace.find_xplane(tracer_dir),
                                     len(devices))
        shutil.rmtree(tracer_dir, ignore_errors=True)
        out["run"] = result.Run(
            cell=cell, device_kind=device["kind"], trace=summary,
            counters={"fits": window, "generations": gens,
                      "compile_requests": c1[0] - c0[0]})
    worst, failed, no_gain = {}, 0, 0
    for clf, xk, yk, masks in fits:
        if control is not None:
            control(config, clf, xk, yk, n_classes, masks)
        got = check_fit(config, clf, xk, yk, n_classes, masks)
        no_gain += got.pop("searches_without_gain")
        bad = False
        for name, v in got.items():
            worst[name] = max(worst.get(name, v), v)
            bad |= v > (FITNESS_LIMIT if name == "fitness_gap" else 0)
        failed += bad
    checks = {k: (v, FITNESS_LIMIT if k == "fitness_gap" else 0)
              for k, v in worst.items()}
    checks["no_gain_share"] = (
        no_gain / sum(len(clf.records_) for clf, *_ in fits), NO_GAIN_LIMIT)
    if checks["no_gain_share"][0] > NO_GAIN_LIMIT:
        failed = len(fits)
    # what the gain check reads: each encoding's generations; and the
    # margin of each validation fitness over its constant circuit
    for label, (clf, _, yk, masks) in (("window", fits[0]),
                                       ("seed", fits[-1])):
        floors = chance_floors(yk, masks, n_classes)
        info[f"{label}_fit_generations"] = [r.generations
                                            for r in clf.records_]
        info[f"{label}_fit_val_over_chance"] = [
            r.val_fitness - f for r, f in zip(clf.records_, floors)]
    out["failed"] = failed
    out["checks"] = checks
    return out
