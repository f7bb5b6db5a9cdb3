"""AutoTinyClassifier — the end-to-end toolflow of Fig. 7 as a public API.

fit(X, y):
  1. for each candidate (encoding strategy, bits/input): fit the encoder on
     the training split, pack the bits, 50/50 train/val split (§3.3),
  2. run the 1+λ EGGP search (§3) — optionally island-parallel on a mesh,
  3. keep the circuit with the best validation fitness across encodings
     (paper §5.2: "experiments report the best-achieved accuracy across the
     available encoding strategies with two and four bits per input").
  Every encoding's search is dispatched before any is read back, so the
  host encodes the next candidate while the device runs the last one's
  search; the results are identical to running them one at a time.

predict / balanced_score: evaluate the evolved circuit.
to_verilog / to_c / hardware_report: the ASIC/FPGA toolflow (§4).
"""
from __future__ import annotations

import dataclasses
import json
import warnings
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.core import encoding as E
from repro.core import fitness as F
from repro.core import gates, hardware, netlist, verilog
from repro.core.evolve import EvolveConfig, EvolveState, evolve_packed
from repro.core.genome import CircuitSpec, Genome, opcodes
from repro.observability.trace import NULL_TRACER

# On-disk ServableCircuit bundle format (see ServableCircuit.save):
# a single .npz holding the genome/encoder arrays plus a JSON metadata
# string.  Bump on any incompatible layout change; load() rejects
# versions it does not know.
#
# Version history:
#   1 — genome + spec + encoder + class count + validated backend.
#   2 — adds optional lineage metadata (parent content hash, refit
#       generation, shadow-window stats, promotion verdict) and the
#       fit-time per-bit activation frequencies (``enc_ref_stats``) the
#       online drift detectors baseline against.  v1 bundles still load
#       (lineage and reference stats simply absent).
SERVABLE_FORMAT_VERSION = 2
_SERVABLE_READABLE_VERSIONS = (1, 2)
SERVABLE_FORMAT_KIND = "tiny-classifier-circuits/servable-circuit"


def read_servable_meta(path: str) -> dict:
    """Read just the JSON metadata of a saved ServableCircuit bundle
    (format version, circuit spec, encoder config, validating backend)."""
    with np.load(path, allow_pickle=False) as z:
        return json.loads(str(z["meta"]))


@dataclasses.dataclass
class FitRecord:
    encoding: E.EncodingConfig
    val_fitness: float
    train_fitness: float
    generations: int


DEFAULT_ENCODINGS = (
    E.EncodingConfig("quantize", 2),
    E.EncodingConfig("quantize", 4),
    E.EncodingConfig("quantile", 2),
    E.EncodingConfig("quantile", 4),
)


def decode_predictions(
    out_words, n_rows: int, n_classes: int
) -> np.ndarray:
    """Packed circuit output words → int class ids, length exactly n_rows.

    `pack_bits_rows` pads the row axis up to the 32-bit word boundary; the
    circuit computes garbage bits for those pad rows, so the decode must trim
    to the true row count before the class clamp (out-of-range binary codes
    map to the last class, matching training-time fitness masking).

    Pure numpy on purpose: this runs on the host per tenant per serving
    tick with a request-dependent ``n_rows``, and a jnp decode would jit
    a fresh set of kernels for every new row count (measured: ~0.5 s per
    novel tick shape — fatal for a deadline scheduler)."""
    words = np.asarray(out_words)                       # u32[O, W]
    shifts = np.arange(E.WORD, dtype=np.uint32)
    bits = (words[..., None] >> shifts) & np.uint32(1)  # (O, W, 32)
    bits = bits.reshape(words.shape[0], -1)[:, :n_rows].astype(np.int64)
    weights = (np.int64(1) << np.arange(words.shape[0], dtype=np.int64))
    ids = (bits * weights[:, None]).sum(axis=0)
    return np.minimum(ids, n_classes - 1)


@dataclasses.dataclass(frozen=True)
class ServableCircuit:
    """Deployable inference artifact of a fitted classifier: the evolved
    genome plus everything needed to run it on raw float features (fitted
    encoder, class count).  This is what `repro.serve.circuits` registers —
    fitting state (records, search config) deliberately stays behind.
    """

    spec: CircuitSpec
    genome: Genome
    encoder: E.Encoder
    n_classes: int
    # -- format v2 provenance (optional, excluded from equality) -------
    # lineage: who this circuit descends from and how it got promoted —
    # JSON-serializable dict with keys like ``parent_hash`` (content hash
    # of the circuit it was refit from), ``refit_generation`` (how many
    # online refits deep this line is), ``shadow`` (the shadow-window
    # stats the promotion decision saw) and ``verdict``.  None for
    # offline fits and v1 bundles.
    lineage: "dict | None" = dataclasses.field(default=None, compare=False)
    # ref_stats: fit-time per-bit activation frequencies of the encoded
    # training data (f32[n_bits_total]) — the reference snapshot the
    # serving stack's drift detectors compare live traffic against.
    ref_stats: "np.ndarray | None" = dataclasses.field(
        default=None, compare=False, repr=False
    )

    def __post_init__(self):
        assert self.spec.n_inputs == self.encoder.n_bits_total, (
            self.spec.n_inputs, self.encoder.n_bits_total,
        )
        assert self.n_classes >= 2
        if self.ref_stats is not None:
            assert np.shape(self.ref_stats) == (self.encoder.n_bits_total,), (
                np.shape(self.ref_stats), self.encoder.n_bits_total,
            )

    @property
    def n_inputs(self) -> int:
        return self.spec.n_inputs

    @property
    def n_outputs(self) -> int:
        return self.spec.n_outputs

    def predict(
        self, x: np.ndarray, *,
        backend: "str | runtime.EvalBackend" = "ref",
    ) -> np.ndarray:
        """Single-model reference path (the serving engine must match this
        bit-exactly)."""
        be = runtime.resolve_backend(backend)
        bits = E.encode(self.encoder, np.asarray(x, np.float32))
        r = bits.shape[0]
        x_words = E.pack_bits_rows(bits, E.n_words(r))
        out = be.eval_circuit(
            opcodes(self.genome, self.spec),
            self.genome.edge_src,
            self.genome.out_src,
            jnp.asarray(x_words),
        )
        return decode_predictions(out, r, self.n_classes)

    def serve_async(
        self, *,
        backend: "str | runtime.EvalBackend" = "ref",
        tenant: str = "default",
        qos=None,
        clock=None,
    ):
        """One-call async serving of this artifact.

        Builds a single-tenant `CircuitRegistry` + `CircuitServer` and
        returns an (unstarted) `AsyncCircuitServer`; enter it to run the
        deadline scheduler::

            with sc.serve_async() as frontend:
                fut = frontend.enqueue("default", x, deadline_s=0.05)
                ids = fut.result()

        or from a coroutine::

            async with sc.serve_async() as frontend:
                ids = await frontend.submit("default", x)

        ``qos`` optionally pins the tenant's `TenantQoS`; ``clock``
        injects a time source (tests).  More tenants can be added to
        ``frontend.server.registry`` afterwards — this is a convenience
        entry, not a constraint."""
        from repro.serve.async_frontend import AsyncCircuitServer
        from repro.serve.circuits import CircuitRegistry, CircuitServer

        reg = CircuitRegistry()
        reg.add(tenant, self, qos=qos)
        server = CircuitServer(reg, backend=backend)
        kwargs = {} if clock is None else {"clock": clock}
        return AsyncCircuitServer(server, **kwargs)

    # -- persistence ---------------------------------------------------
    def save(
        self, path: str, *,
        validated_backend: "str | runtime.EvalBackend" = "ref",
    ) -> str:
        """Deprecated alias of `save_servable` — one more release, then
        gone.  Prefer `save_servable(sc, path)` for single bundles, or an
        `repro.serve.artifacts.ArtifactStore` for anything fleet-shaped
        (content-addressed objects, one manifest, executables)."""
        warnings.warn(
            "ServableCircuit.save() is deprecated; use "
            "repro.core.api.save_servable(circuit, path) or an "
            "repro.serve.artifacts.ArtifactStore",
            DeprecationWarning, stacklevel=2,
        )
        return save_servable(self, path, validated_backend=validated_backend)

    @classmethod
    def load(cls, path: str) -> "ServableCircuit":
        """Deprecated alias of `load_servable` — one more release, then
        gone."""
        warnings.warn(
            "ServableCircuit.load() is deprecated; use "
            "repro.core.api.load_servable(path) or an "
            "repro.serve.artifacts.ArtifactStore",
            DeprecationWarning, stacklevel=2,
        )
        return load_servable(path)


def save_servable(
    circuit: ServableCircuit, path: str, *,
    validated_backend: "str | runtime.EvalBackend" = "ref",
) -> str:
    """Write a `ServableCircuit` as a versioned npz+JSON bundle.

    The bundle carries everything `load_servable` needs to serve raw
    float features — genome arrays, circuit spec (incl. the opcode
    function set), fitted encoder parameters, class count — plus a
    format version and the name of the backend the artifact was
    validated on.  Returns the path written (np.savez appends ``.npz``
    when missing).  This is the one canonical bundle writer; the
    registry/fleet persistence layers (`repro.serve.artifacts`) delegate
    here so every circuit on disk shares one format.
    """
    be_name = runtime.resolve_backend(validated_backend).name
    meta = {
        "kind": SERVABLE_FORMAT_KIND,
        "format_version": SERVABLE_FORMAT_VERSION,
        "spec": {
            "n_inputs": int(circuit.spec.n_inputs),
            "n_nodes": int(circuit.spec.n_nodes),
            "n_outputs": int(circuit.spec.n_outputs),
            "fn_set": [int(op) for op in circuit.spec.fn_set],
        },
        "encoder": {
            "strategy": circuit.encoder.strategy,
            "bits": int(circuit.encoder.bits),
        },
        "n_classes": int(circuit.n_classes),
        "validated_backend": be_name,
        # v2: lineage rides the JSON (it is metadata, not tensors);
        # json.dumps raises here — not at load — if a caller sneaks
        # in something non-serializable
        "lineage": circuit.lineage,
    }
    if not path.endswith(".npz"):
        path = path + ".npz"
    arrays = {
        "gate_fn": np.asarray(circuit.genome.gate_fn, np.int32),
        "edge_src": np.asarray(circuit.genome.edge_src, np.int32),
        "out_src": np.asarray(circuit.genome.out_src, np.int32),
        "enc_thresholds": np.asarray(circuit.encoder.thresholds, np.float32),
        "enc_codes": np.asarray(circuit.encoder.codes, np.uint8),
    }
    if circuit.ref_stats is not None:
        arrays["enc_ref_stats"] = np.asarray(circuit.ref_stats, np.float32)
    np.savez(path, meta=json.dumps(meta), **arrays)
    return path


def load_servable(path: str) -> ServableCircuit:
    """Load a bundle written by `save_servable`; predictions are
    bit-identical to the artifact that was saved."""
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["meta"]))
        if meta.get("kind") != SERVABLE_FORMAT_KIND:
            raise ValueError(
                f"{path}: not a ServableCircuit bundle "
                f"(kind={meta.get('kind')!r})"
            )
        version = meta.get("format_version")
        if version not in _SERVABLE_READABLE_VERSIONS:
            raise ValueError(
                f"{path}: unsupported bundle format version {version!r} "
                f"(this build reads versions "
                f"{list(_SERVABLE_READABLE_VERSIONS)})"
            )
        spec = CircuitSpec(
            n_inputs=meta["spec"]["n_inputs"],
            n_nodes=meta["spec"]["n_nodes"],
            n_outputs=meta["spec"]["n_outputs"],
            fn_set=tuple(meta["spec"]["fn_set"]),
        )
        genome = Genome(
            gate_fn=jnp.asarray(z["gate_fn"], jnp.int32),
            edge_src=jnp.asarray(z["edge_src"], jnp.int32),
            out_src=jnp.asarray(z["out_src"], jnp.int32),
        )
        encoder = E.Encoder(
            thresholds=np.asarray(z["enc_thresholds"], np.float32),
            codes=np.asarray(z["enc_codes"], np.uint8),
            strategy=meta["encoder"]["strategy"],
            bits=meta["encoder"]["bits"],
        )
        # v2 additions; absent from v1 bundles (and optional in v2)
        ref_stats = (
            np.asarray(z["enc_ref_stats"], np.float32)
            if "enc_ref_stats" in z.files else None
        )
    return ServableCircuit(
        spec=spec, genome=genome, encoder=encoder,
        n_classes=meta["n_classes"],
        lineage=meta.get("lineage"),
        ref_stats=ref_stats,
    )


class AutoTinyClassifier:
    def __init__(
        self,
        n_gates: int = 300,
        fn_set: str | tuple[int, ...] = "full",
        encodings: Sequence[E.EncodingConfig] = DEFAULT_ENCODINGS,
        lam: int = 4,
        p: float | None = None,
        gamma: float = 0.01,
        kappa: int = 300,
        max_gens: int = 8000,
        n_out_bits: int | None = None,
        val_fraction: float = 0.5,
        seed: int = 0,
        backend: "str | runtime.EvalBackend" = "ref",
    ):
        self.backend = runtime.resolve_backend(backend)
        self.fn_set = gates.FUNCTION_SETS[fn_set] if isinstance(fn_set, str) else fn_set
        self.n_gates = n_gates
        self.encodings = tuple(encodings)
        self.cfg = EvolveConfig(
            lam=lam, p=p, gamma=gamma, kappa=kappa, max_gens=max_gens,
            backend=self.backend,
        )
        self.n_out_bits = n_out_bits
        self.val_fraction = val_fraction
        self.seed = seed
        # fitted state
        self.spec_: CircuitSpec | None = None
        self.genome_: Genome | None = None
        self.encoder_: E.Encoder | None = None
        self.n_classes_: int | None = None
        self.ref_stats_: np.ndarray | None = None
        self.records_: list[FitRecord] = []

    # ------------------------------------------------------------------
    def fit(self, x: np.ndarray, y: np.ndarray, n_classes: int | None = None):
        """Search a circuit for each encoding and keep the one with the
        best validation fitness (the first of equals).  Every encoding's
        search is dispatched before any is read back, so encoding runs on
        the host while earlier searches run on the device; the records,
        circuit, encoder and ``ref_stats_`` are identical to running the
        searches one at a time."""
        # spans cost one branch unless a JAX profiler capture records
        # (repro.observability.trace)
        with NULL_TRACER.span("fit"):
            return self._fit(x, y, n_classes)

    def _fit(self, x: np.ndarray, y: np.ndarray, n_classes: int | None):
        x = np.asarray(x, np.float32)
        y = np.asarray(y, np.int64)
        self.n_classes_ = n_classes or int(y.max()) + 1
        n_out = self.n_out_bits or max(
            1, int(np.ceil(np.log2(max(self.n_classes_, 2))))
        )
        # every search is dispatched before any is read back: encoding
        # i + 1 runs on the host while search i runs on the device, which
        # runs the searches in dispatch order
        searches = []
        for ei, ecfg in enumerate(self.encodings):
            if searches:
                NULL_TRACER.count("fit.encode_overlapped")
            with NULL_TRACER.span("fit.encode"):
                with NULL_TRACER.span("fit.encode.edges"):
                    enc = E.fit_encoder(x, ecfg)
                with NULL_TRACER.span("fit.encode.bits"):
                    bits = E.encode(enc, x)
                with NULL_TRACER.span("fit.encode.pack"):
                    data = E.pack_dataset(bits, y, self.n_classes_, n_out)
                w = data.x_words.shape[1]
                with NULL_TRACER.span("fit.encode.split"):
                    mtr, mva = E.split_masks(
                        x.shape[0], w, self.val_fraction, seed=self.seed + ei
                    )
            spec = CircuitSpec(
                n_inputs=bits.shape[1], n_nodes=self.n_gates,
                n_outputs=n_out, fn_set=self.fn_set,
            )
            key = jax.random.key(self.seed * 1000 + ei)
            final: EvolveState = evolve_packed(key, spec, self.cfg, data, mtr, mva)
            searches.append((ecfg, spec, enc, bits, final))
        best = None
        self.records_ = []
        while searches:
            # popped, so an encoding's bits go once selection passes them
            ecfg, spec, enc, bits, final = searches.pop(0)
            with NULL_TRACER.span("fit.readback"):  # waits for the loop
                rec = FitRecord(
                    encoding=ecfg,
                    val_fitness=float(final.best_val),
                    train_fitness=float(final.best_train),
                    generations=int(final.gen),
                )
            self.records_.append(rec)
            if best is None or rec.val_fitness > best[0]:
                best = (rec.val_fitness, spec, final.best, enc, bits)
        (_, self.spec_, self.genome_, self.encoder_, best_bits) = best
        # per-bit activation frequency of the chosen encoding's training
        # data: the reference snapshot online drift detection compares
        # live traffic against (bundle v2 `ref_stats`)
        with NULL_TRACER.span("fit.ref_stats"):
            self.ref_stats_ = best_bits.mean(axis=0).astype(np.float32)
        return self

    # ------------------------------------------------------------------
    def _require_fit(self):
        if self.genome_ is None:
            raise RuntimeError("call fit() first")

    def to_servable(self) -> ServableCircuit:
        """Export the deployment artifact (registered by serve.circuits)."""
        self._require_fit()
        return ServableCircuit(
            spec=self.spec_, genome=self.genome_,
            encoder=self.encoder_, n_classes=self.n_classes_,
            ref_stats=self.ref_stats_,
        )

    def predict(self, x: np.ndarray) -> np.ndarray:
        return self.to_servable().predict(x, backend=self.backend)

    def balanced_score(self, x: np.ndarray, y: np.ndarray) -> float:
        pred = self.predict(x)
        y = np.asarray(y)
        return F.balanced_accuracy_rows(
            pred, y, np.ones_like(y, bool), self.n_classes_
        )

    def accuracy(self, x: np.ndarray, y: np.ndarray) -> float:
        return float((self.predict(x) == np.asarray(y)).mean())

    # ------------------------------------------------------------------
    def netlist(self) -> netlist.Netlist:
        self._require_fit()
        return netlist.extract(self.genome_, self.spec_)

    def to_verilog(self, module_name: str = "tiny_classifier",
                   registered: bool = False) -> str:
        return verilog.to_verilog(self.netlist(), module_name, registered)

    def to_c(self, fn_name: str = "tiny_classifier_predict") -> str:
        return verilog.to_c(self.netlist(), fn_name)

    def hardware_report(
        self, tech: hardware.TechModel = hardware.SILICON_45NM,
        design: str = "tiny",
    ) -> hardware.HardwareReport:
        return hardware.tiny_classifier_report(self.netlist(), tech, design)
