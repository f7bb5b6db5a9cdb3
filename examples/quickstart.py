"""Quickstart: evolve a Tiny Classifier circuit for a tabular dataset and
run the full paper toolflow — accuracy, netlist, Verilog/C RTL, and the
ASIC/FlexIC/FPGA cost reports (paper Fig. 7).

    PYTHONPATH=src python examples/quickstart.py [dataset]
"""
import sys

sys.path.insert(0, "src")

from repro.core import hardware
from repro.core.api import AutoTinyClassifier
from repro.core.encoding import EncodingConfig
from repro.data import load_dataset, train_test_split
from repro.utils.compile_cache import use_compile_cache


def main(dataset: str = "blood"):
    ds = load_dataset(dataset)
    train, test = train_test_split(ds, test_fraction=0.2, seed=0)
    print(f"dataset={ds.name}: {ds.n_rows} rows, {ds.n_features} features, "
          f"{ds.n_classes} classes")

    clf = AutoTinyClassifier(
        n_gates=300,
        fn_set="full",
        encodings=(EncodingConfig("quantize", 2),
                   EncodingConfig("quantile", 2)),
        kappa=300,
        max_gens=3000,
        seed=0,
    )
    clf.fit(train.x, train.y, ds.n_classes)
    for r in clf.records_:
        print(f"  encoding={r.encoding.strategy}/{r.encoding.bits}b  "
              f"val={r.val_fitness:.3f}  gens={r.generations}")
    print(f"test balanced accuracy: {clf.balanced_score(test.x, test.y):.3f}")

    net = clf.netlist()
    print(f"\nnetlist: {net.n_gates} active gates "
          f"({net.logic_ge():.1f} GE logic + {net.buffer_bits()} buffer bits), "
          f"depth {net.depth()}")

    print("\n--- Verilog (first 15 lines) ---")
    print("\n".join(clf.to_verilog().splitlines()[:15]))
    print("...\n--- HLS C (first 8 lines) ---")
    print("\n".join(clf.to_c().splitlines()[:8]))

    print("\n--- hardware reports ---")
    for tech in (hardware.SILICON_45NM, hardware.FLEXIC_08UM):
        rep = clf.hardware_report(tech)
        print(f"{tech.name:14s}: {rep.ge_total:7.1f} GE  "
              f"{rep.area_mm2:9.6f} mm²  {rep.power_mw:7.4f} mW  "
              f"fmax={rep.fmax_hz/1e3:9.1f} kHz  "
              f"LUTs={rep.luts} FFs={rep.ffs}")


if __name__ == "__main__":
    use_compile_cache()
    main(sys.argv[1] if len(sys.argv) > 1 else "blood")
