"""Test-size cells: the cells of ``BENCHMARK.json`` cut down so that a
CPU runs them in seconds (Pallas in interpret mode): small circuits,
short searches, and iris in place of a table of more than 5,000 rows."""
import copy

from harness import spec, tabular

TINY_SEARCH = {"n_gates": 24, "kappa": 12, "max_gens": 40}


def cell(name: str) -> spec.Cell:
    c = copy.deepcopy(spec.load_cell(name))
    c.config.update(TINY_SEARCH)
    if tabular.TABLE1[c.traffic["dataset"]][1] > 5000:
        c.traffic["dataset"] = "iris"
    c.traffic.update({"min_fit_s": 0.5, "fit_timeout_s": 120})
    return c
