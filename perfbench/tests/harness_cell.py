"""A cell at a size a CPU test run holds: the real configuration and
traffic files with fewer, smaller sites, and the real cell's limits.
``MIXED`` runs the same cell over sites of 3, 4 and 5 features, which
routes the fit through the cohort engine."""
import json

import manifest as M

CELLS = tuple(w["name"] for w in M.load()["workloads"])
MIXED = (3, 4, 5)


def small_cell(name: str = CELLS[0], nf_choices=None) -> dict:
    cell = M.cell(M.load(), name)
    conf = json.loads(json.dumps(cell["config"]))
    if nf_choices:
        conf["nf_choices"] = list(nf_choices)
    # two sites of every feature count, at least four
    conf.update(sites=2 * max(2, len(conf["nf_choices"])),
                patients_per_site=10, events_per_patient=200, epochs=2,
                split_lengths={"train": 100, "valid": 20, "test": 20})
    return {**cell, "config": conf}
