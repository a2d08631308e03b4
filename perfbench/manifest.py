"""``BENCHMARK.json`` and the files it names, loaded and checked.

Everything that belongs to one cell is found by name: the cell's own file
``workloads/<cell>.json``, its configuration's file (``file`` in
``BENCHMARK.json``), its traffic mix ``traffic/<traffic>.json`` and one
reader ``metrics/<metric>.py`` per per-layer metric.
"""
from __future__ import annotations

import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")


class ManifestError(ValueError):
    pass


def check_name(s, what: str) -> str:
    if not isinstance(s, str) or not NAME.match(s):
        raise ManifestError(f"{what} {s!r}: a name is 1-64 of A-Z a-z 0-9 "
                            f"_ . - and starts with a letter, digit or _")
    return s


def check_unit(s, what: str) -> str:
    if not isinstance(s, str) or not UNIT.match(s):
        raise ManifestError(f"{what} unit {s!r}: 1-16 of A-Z a-z 0-9 "
                            f"_ / % . -")
    return s


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def check(manifest: dict) -> dict:
    """Raise ManifestError unless names, units and cross-references hold."""
    seen = set()
    for c in manifest["configs"]:
        check_name(c["name"], "config")
        for k in c["reduced"]:
            check_name(k, "reduced key")
    configs = {c["name"] for c in manifest["configs"]}
    for w in manifest["workloads"]:
        check_name(w["name"], "workload")
        check_name(w["traffic"], "traffic")
        if w["config"] not in configs:
            raise ManifestError(f"workload {w['name']}: no config "
                                f"{w['config']!r}")
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = set()
    for kind in ("end_to_end", "per_layer"):
        for m in manifest[kind]:
            check_name(m["name"], "metric")
            check_unit(m["unit"], m["name"])
            if m["name"] in seen:
                raise ManifestError(f"metric {m['name']} twice")
            seen.add(m["name"])
            for w in m.get("workloads", ()):
                if w not in cells:
                    raise ManifestError(f"metric {m['name']}: no cell {w!r}")
            if kind == "end_to_end":
                e2e.add(m["name"])
            elif m["moves"] not in e2e:
                raise ManifestError(f"metric {m['name']} moves "
                                    f"{m['moves']!r}, not an end-to-end "
                                    f"metric")
    return manifest


def load(root: Path = ROOT) -> dict:
    return check(_read(root / "BENCHMARK.json"))


def cell(manifest: dict, name: str, root: Path = ROOT) -> dict:
    """One cell with everything it needs: {"name", "chips", "config",
    "traffic", "spec", "e2e", "per_layer"}."""
    check_name(name, "workload")
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in manifest["configs"]
                if c["name"] == entry["config"])

    def applies(m):
        return name in m.get("workloads", [name])

    return {"name": name, "chips": entry["chips"],
            "config": _read(root / conf["file"]),
            "traffic": _read(HERE / "traffic" / f"{entry['traffic']}.json"),
            "spec": _read(HERE / "workloads" / f"{name}.json"),
            "e2e": [m for m in manifest["end_to_end"] if applies(m)],
            "per_layer": [m for m in manifest["per_layer"] if applies(m)]}
