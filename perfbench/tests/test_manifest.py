"""The manifest loader: names and units outside the allowed characters are
refused, and the repository's own ``BENCHMARK.json`` loads."""
import copy

import pytest

import manifest as M

GOOD = {
    "configs": [{"name": "cfg-a", "reduced": ["sites"]}],
    "workloads": [{"name": "cfg.a", "config": "cfg-a", "traffic": "always"}],
    "end_to_end": [{"name": "rate", "unit": "client-rounds/s"}],
    "per_layer": [{"name": "mfu", "unit": "%", "moves": "rate",
                   "workloads": ["cfg.a"]}],
}


def test_good_manifest_passes():
    M.check(copy.deepcopy(GOOD))


@pytest.mark.parametrize("bad", ["a b", "a,b", "a/b", ".a", "", "é",
                                 "x" * 65])
def test_bad_names_refused(bad):
    for path in (("workloads", 0, "name"), ("end_to_end", 0, "name"),
                 ("configs", 0, "name"), ("workloads", 0, "traffic")):
        m = copy.deepcopy(GOOD)
        node = m
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = bad
        with pytest.raises(M.ManifestError):
            M.check(m)


@pytest.mark.parametrize("bad", ["tokens per second", "µs", "",
                                 "x" * 17, "ms\t"])
def test_bad_units_refused(bad):
    m = copy.deepcopy(GOOD)
    m["per_layer"][0]["unit"] = bad
    with pytest.raises(M.ManifestError):
        M.check(m)


def test_dangling_references_refused():
    m = copy.deepcopy(GOOD)
    m["per_layer"][0]["workloads"] = ["nope"]
    with pytest.raises(M.ManifestError):
        M.check(m)
    m = copy.deepcopy(GOOD)
    m["per_layer"][0]["moves"] = "mfu"
    with pytest.raises(M.ManifestError):
        M.check(m)


def test_repository_manifest_loads_every_cell():
    man = M.load()
    for w in man["workloads"]:
        cell = M.cell(man, w["name"])
        assert cell["spec"]["limits"]
        assert {m["name"] for m in cell["e2e"]} >= {"setup_s"}
        assert cell["per_layer"]
        for m in cell["per_layer"]:
            assert (M.HERE / "metrics" / f"{m['name']}.py").exists()
