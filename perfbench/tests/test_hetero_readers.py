"""The cohort epoch's readers on hand-made extracts, events as
``layers.extract`` keeps them: ``hetero_epoch_device_ms`` (module events
of ``jit_hetero_epoch``) and ``union_view_ms`` (ops under the
``union_view`` scope), per epoch the trace kept, each None where its
module or scope map is absent; and the cell ``mixed48.always`` as the
manifest loads it."""
import collections

import pytest

import manifest as M
import run as RUN

HETERO = "jit_hetero_epoch"


def _extract(module=HETERO, scopes=True):
    """Two chips, one recorded epoch of ``module`` each (1000 and
    1200 ns), and a homogeneous ``jit_epoch`` beside it whose ops carry
    the same instruction names."""
    prefix = "jit(hetero_epoch)/while/body"
    devices = {}
    for k, length in enumerate((1000, 1200)):
        devices[f"/device:TPU:{k}"] = {
            "modules": [[f"{module}({k + 1})", 0, length],
                        ["jit_epoch(9)", 2000, 400]],
            "ops": [["%while.1", 0, length],
                    ["%scatter.2", 10, 40],      # union view: assembly
                    ["%fusion.3", 40, 30],       # union view, overlapping
                    ["%fusion.4", 100, 500],     # policy round
                    ["%gather.5", 700, 20 + k * 10],  # union view: back
                    ["%scatter.2", 2100, 100]]}  # jit_epoch: not counted
    ex = {"devices": devices, "host": []}
    if scopes:
        ex["op_scopes"] = {
            f"{module}({k + 1})": {
                "%while.1": f"{prefix}",
                "%scatter.2": f"{prefix}/union_view/scatter",
                "%fusion.3": f"{prefix}/union_view/pad",
                "%fusion.4": f"{prefix}/policy_round/eq7_score/dot",
                "%gather.5": f"{prefix}/union_view/gather"}
            for k in range(2)}
        ex["op_scopes"]["jit_epoch(9)"] = {
            "%scatter.2": "jit(epoch)/union_view/x"}
    return ex


def _ctx(ex, epochs=1):
    return {"trace": ex, "window": (0, 3000), "epochs": epochs,
            "spec": {"epoch_module": HETERO}}


def test_hetero_epoch_device_ms():
    read = RUN._load_reader("hetero_epoch_device_ms")
    # (1000 + 1200) / 2 chips, one epoch each, in ms
    assert read(_ctx(_extract())) == pytest.approx(1100e-6)
    assert read(_ctx(_extract(scopes=False))) == pytest.approx(1100e-6)
    # a trace of 50 epochs whose device events ran out after the first:
    # per epoch kept, not per epoch traced
    assert read(_ctx(_extract(), epochs=50)) == pytest.approx(1100e-6)
    # the homogeneous module alone, as a program without the cohort
    # engine's own module name traces it
    assert read(_ctx(_extract(module="jit_epoch"))) is None
    assert read(_ctx(_extract(), epochs=0)) is None


def test_union_view_ms():
    read = RUN._load_reader("union_view_ms")
    # chip 0: [10, 70] + [700, 720] = 80 ns; chip 1: 60 + 30 = 90 ns;
    # mean 85 ns, one epoch kept
    assert read(_ctx(_extract())) == pytest.approx(85e-6)
    assert read(_ctx(_extract(), epochs=50)) == pytest.approx(85e-6)
    # devtrace.extract keeps no scope maps
    assert read(_ctx(_extract(scopes=False))) is None
    assert read(_ctx(_extract(module="jit_epoch"))) is None


def test_kept_epochs_never_exceed_the_epochs_traced():
    """Two module events in one traced epoch (a chunked dispatch) count
    as that one epoch."""
    ex = _extract()
    for k, dev in enumerate(ex["devices"].values()):
        dev["modules"].append([f"{HETERO}({k + 5})", 1500, 300])
    read = RUN._load_reader("hetero_epoch_device_ms")
    # ((1000 + 300) + (1200 + 300)) / 2 chips
    assert read(_ctx(ex, epochs=1)) == pytest.approx(1400e-6)
    assert read(_ctx(ex, epochs=2)) == pytest.approx(700e-6)


def test_mixed_cell_loads():
    man = M.load()
    cell = M.cell(man, "mixed48.always")
    conf = cell["config"]
    nfs = [conf["nf_choices"][h % len(conf["nf_choices"])]
           for h in range(conf["sites"])]
    assert collections.Counter(nfs) == {3: 16, 4: 16, 5: 16}
    assert cell["spec"]["epoch_module"] == HETERO
    assert [m["name"] for m in cell["per_layer"]] == \
        ["hetero_epoch_device_ms"]
    silo = next(c for c in man["configs"] if c["name"] == "table4-silo32")
    assert cell["config"]["reduced"] == silo["reduced"]
