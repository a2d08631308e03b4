"""The per-layer readers of ``metrics/``: the host-clock epoch readers on
hand-made intervals, and a traced run driven on the CPU at a small size,
whose line carries the fit driver's metrics."""
import jax
import pytest

import run as RUN
from harness_cell import small_cell


@pytest.mark.parametrize("name,want", [
    # inclusive quantiles of 1..20 ms: the 95th lies at 19.05
    ("epoch_interval_p95_ms", 19.05),
    ("epoch_interval_median_ms", 10.5),
])
def test_epoch_interval_readers(name, want):
    ctx = {"epoch_intervals_s": [k * 1e-3 for k in range(20, 0, -1)]}
    assert RUN._load_reader(name)(ctx) == pytest.approx(want)
    assert RUN._load_reader(name)({"epoch_intervals_s": []}) is None


def test_traced_run_reports_fit_driver_metrics():
    out = RUN.run_cell(small_cell(), 2**31 + 7, 0.0, True, jax.devices(),
                       log=lambda s: None)
    m = out["metrics"]
    for name in ("fit_sync_ms", "epoch_interval_p95_ms",
                 "epoch_interval_median_ms"):
        assert m[name]["value"] > 0, name
    assert (m["epoch_interval_p95_ms"]["value"]
            >= m["epoch_interval_median_ms"]["value"])
    assert out["device"]["window_s"] > 0
