"""The trace reduction: busy/idle union, module sums and gap labels, on a
hand-made case and on an extract of a trace recorded on a TPU v5e
(``data/trace_extract.json``: the first 40 ms of a traced
``silo32.always`` fit, events as ``devtrace.extract`` keeps them)."""
import json
from pathlib import Path

import pytest

import devtrace as T

DATA = Path(__file__).resolve().parent / "data" / "trace_extract.json"


def _sweep_busy(events, lo, hi):
    """Covered length by an endpoint sweep (an independent count)."""
    pts = []
    for _n, s, d in events:
        s, t = max(s, lo), min(s + d, hi)
        if t > s:
            pts += [(s, 1), (t, -1)]
    pts.sort(key=lambda p: (p[0], -p[1]))
    depth, last, busy = 0, None, 0
    for x, k in pts:
        if depth > 0:
            busy += x - last
        depth += k
        last = x
    return busy


def test_hand_made_case():
    ops = [["a", 0, 10], ["b", 5, 15], ["a", 30, 10], ["c", 45, 20]]
    host = [["fit", -5, 100], ["exchange", 21, 8]]
    assert T.busy_ns(ops, 0, 50) == 35
    assert T.idle_gaps(ops, 0, 50) == [(20, 30), (40, 45)]
    assert T.label((20, 30), host) == "exchange"
    assert T.label((40, 45), host) == "fit"
    assert T.label((200, 210), host) == "no span"
    assert T.summed_ns(ops, 0, 50, lambda e: e[0] == "b") == 15
    assert T.top_ops(ops, 0, 50)[0] == ["a", 20e-9]
    assert T.union([(0, 1), (1, 2), (5, 6)]) == [(0, 2), (5, 6)]


@pytest.fixture(scope="module")
def extract():
    with open(DATA) as f:
        return json.load(f)


def test_recorded_extract(extract):
    lo, hi = extract["cut"]
    dev = next(iter(extract["devices"].values()))
    ops, mods = dev["ops"], dev["modules"]
    assert ops and mods
    busy = T.busy_ns(ops, lo, hi)
    assert busy == _sweep_busy(ops, lo, hi)
    gaps = T.idle_gaps(ops, lo, hi)
    assert busy + sum(t - s for s, t in gaps) == hi - lo
    assert all(a[1] - a[0] >= b[1] - b[0] for a, b in zip(gaps, gaps[1:]))
    names = {h[0] for h in extract["host"]} | {"no span"}
    assert all(T.label(g, extract["host"]) in names for g in gaps)
    name = mods[0][0]
    direct = sum(min(s + d, hi) - max(s, lo) for n, s, d in mods
                 if n == name and min(s + d, hi) > max(s, lo))
    assert T.summed_ns(mods, lo, hi, lambda e: e[0] == name) == direct
    top = T.top_ops(ops, lo, hi)
    assert len(top) <= 10
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    total = sum(min(s + d, hi) - max(s, lo) for _n, s, d in ops
                if min(s + d, hi) > max(s, lo))
    assert sum(v for _, v in top) <= total * 1e-9 * (1 + 1e-9)
