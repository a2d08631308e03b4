"""From a profiler trace to the events the per-layer readers need.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
three lists on the trace's one clock, in nanoseconds:

* per device: ``ops`` [name, start, duration] from the "XLA Ops" line
  (a TPU v5e trace under JAX 0.9 carries no name stack on its ops) and
  ``modules`` [name, start, duration] from the "XLA Modules" line;
* ``host``: [name, start, duration] of the host spans: the benchmark's
  ``bench.*`` annotations and the program's ``fit``, ``dispatch`` and
  ``exchange`` spans.

The rest are pure functions over such lists, checked on a recorded
extract in ``tests/``.
"""
from __future__ import annotations

import glob
from collections import defaultdict

HOST_SPANS = ("fit", "dispatch", "exchange")


def op_name(text: str) -> str:
    """An op event's name: the HLO instruction's name, without the
    instruction text a TPU trace appends (``%copy.1 = f32[...] copy(...)``
    -> ``%copy.1``)."""
    return text.split(" = ", 1)[0]


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def extract(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if _is_device(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"].extend([op_name(e.name), e.start_ns,
                                       e.duration_ns] for e in line.events)
                elif line.name == "XLA Modules":
                    dev["modules"].extend([e.name, e.start_ns,
                                           e.duration_ns]
                                          for e in line.events)
            if dev["ops"] or dev["modules"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, e.start_ns, e.duration_ns] for e in line.events
                    if e.name.startswith("bench.")
                    or e.name in HOST_SPANS)
    return out


def window(ex: dict, span: str = "bench.fit"):
    """(start, end) of the first host span named ``span``."""
    for name, t, d in ex["host"]:
        if name == span:
            return t, t + d
    raise ValueError(f"no {span!r} span in the trace")


def clip(events, lo, hi):
    """Events cut to [lo, hi]: (start, end) pairs, empty ones dropped."""
    out = []
    for e in events:
        s, t = max(e[1], lo), min(e[1] + e[2], hi)
        if t > s:
            out.append((s, t))
    return out


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    merged = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [tuple(m) for m in merged]


def busy_ns(ops, lo, hi) -> float:
    return float(sum(t - s for s, t in union(clip(ops, lo, hi))))


def idle_gaps(ops, lo, hi):
    """The gaps in [lo, hi] in which no op ran, longest first."""
    gaps, cur = [], lo
    for s, t in union(clip(ops, lo, hi)):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        gaps.append((cur, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def label(gap, host) -> str:
    """The innermost (shortest) host span that covers the gap's middle."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for name, t, d in host:
        if t <= mid <= t + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "no span"


def summed_ns(events, lo, hi, match) -> float:
    """Summed duration in [lo, hi] of the events ``match(event)`` accepts."""
    return float(sum(t - s for s, t in clip(
        [e for e in events if match(e)], lo, hi)))


def top_ops(ops, lo, hi, k: int = 10):
    """[name, seconds] of the k ops that took most device time."""
    tot = defaultdict(float)
    for e in ops:
        s, t = max(e[1], lo), min(e[1] + e[2], hi)
        if t > s:
            tot[e[0]] += t - s
    return [[n, v * 1e-9] for n, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def mean_over_devices(ex: dict, fn) -> float:
    vals = [fn(dev) for dev in ex["devices"].values()]
    return sum(vals) / len(vals) if vals else 0.0
