"""Operations and bytes of the work a window did, from the shapes alone.

``eq7_flops`` / ``eq7_bytes`` are copied from ``benchmarks/roofline.py``
(``analytic_flops`` / ``analytic_bytes``): every (feature, pool row, probe
sample) triple runs the head MLP forward, 2ab + b per dense layer, plus 3
for the squared error; the bytes are the pool weights, probes and errors,
each moved once, in float32.

``mlp_matmul_flops`` counts 2ab per dense layer and nothing else: the
count that utilisation of the chip's matrix peak is taken against.
"""
from __future__ import annotations


def _layers(dims):
    return list(zip(dims[:-1], dims[1:]))


def head_params(dims) -> int:
    return sum(a * b + b for a, b in _layers(dims))


def head_forward_flops(dims) -> int:
    """One head forward on one sample, bias adds included (roofline.py)."""
    return sum(2 * a * b + b for a, b in _layers(dims))


def eq7_flops(ns: int, nf: int, R: int, dims) -> float:
    """One client's Eq.-7 sweep: ``nf`` probe features against ``ns`` rows."""
    return float(nf) * ns * R * (head_forward_flops(dims) + 3)


def eq7_bytes(ns: int, nf: int, R: int, dims) -> float:
    w = dims[0]
    return 4.0 * (ns * head_params(dims) + nf * R * w + R + nf * ns)


def mlp_matmul_flops(dims) -> int:
    return sum(2 * a * b for a, b in _layers(dims))


def forward_flops(nets: dict, nf: int) -> int:
    """Matmul FLOPs of one sample through the whole predictor: nf heads,
    the embedding and the prediction network (``nets`` from
    ``population.mlp_dims``)."""
    return (nf * mlp_matmul_flops(nets["heads"])
            + mlp_matmul_flops(nets["embed"])
            + mlp_matmul_flops(nets["pred"]))


def epoch_flops(nets_of, nfs, R: int, n_sub: int, n_exch: int,
                n_val: int) -> float:
    """Matmul FLOPs one epoch requires: every client's Adam steps (forward
    and backward, three forwards), the Eq.-7 forwards over every real pool
    row and probe at each of the ``n_exch`` exchanges, and the validation
    pass."""
    total = 0.0
    rows = sum(nfs)
    for nf in nfs:
        fwd = forward_flops(nets_of(nf), nf)
        total += 3 * fwd * R * n_sub + fwd * n_val
        total += n_exch * nf * rows * R * mlp_matmul_flops(
            nets_of(nf)["heads"])
    return total


def test_flops(nets_of, nfs, n_test: int) -> float:
    """The test pass one fit ends with."""
    return float(sum(forward_flops(nets_of(nf), nf) * n_test for nf in nfs))
