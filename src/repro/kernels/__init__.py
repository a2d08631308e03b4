"""Pallas kernels: the Eq.-7 pool sweep (``pool_mlp``) and the model zoo's
``flash_attention``, ``mlstm`` and ``rg_lru``."""
from __future__ import annotations

import jax


def resolve_interpret(interpret=None) -> bool:
    """Whether a ``pallas_call`` runs in interpret mode.

    ``None`` resolves from the platform: the compiled kernel on any
    accelerator, the interpreter only on the CPU (where the tests run).
    There is no override: a kernel that does not lower on an accelerator
    raises there instead of quietly running the interpreter."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
