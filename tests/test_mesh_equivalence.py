"""Sharded-vs-single-device numerical equivalence: the same train step on a
(2, 4) device mesh must produce the same loss as on 1 device — the end-to-end
proof that the sharding rules change WHERE the math runs, not WHAT it
computes.  Runs in a subprocess (jax locks the host device count)."""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.data.lm_pipeline import LMPipelineConfig, TokenPipeline
from repro.launch import steps

ROOT = Path(__file__).resolve().parent.parent

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding
from repro.configs import smoke_config
from repro.data.lm_pipeline import LMPipelineConfig, TokenPipeline
from repro.launch import steps
from repro.launch.dryrun import named

cfg = smoke_config("{arch}")
opt = steps.default_optimizer(1e-3)
state = steps.init_state(cfg, opt, jax.random.PRNGKey(0))
pipe = TokenPipeline(LMPipelineConfig(batch=8, seq_len=32,
                                      vocab_size=cfg.vocab_size,
                                      n_patches=8), cfg)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with mesh:
    st_specs = named(steps.state_pspecs(cfg, opt, mesh), mesh)
    from repro.configs.base import INPUT_SHAPES, InputShape
    shp = InputShape("t", 32, 8, "train")
    b_specs = named(steps.batch_pspecs(cfg, shp, mesh), mesh)
    ts = jax.jit(steps.make_train_step(cfg, opt, dtype=jnp.float32),
                 in_shardings=(st_specs, b_specs),
                 out_shardings=(st_specs, None))
    losses = []
    for step in range(3):
        batch = {{k: jnp.asarray(v) for k, v in pipe.batch_at(step).items()}}
        state, m = ts(state, batch)
        losses.append(float(m["loss"]))
print("LOSSES", losses)
"""


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "olmoe-1b-7b"])
def test_sharded_equals_single_device(arch):
    # single-device reference
    cfg = smoke_config(arch)
    opt = steps.default_optimizer(1e-3)
    state = steps.init_state(cfg, opt, jax.random.PRNGKey(0))
    pipe = TokenPipeline(LMPipelineConfig(batch=8, seq_len=32,
                                          vocab_size=cfg.vocab_size,
                                          n_patches=8), cfg)
    ts = jax.jit(steps.make_train_step(cfg, opt, dtype=jnp.float32))
    ref = []
    for step in range(3):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(step).items()}
        state, m = ts(state, batch)
        ref.append(float(m["loss"]))

    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT.format(arch=arch)],
        env={"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=560)
    assert "LOSSES" in proc.stdout, proc.stdout + proc.stderr[-2000:]
    got = eval(proc.stdout.split("LOSSES", 1)[1].strip())
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
