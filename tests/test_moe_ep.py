"""Expert-parallel all-to-all dispatch (models/moe_ep.py) vs the GSPMD MoE:
same outputs, flowing gradients (subprocess: needs a multi-device mesh)."""
import subprocess
import sys

import pytest

ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
       "JAX_PLATFORMS": "cpu"}  # host-platform test: skip TPU probing

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.configs import smoke_config
from repro.models import ffn, moe_ep
from repro.sharding import ctx

cfg = smoke_config("dbrx_132b")                       # 4 experts top-2
cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)  # no drops
mesh = make_mesh((2, 4), ("data", "model"), jax.devices())
ctx.set_mesh(mesh)
assert moe_ep.applicable(cfg, mesh)

p = ffn.init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), "float32")

ref_out, _ = ffn.moe(p, cfg, x)
got_out, got_aux = jax.jit(lambda p, x: moe_ep.moe_ep(p, cfg, x))(p, x)
np.testing.assert_allclose(np.asarray(got_out), np.asarray(ref_out),
                           rtol=3e-3, atol=3e-3)
assert np.isfinite(float(got_aux))

g = jax.jit(jax.grad(lambda p, x: moe_ep.moe_ep(p, cfg, x)[0].sum()))(p, x)
assert float(jnp.abs(g.w_gate).sum()) > 0
assert float(jnp.abs(g.router).sum()) > 0

# the HLO must contain all-to-all (the whole point)
txt = jax.jit(lambda p, x: moe_ep.moe_ep(p, cfg, x)).lower(p, x).compile().as_text()
assert "all-to-all" in txt
print("MOE_EP_OK")
"""


@pytest.mark.slow
def test_moe_ep_matches_gspmd_moe():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=600, env=ENV)
    assert "MOE_EP_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-2500:]
