"""Expert-parallel shard_map MoE vs single-device reference.

Needs >1 device, so the actual check runs in a subprocess with
``--xla_force_host_platform_device_count=8`` (the test process itself must
stay single-device per the dry-run contract)."""

import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.core.aebs import ReplicaLayout, aebs_assign
from repro.models import moe as moe_mod
from repro.models.moe_ep import moe_layer_ep

mesh = jax.make_mesh((2, 4), ("data", "model"))
cfg = get_config("qwen2-moe-a2.7b-reduced")  # 4 experts top-2
params = moe_mod.init_moe(cfg, jax.random.PRNGKey(0), jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model), jnp.float32) * 0.3

# reference: single-device einsum dispatch, ample capacity
y_ref = moe_mod.moe_layer(params, x, cfg, capacity=64)

with jax.set_mesh(mesh):
    # logical EP mode (training path) — scatter and grouped bodies
    for disp in ("scatter", "grouped"):
        y_ep = jax.jit(lambda p, xx: moe_layer_ep(
            p, xx, cfg, mesh=mesh, dp_axes=("data",), model_axis="model",
            mode="logical", dispatch=disp, capacity_factor=8.0))(params, x)
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ep), atol=2e-4, rtol=2e-3)

    # scheduled EP mode (serving path): slots divisible by model axis
    layout = ReplicaLayout.round_robin(cfg.num_experts, 4, 2)
    stx = jnp.asarray(layout.slot_to_expert.reshape(-1))
    for disp in ("scatter", "grouped"):
        y_sched = jax.jit(lambda p, xx: moe_layer_ep(
            p, xx, cfg, mesh=mesh, dp_axes=("data",), model_axis="model",
            mode="scheduled", dispatch=disp, scheduler=aebs_assign,
            layout_tables=layout.device_tables(), slot_to_expert=stx,
            num_instances=4, capacity_factor=8.0))(params, x)
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_sched), atol=2e-4, rtol=2e-3)

    # scheduled + grouped with weights pinned at deployment (identity map)
    pinned = dict(params)
    for n in ("w_gate", "w_up", "w_down"):
        pinned[n] = params[n][jnp.maximum(stx, 0)]
    y_pin = jax.jit(lambda p, xx: moe_layer_ep(
        p, xx, cfg, mesh=mesh, dp_axes=("data",), model_axis="model",
        mode="scheduled", dispatch="grouped", scheduler=aebs_assign,
        layout_tables=layout.device_tables(), slot_to_expert=stx,
        num_instances=4, capacity_factor=8.0))(pinned, x)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pin), atol=2e-4, rtol=2e-3)

    # gradients flow through the EP path
    def loss(p):
        return jnp.sum(moe_layer_ep(
            p, x, cfg, mesh=mesh, dp_axes=("data",), model_axis="model",
            mode="logical", capacity_factor=8.0) ** 2)
    g = jax.grad(loss)(params)
    gn = sum(float(jnp.abs(v).sum()) for v in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0
print("EP_OK")
"""


@pytest.mark.subprocess
def test_ep_matches_reference_subprocess():
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True,
        text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": "/root"},
        cwd="/root/repo",
        timeout=600,
    )
    assert "EP_OK" in r.stdout, r.stdout + "\n" + r.stderr
