"""shard_map MoE ≡ dense MoE (dropless), on trivial and 2×2 meshes."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.registry import reduced_config
from repro.models.moe import moe_apply, moe_init
from repro.models.moe_shard_map import moe_apply_shard_map

cfg = dataclasses.replace(reduced_config("qwen3-moe-235b-a22b"), capacity_factor=8.0)
key = jax.random.PRNGKey(0)
p = moe_init(key, cfg, jnp.float32)
x = jax.random.normal(key, (2, 16, cfg.d_model))
y_d, aux_d = moe_apply(p, x, cfg)
rules = {"batch": ("data",), "seq_res": None}

AUTO = (jax.sharding.AxisType.Auto,) * 2
mesh1 = jax.make_mesh((1, 1), ("data", "model"), axis_types=AUTO)
with mesh1:
    y1, a1 = jax.jit(lambda p_, x_: moe_apply_shard_map(p_, x_, cfg, mesh1, rules))(p, x)
assert float(jnp.abs(y1 - y_d).max()) < 1e-5, "1x1 mismatch"
assert abs(float(a1) - float(aux_d)) < 1e-5

mesh = jax.make_mesh((2, 2), ("data", "model"), axis_types=AUTO)
xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
with mesh:
    y2, a2 = jax.jit(lambda p_, x_: moe_apply_shard_map(p_, x_, cfg, mesh, rules))(p, xs)
assert float(jnp.abs(y2 - y_d).max()) < 1e-5, "2x2 mismatch"

# gradients flow through the all_to_all exchange
g = jax.grad(lambda p_: jnp.sum(jnp.tanh(
    moe_apply_shard_map(p_, xs, cfg, mesh, rules)[0])))(p)
import numpy as np
with mesh:
    pass
for leaf in jax.tree.leaves(g):
    assert bool(jnp.isfinite(leaf).all()), "NaN grads through shard_map MoE"
print("SHARD_MAP_MOE_OK")
"""


def test_shard_map_moe_subprocess():
    """Needs 4 host devices → subprocess (XLA_FLAGS before jax init)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=480,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHARD_MAP_MOE_OK" in out.stdout
