"""Multi-device semantics via a subprocess with 8 forced host devices
(XLA_FLAGS must be set before jax import, so these run out of process).

Covers: sharded train step numerics == single-device, elastic restore onto
a smaller mesh, and the int8 compressed_psum collective under shard_map.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import AxisType
AUTO = AxisType.Auto
from functools import partial

assert len(jax.devices()) == 8

from repro.configs import get_config
from repro.models import get_model, make_concrete_batch
from repro.optim import OptConfig, init_train_state, make_train_step
from repro.distributed.sharding import param_shardings
from repro.distributed.ft import elastic_mesh
from repro.checkpoint import save, restore
from repro.distributed.compression import compressed_psum

# ---- 1) sharded train step == single-device train step ----
cfg = get_config("smollm-135m").reduced()
model = get_model(cfg)
ocfg = OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
step = make_train_step(model, ocfg)
params = model.init(jax.random.PRNGKey(0))
state = init_train_state(params, ocfg)
batch = make_concrete_batch(cfg, 4, 32, jax.random.PRNGKey(1))

ref_state, ref_metrics = jax.jit(step)(jax.tree.map(jnp.copy, state), batch)

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AUTO, AUTO))
psh = param_shardings(mesh, params, cfg.tie_embeddings)
state_sh = {"params": psh, "m": psh, "v": psh,
            "step": NamedSharding(mesh, P())}
batch_sh = {k: NamedSharding(mesh, P("data", None)) for k in batch}
st = jax.device_put(state, state_sh)
bt = jax.device_put(batch, batch_sh)
out_state, metrics = jax.jit(step, in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None))(st, bt)
np.testing.assert_allclose(float(metrics["loss"]), float(ref_metrics["loss"]),
                           rtol=1e-4)
for a, b in zip(jax.tree.leaves(ref_state["params"]),
                jax.tree.leaves(out_state["params"])):
    np.testing.assert_allclose(np.asarray(a), np.asarray(jax.device_get(b)),
                               rtol=2e-4, atol=2e-4)
print("OK sharded==single")

# ---- 2) elastic restore onto a smaller mesh ----
import tempfile
with tempfile.TemporaryDirectory() as d:
    save(jax.device_get(out_state), d, step=1)
    small = elastic_mesh(model_dim=2, devices=jax.devices()[:4])
    psh2 = param_shardings(small, params, cfg.tie_embeddings)
    sh2 = {"params": psh2, "m": psh2, "v": psh2,
           "step": NamedSharding(small, P())}
    abs_state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    restored = restore(d, abs_state, shardings=sh2)
    for a, b in zip(jax.tree.leaves(out_state["params"]),
                    jax.tree.leaves(restored["params"])):
        np.testing.assert_allclose(np.asarray(jax.device_get(a)),
                                   np.asarray(jax.device_get(b)), rtol=1e-6)
print("OK elastic reshard")

# ---- 3) compressed int8 psum == float psum (within quant error) ----
mesh1d = jax.make_mesh((8,), ("data",), axis_types=(AUTO,))
x = jax.random.normal(jax.random.PRNGKey(2), (8, 4096))

@partial(jax.shard_map, mesh=mesh1d, in_specs=P("data", None), out_specs=P("data", None))
def f_comp(xl):
    return compressed_psum(xl[0], "data")[None]

@partial(jax.shard_map, mesh=mesh1d, in_specs=P("data", None), out_specs=P("data", None))
def f_exact(xl):
    return jax.lax.psum(xl[0], "data")[None]

got = np.asarray(f_comp(x))[0]
want = np.asarray(f_exact(x))[0]
scale = np.abs(x).max() / 127.0 * 8
assert np.abs(got - want).max() <= scale * 1.05, np.abs(got - want).max()
print("OK compressed_psum")

# ---- 4) MoE shard_map EP path == pure-jit dispatch path ----
import dataclasses
from repro.distributed.sharding import MeshRules, activation_rules
cfgm = dataclasses.replace(get_config("qwen3-moe-30b-a3b").reduced(),
                           moe_mode="dispatch", capacity_factor=8.0,
                           seq_parallel=True)
mm = get_model(cfgm)
mparams = mm.init(jax.random.PRNGKey(3))
mbatch = make_concrete_batch(cfgm, 4, 32, jax.random.PRNGKey(4))
ref_loss, _ = jax.jit(mm.loss)(mparams, mbatch)   # no rules -> pure-jit path

mesh2 = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AUTO, AUTO))
psh2 = param_shardings(mesh2, mparams, cfgm.tie_embeddings)
bsh2 = {k: NamedSharding(mesh2, P("data", *([None] * (v.ndim - 1))))
        for k, v in mbatch.items()}
rules = MeshRules(mesh=mesh2, data_axes=("data",))
with activation_rules(rules):
    loss_fn = jax.jit(mm.loss, in_shardings=(psh2, bsh2))
    sm_loss, _ = loss_fn(jax.device_put(mparams, psh2),
                         jax.device_put(mbatch, bsh2))
    # gradients flow through the a2a path
    g = jax.jit(jax.grad(lambda pp, bb: mm.loss(pp, bb)[0]),
                in_shardings=(psh2, bsh2))(jax.device_put(mparams, psh2),
                                           jax.device_put(mbatch, bsh2))
gn = sum(float(jnp.sum(jnp.square(l.astype(jnp.float32)))) for l in jax.tree.leaves(g))
assert gn > 0 and np.isfinite(gn)
np.testing.assert_allclose(float(sm_loss), float(ref_loss), rtol=2e-3)
print("OK moe shard_map")
"""


@pytest.mark.slow
def test_multidevice_semantics():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout[-3000:]}\nstderr:\n{r.stderr[-3000:]}"
    for marker in ("OK sharded==single", "OK elastic reshard",
                   "OK compressed_psum", "OK moe shard_map"):
        assert marker in r.stdout
