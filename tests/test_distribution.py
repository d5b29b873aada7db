"""Distribution tests on 8 placeholder devices.

These run in SUBPROCESSES because XLA_FLAGS device-count must be set before
jax initializes, and the assignment forbids setting it globally for the test
session (smoke tests must see 1 device).
"""

import os
import subprocess
import sys


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, timeout=900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_sharded_train_step_compiles_and_runs():
    """Smoke config, 2x4 (data, model) mesh: the full sharded train step
    (FQT + SP + sdpa hint) compiles AND executes with finite loss."""
    out = run_sub("""
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.core import QuantPolicy
from repro.engine import (abstract_train_state, init_train_state,
                          jit_step, make_step_fn)
from repro.models import build_model
from repro.optim import sgd
from repro.sharding import make_plan
from repro.launch.mesh import make_test_mesh
from repro.data import make_batch_for

mesh = make_test_mesh(2, 4)
plan = make_plan(mesh)
cfg = get_config("granite-3-2b", smoke=True)
model = build_model(cfg)
pol = QuantPolicy.fqt("bhq", 5, bhq_block=16)
opt = sgd(0.9)
state = init_train_state(model, opt, seed=0)
batch = make_batch_for(cfg, 4, 16)
astate = abstract_train_state(model, opt)
step = make_step_fn(model, pol, opt, lambda s: 1e-3, remat=True,
                    loss_kwargs={"sdpa_hint": plan.attn_shardings})
with mesh:
    jf = jit_step(step, plan=plan, abstract_state=astate)
    state2, mets = jf(state, batch)
assert bool(jnp.isfinite(mets["loss"])), mets
assert int(state2.step) == 1
assert jax.tree.leaves(state2.params)[0].sharding.mesh == mesh
print("LOSS", float(mets["loss"]))
""")
    assert "LOSS" in out


def test_compressed_allreduce_unbiased_int8_wire():
    out = run_sub("""
import jax, jax.numpy as jnp, re
from jax.sharding import PartitionSpec as P
from repro.core.compression import compressed_psum
mesh = jax.make_mesh((8,), ("pod",),
                     axis_types=(jax.sharding.AxisType.Auto,))
gw = jax.random.normal(jax.random.PRNGKey(0), (8, 33, 7))
def run(gl, key):
    return compressed_psum(gl[0], key[0], "pod", bits=8)[None] / 8
f = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(P("pod"), P("pod")),
                          out_specs=P("pod"), check_vma=False))
ks = jax.random.split(jax.random.PRNGKey(2), 8)
out = f(gw, ks)
exact = jnp.mean(gw, axis=0)
rel = float(jnp.max(jnp.abs(out - exact[None])) / jnp.max(jnp.abs(exact)))
assert rel < 0.05, rel
outs = [f(gw, jax.random.split(jax.random.PRNGKey(100+s), 8))[0] for s in range(48)]
m = jnp.mean(jnp.stack(outs), 0)
bias = float(jnp.max(jnp.abs(m - exact)))
sem = float(jnp.max(jnp.std(jnp.stack(outs), 0))) / (48 ** 0.5)
assert bias < 6 * sem + 1e-3, (bias, sem)
hlo = f.lower(gw, ks).compile().as_text()
assert re.search(r"= s8.*all-gather", hlo), "int8 must be on the wire"
print("OK rel", rel)
""")
    assert "OK" in out


def test_plan_divisibility_all_archs():
    """Every full-config param shards evenly on a model=4 mesh axis; specs
    never request non-divisible sharding."""
    out = run_sub("""
import jax, jax.numpy as jnp
from repro.configs import ARCH_NAMES, get_config
from repro.models import build_model
from repro.sharding import make_plan
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh(2, 4)
plan = make_plan(mesh)
for arch in ARCH_NAMES:
    cfg = get_config(arch)                  # FULL configs
    model = build_model(cfg)
    ap = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    specs = plan.param_specs(ap)
    flat_p = jax.tree_util.tree_leaves_with_path(ap)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "index"))
    import jax.sharding as shd
    for (path, leaf), spec in zip(flat_p, flat_s, strict=True):
        for dim, ax in zip(leaf.shape, tuple(spec), strict=False):
            if ax is not None:
                size = mesh.shape[ax] if isinstance(ax, str) else 1
                assert dim % size == 0, (arch, path, leaf.shape, spec)
print("DIVISIBLE")
""")
    assert "DIVISIBLE" in out


def test_elastic_restore_across_meshes(tmp_path):
    """Save sharded state on a 2x4 mesh, restore onto 4x2 and 8x1 — elastic."""
    out = run_sub(f"""
import jax, jax.numpy as jnp, numpy as np
from repro.checkpoint import CheckpointManager
from repro.sharding import make_plan
from repro.launch.mesh import make_test_mesh
from jax.sharding import NamedSharding, PartitionSpec as P

tree = {{"w": jax.random.normal(jax.random.PRNGKey(0), (8, 16))}}
mesh_a = make_test_mesh(2, 4)
sh_a = NamedSharding(mesh_a, P("data", "model"))
placed = jax.device_put(tree["w"], sh_a)
ckpt = CheckpointManager("{tmp_path}")
ckpt.save(1, {{"w": placed}})
for shape in [(4, 2), (8, 1), (1, 8)]:
    mesh_b = make_test_mesh(*shape)
    sh_b = NamedSharding(mesh_b, P("data", "model"))
    out = ckpt.restore(1, {{"w": jax.ShapeDtypeStruct((8, 16), jnp.float32)}},
                       shardings={{"w": sh_b}})
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
    assert out["w"].sharding == sh_b
print("ELASTIC")
""")
    assert "ELASTIC" in out


def test_production_mesh_shapes():
    """make_production_mesh on 512 fake devices (separate process)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", """
from repro.launch.mesh import make_production_mesh
m1 = make_production_mesh()
assert dict(m1.shape) == {"data": 16, "model": 16}, m1.shape
m2 = make_production_mesh(multi_pod=True)
assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}, m2.shape
print("MESH OK")
"""], env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH OK" in out.stdout
