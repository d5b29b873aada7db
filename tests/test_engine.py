"""Engine tests: TrainState lifecycle, exact resume (data + rng streams),
gradient accumulation, and the sharded/donated step on fake-device meshes.

Mesh tests run in SUBPROCESSES because XLA_FLAGS device-count must be set
before jax initializes (same convention as tests/test_distribution.py).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import QuantPolicy
from repro.data import make_batch_for
from repro.engine import Engine, TrainState, split_microbatches

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 4, timeout=900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


# ---------------------------------------------------------------------------
# single-device: state, microbatching, resume
# ---------------------------------------------------------------------------

def test_split_microbatches():
    batch = {"tokens": jnp.zeros((8, 16), jnp.int32),
             "labels": jnp.zeros((8, 16), jnp.int32),
             "positions": jnp.zeros((3, 8, 16), jnp.int32)}  # vlm m-rope
    micro = split_microbatches(batch, 4)
    assert micro["tokens"].shape == (4, 2, 16)
    assert micro["positions"].shape == (4, 3, 2, 16)
    with pytest.raises(ValueError, match="not divisible"):
        split_microbatches({"tokens": jnp.zeros((6, 4))}, 4)


def test_train_state_checkpoint_roundtrip(tmp_path):
    """TrainState's dict form round-trips through CheckpointManager with
    step and rng intact (the fields exact resume depends on)."""
    from repro.checkpoint import CheckpointManager
    state = TrainState(params={"w": jnp.ones((4, 2))},
                       opt_state={"mu": {"w": jnp.zeros((4, 2))}},
                       step=jnp.asarray(7, jnp.int32),
                       rng=jax.random.PRNGKey(3))
    ckpt = CheckpointManager(str(tmp_path))
    ckpt.save(7, state.as_dict())
    out = TrainState.from_dict(
        ckpt.restore(7, jax.tree.map(np.asarray, state.as_dict())))
    assert int(out.step) == 7
    np.testing.assert_array_equal(np.asarray(out.rng), np.asarray(state.rng))
    np.testing.assert_array_equal(np.asarray(out.params["w"]),
                                  np.asarray(state.params["w"]))


def _engine(batch_fn=None, **kw):
    cfg = get_config("statquant-tx", smoke=True)
    pol = QuantPolicy.fqt("bhq", 5, bhq_block=16)
    args = dict(steps=6, batch_size=4, seq_len=16, log_every=100,
                log_fn=None, batch_fn=batch_fn)
    args.update(kw)
    return Engine(cfg, pol, **args)


def test_compiled_pallas_on_a_mesh_is_refused_up_front():
    """Mosaic kernels are not partitioned by GSPMD: a compiled-Pallas
    policy on a mesh must fail at construction with a message naming the
    backends that do train sharded, not deep inside lowering."""
    from repro.launch.mesh import make_test_mesh
    pol = QuantPolicy.fqt("bhq", 5, bhq_block=16, backend="pallas",
                          pallas_interpret=False)
    with pytest.raises(ValueError, match="cannot train on a mesh"):
        Engine(get_config("statquant-tx", smoke=True), pol,
               mesh=make_test_mesh(1, 1), steps=1, batch_size=2,
               seq_len=8, log_fn=None)


def _recording_batch_fn(log):
    cfg = get_config("statquant-tx", smoke=True)

    def fn(step):
        log.append(step)
        return make_batch_for(cfg, 4, 16, step=step, seed=0)
    return fn


def test_resume_is_bit_identical_and_data_continuous(tmp_path):
    """run-6-steps == run-3-save + restore-run-3, bit for bit.

    Covers both resume bugs at once: the rng stream lives in TrainState (so
    SR draws replay identically) and the loader position is restored from
    the checkpointed step (so the stream continues at batch 3, not batch 0).
    """
    rec_a, rec_b1, rec_b2 = [], [], []
    full = _engine(_recording_batch_fn(rec_a)).run()

    e1 = _engine(_recording_batch_fn(rec_b1),
                 ckpt_dir=str(tmp_path), ckpt_every=3)
    h1 = e1.run(steps=3)
    e2 = _engine(_recording_batch_fn(rec_b2),
                 ckpt_dir=str(tmp_path), ckpt_every=100)
    h2 = e2.run()

    assert full == h1 + h2          # losses bit-identical, steps contiguous
    assert [s for s, _ in h1 + h2] == list(range(6))
    # loader position: the resumed engine never re-reads batches 0..2
    # (prefetch may read ahead past the end, so assert the prefix + floor)
    assert rec_a[:6] == list(range(6))
    assert rec_b1[:3] == [0, 1, 2]
    assert rec_b2[:3] == [3, 4, 5]
    assert min(rec_b2) == 3
    assert int(e2.state.step) == 6


def test_accumulation_matches_full_batch_exact_policy():
    """accum=2 vs accum=1 under the exact policy: same data, no quantization
    noise, so the mean-of-microbatch gradients equal the full-batch gradient
    up to fp32 reduction order — losses track within tolerance."""
    cfg = get_config("statquant-tx", smoke=True)
    kw = dict(steps=3, batch_size=8, seq_len=16, log_every=100, log_fn=None)
    h1 = Engine(cfg, QuantPolicy.exact(), accum_steps=1, **kw).run()
    h2 = Engine(cfg, QuantPolicy.exact(), accum_steps=2, **kw).run()
    np.testing.assert_allclose([l for _, l in h1], [l for _, l in h2],
                               rtol=1e-4, atol=1e-4)


def test_engine_rejects_bad_accum():
    with pytest.raises(ValueError, match="not divisible"):
        _engine(accum_steps=5)


def test_legacy_checkpoint_migrates(tmp_path):
    """A pre-engine checkpoint ({params, opt} only) resumes: step comes from
    the checkpoint index, the rng stream restarts instead of KeyError-ing."""
    from repro.checkpoint import CheckpointManager
    e = _engine()
    st = e.init_state()
    CheckpointManager(str(tmp_path)).save(
        2, {"params": st.params, "opt": st.opt_state})
    msgs = []
    e2 = _engine(ckpt_dir=str(tmp_path), log_fn=msgs.append)
    h = e2.run(steps=4)
    assert [s for s, _ in h] == [2, 3]
    assert int(e2.state.step) == 4
    assert any("legacy checkpoint" in m for m in msgs)


def test_straggler_probe_flags_slow_host():
    """With an injected fleet-times probe (what scheduler heartbeats supply
    on a real cluster), a persistently slow host is flagged and logged."""
    from repro.runtime import StragglerMonitor
    msgs = []
    eng = _engine(straggler=StragglerMonitor(n_hosts=4, patience=2),
                  straggler_probe=lambda dt: [dt, dt, dt, dt * 10],
                  log_fn=msgs.append)
    eng.run(steps=3)
    assert eng.straggler.stragglers() == [3]
    assert any("stragglers: [3]" in m for m in msgs)


def test_run_spans_reach_a_profiler_capture(tmp_path):
    """Two steps under the profiler: each step's batch wait and dispatch,
    the drains, the checkpoint save and wait, and the prefetcher's start
    and stop are host spans of the capture, named ``repro.engine.*``."""
    import collections
    import glob

    from jax.profiler import ProfileData
    eng = _engine(ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=2)
    eng.run(steps=2)                      # compiles outside the capture
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        eng.run(steps=4)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    spans = collections.Counter(
        ev.name for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:") for line in plane.lines
        for ev in line.events if ev.name.startswith("repro.engine."))
    # drains: the last step's log line, the save at step 3, and the end
    assert spans == {"repro.engine.batch": 2, "repro.engine.dispatch": 2,
                     "repro.engine.drain": 3, "repro.engine.checkpoint": 2,
                     "repro.engine.prefetch": 2}


# ---------------------------------------------------------------------------
# fake-device meshes (subprocesses)
# ---------------------------------------------------------------------------

_PARITY_CODE = r"""
import numpy as np
from repro.configs import get_config
from repro.core import QuantPolicy
from repro.engine import Engine
from repro.launch.mesh import make_test_mesh

cfg = get_config("statquant-tx", smoke=True)
for backend in BACKENDS:
    pol = QuantPolicy.fqt("bhq", 5, bhq_block=16, backend=backend, overrides={
        r"lm_head": "exact",
        r"layers\.attn\.": 8,
        r"layers\.mlp\.": {"agrad": ("bhq", 4)},
    })
    kw = dict(steps=3, batch_size=8, seq_len=16, accum_steps=2,
              log_every=1, log_fn=None)
    h_mesh = Engine(cfg, pol, mesh=make_test_mesh(2, 2), **kw).run()
    h_flat = Engine(cfg, pol, **kw).run()
    assert len(h_mesh) == 3
    # step 0 sees identical params + identical SR draws: pure GSPMD
    # reduction-order noise.  Later steps amplify it through discrete SR
    # boundary flips, so the trajectory tolerance is looser.
    np.testing.assert_allclose(h_mesh[0][1], h_flat[0][1], rtol=1e-4,
                               err_msg=backend)
    np.testing.assert_allclose([l for _, l in h_mesh],
                               [l for _, l in h_flat], rtol=2e-3, atol=2e-3,
                               err_msg=backend)
    print("PARITY", backend, [round(l, 4) for _, l in h_mesh])
"""


def test_sharded_accum_matches_unsharded_simulate():
    """Acceptance: a heterogeneous-policy LM trains 3 steps through
    Engine.run() on a 2x2 mesh with accum=2, loss within fp32 tolerance of
    the unsharded run (same microbatching, so identical SR draws)."""
    out = run_sub('BACKENDS = ("simulate",)\n' + _PARITY_CODE)
    assert "PARITY simulate" in out


@pytest.mark.slow
def test_sharded_accum_matches_unsharded_native_pallas():
    """Same acceptance check on the native int8 and (interpreted) Pallas
    backends — exhaustive sweep, excluded from tier-1."""
    out = run_sub('BACKENDS = ("native", "pallas")\n' + _PARITY_CODE,
                  timeout=1800)
    assert "PARITY native" in out and "PARITY pallas" in out


def test_plan_divisibility_fallback_tiny_mesh():
    """Every config resolves a full TrainState sharding plan on a mesh whose
    model axis (3) divides almost nothing — the fallback must replicate
    instead of erroring — and smoke states actually place on it."""
    out = run_sub(r"""
import jax
from repro.configs import ALL_NAMES, get_config
from repro.engine import (abstract_train_state, init_train_state,
                          state_shardings, state_specs)
from repro.models import build_model
from repro.optim import sgd
from repro.sharding import make_plan
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh(2, 3)
plan = make_plan(mesh)
opt = sgd(0.9)
for arch in ALL_NAMES:
    cfg = get_config(arch)                    # FULL configs
    model = build_model(cfg)
    astate = abstract_train_state(model, opt)
    specs = state_specs(plan, astate)
    flat_p = jax.tree_util.tree_leaves_with_path(astate)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "index"))
    assert len(flat_p) == len(flat_s)
    for (path, leaf), spec in zip(flat_p, flat_s, strict=True):
        for dim, ax in zip(leaf.shape, tuple(spec), strict=False):
            if ax is not None:
                assert dim % mesh.shape[ax] == 0, (arch, path, leaf.shape,
                                                   spec)
# actual placement (uneven sharding would raise at device_put)
for arch in ("statquant-tx", "granite-moe-1b-a400m", "rwkv6-1.6b"):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    st = init_train_state(model, opt, seed=0)
    sh = state_shardings(plan, abstract_train_state(model, opt))
    placed = jax.device_put(st, sh)
    jax.block_until_ready(placed.params)
print("FALLBACK OK")
""", devices=6)
    assert "FALLBACK OK" in out


def test_engine_compressed_allreduce_runs():
    """The beyond-paper int8 compressed DP all-reduce composes with the
    engine step (shard_map inside the jitted, donated, accumulated step)."""
    out = run_sub("""
import math
from repro.configs import get_config
from repro.core import QuantPolicy
from repro.engine import Engine
from repro.launch.mesh import make_test_mesh

cfg = get_config("statquant-tx", smoke=True)
pol = QuantPolicy.fqt("bhq", 5, bhq_block=16)
eng = Engine(cfg, pol, steps=2, batch_size=8, seq_len=16, accum_steps=2,
             mesh=make_test_mesh(2, 2), compress_axis="data", log_fn=None)
h = eng.run()
assert len(h) == 2 and all(math.isfinite(l) for _, l in h), h
print("COMPRESSED OK", [round(l, 4) for _, l in h])
""")
    assert "COMPRESSED OK" in out


def test_elastic_resume_across_mesh_shapes(tmp_path):
    """Engine checkpoints on a 2x2 mesh; a second engine on a 4x1 mesh
    restores the same TrainState (CheckpointManager reshards on device_put)
    and continues training."""
    out = run_sub(f"""
import jax, math
from repro.configs import get_config
from repro.core import QuantPolicy
from repro.engine import Engine
from repro.launch.mesh import make_test_mesh

cfg = get_config("statquant-tx", smoke=True)
pol = QuantPolicy.fqt("bhq", 5, bhq_block=16)
kw = dict(steps=3, batch_size=8, seq_len=16, accum_steps=2, log_fn=None,
          ckpt_dir="{tmp_path}", ckpt_every=2)
e1 = Engine(cfg, pol, mesh=make_test_mesh(2, 2), **kw)
h1 = e1.run(steps=2)
e2 = Engine(cfg, pol, mesh=make_test_mesh(4, 1), **kw)
h2 = e2.run()
assert [s for s, _ in h2] == [2], h2
assert int(e2.state.step) == 3
assert jax.tree.leaves(e2.state.params)[0].sharding.mesh == e2.mesh
assert all(math.isfinite(l) for _, l in h1 + h2)
print("ELASTIC ENGINE OK")
""")
    assert "ELASTIC ENGINE OK" in out


@pytest.mark.slow
def test_cli_engine_smoke_4dev_mesh():
    """The CI smoke job, as a test: the training CLI runs the engine 3 steps
    on a 2x2 fake-CPU mesh with accumulation."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--steps", "3",
         "--batch", "8", "--seq", "16", "--mesh", "2x2", "--accum", "2"],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "step     2" in out.stdout
