"""SPMD tests that need >1 device: run in subprocesses that set
XLA_FLAGS=--xla_force_host_platform_device_count BEFORE importing jax
(the main test process must keep the real single-device view)."""
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_moe_shard_map_matches_pure_path():
    """Manual-EP shard_map MoE == single-device pure path, bit-for-bit-ish."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, Mesh
from repro.configs import get_smoke_config, SHAPES
from repro.models.moe import moe_init, moe_forward
from repro.parallel import make_plan, activate

cfg = get_smoke_config('phi3.5-moe-42b-a6.6b')   # 4 experts
p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))

y_pure, aux_pure = moe_forward(p, x, cfg)        # no active plan

mesh = jax.make_mesh((2, 4), ('data', 'model'), axis_types=(AxisType.Auto,) * 2)  # 4 experts over 4 shards
plan = make_plan(mesh, cfg, SHAPES['train_4k'])
assert plan.rules['experts'] == 'model'
plan.rules['seq'] = None    # psum path: exact group-dispatch equality
with mesh, activate(plan):
    y_ep, aux_ep = jax.jit(lambda p, x: moe_forward(p, x, cfg))(p, x)
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_pure), rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(float(aux_ep), float(aux_pure), rtol=1e-5)
print('EP psum OK')

# all-to-all path (sequence-sharded tokens): exact when capacity is
# loose enough that the per-slice dispatch drops nothing
import dataclasses as dc
cfg_nd = dc.replace(cfg, moe=dc.replace(cfg.moe, capacity_factor=8.0))
y_pure_nd, _ = moe_forward(p, x, cfg_nd)
plan_a2a = make_plan(mesh, cfg_nd, SHAPES['train_4k'])
plan_a2a.rules['seq'] = 'model'
with mesh, activate(plan_a2a):
    y_a2a, _ = jax.jit(lambda p, x: moe_forward(p, x, cfg_nd))(p, x)
np.testing.assert_allclose(np.asarray(y_a2a), np.asarray(y_pure_nd), rtol=2e-4, atol=2e-4)
print('EP all-to-all OK')

cfg2 = get_smoke_config('qwen2-moe-a2.7b')       # 6 experts, ff sharded
p2 = moe_init(jax.random.PRNGKey(2), cfg2, jnp.float32)
x2 = jax.random.normal(jax.random.PRNGKey(3), (4, 16, cfg2.d_model))
y2_pure, _ = moe_forward(p2, x2, cfg2)
mesh2 = jax.make_mesh((2, 4), ('data', 'model'), axis_types=(AxisType.Auto,) * 2)  # 6 % 4 != 0 -> ff path
plan2 = make_plan(mesh2, cfg2, SHAPES['train_4k'])
assert plan2.rules['experts'] is None and plan2.rules['ff'] == 'model'
with mesh2, activate(plan2):
    y2_ep, _ = jax.jit(lambda p, x: moe_forward(p, x, cfg2))(p2, x2)
np.testing.assert_allclose(np.asarray(y2_ep), np.asarray(y2_pure), rtol=2e-4, atol=2e-4)
print('TP-in-expert OK')
""")


def test_sharded_train_step_matches_single_device():
    """One jitted train step on a 2x2 mesh == the unsharded step."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.configs import get_smoke_config, SHAPES
from repro.models import build_model
from repro.train import AdamW, TrainPlan, make_train_step
from repro.parallel import make_plan, activate, param_specs, data_specs
from repro.train.optimizer import opt_state_specs
from repro.data import SyntheticLM

cfg = get_smoke_config('granite-3-2b')
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
opt = AdamW(lr=1e-2)
state = opt.init(params)
data = SyntheticLM(cfg, batch=8, seq=32)
batch = data(0)
step = make_train_step(model, opt, TrainPlan())
p_ref, s_ref, m_ref = jax.jit(step)(params, state, batch)

mesh = jax.make_mesh((2, 2), ('data', 'model'), axis_types=(AxisType.Auto,) * 2)
plan = make_plan(mesh, cfg, SHAPES['train_4k'])
ps = param_specs(plan, params)
os_ = opt_state_specs(plan, params, state)
bs = data_specs(plan, batch)
with mesh, activate(plan):
    jit_step = jax.jit(step, in_shardings=(ps, os_, bs))
    p_sh, s_sh, m_sh = jit_step(jax.device_put(params, ps),
                                jax.device_put(state, os_),
                                jax.device_put(batch, bs))
np.testing.assert_allclose(float(m_sh['loss']), float(m_ref['loss']), rtol=1e-4)
for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)):
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=5e-3, atol=5e-3)
print('SPMD train step OK')
""")


def test_mini_dryrun_lowers_and_compiles():
    """A miniature production mesh (2x2x2 pod/data/model) lowers+compiles
    train, prefill and decode for a smoke arch — the multi-pod pattern."""
    _run("""
import jax, numpy as np
from jax.sharding import AxisType
from repro.configs import get_smoke_config, SHAPES, ShapeSpec
from repro.launch.specs import build_step, lower_step

mesh = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'),
                     axis_types=(AxisType.Auto,) * 3)
cfg = get_smoke_config('granite-3-2b')
for name, kind, seq, gb in [('train', 'train', 64, 8),
                            ('prefill', 'prefill', 64, 4),
                            ('decode', 'decode', 64, 8)]:
    shape = ShapeSpec(name, kind, seq, gb)
    bundle = build_step(cfg, shape, mesh)
    compiled = lower_step(bundle, mesh).compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes >= 0
    print(name, 'ok', compiled.cost_analysis().get('flops'))
""")


def test_elastic_restart_reshards_checkpoint():
    """Save on a 2x2 mesh, lose half the fleet, restore onto 1x2 mesh."""
    _run("""
import jax, numpy as np, tempfile, os
from jax.sharding import AxisType
from repro.configs import get_smoke_config, SHAPES
from repro.models import build_model
from repro.parallel import make_plan, param_specs
from repro.ckpt import CheckpointManager, ElasticReMesher

cfg = get_smoke_config('qwen3-0.6b')
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))

mesh = jax.make_mesh((2, 2), ('data', 'model'), axis_types=(AxisType.Auto,) * 2)
plan = make_plan(mesh, cfg, SHAPES['train_4k'])
sharded = jax.device_put(params, param_specs(plan, params))

with tempfile.TemporaryDirectory() as d:
    mgr = CheckpointManager(d)
    mgr.save(7, sharded, blocking=True)
    # "lose" 2 devices: remesh to (1,2)
    rm = ElasticReMesher(model_size=2, chips_per_host=2)
    res = rm.replan([0])   # one surviving host of 2 chips
    assert res.data_size == 1 and res.model_size == 2
    import numpy as onp
    new_mesh = jax.sharding.Mesh(onp.asarray(jax.devices()[:2]).reshape(1, 2), ('data', 'model'))
    new_plan = make_plan(new_mesh, cfg, SHAPES['train_4k'])
    step, restored = mgr.restore_latest(params, param_specs(new_plan, params))
    assert step == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print('elastic restore OK')
""")
