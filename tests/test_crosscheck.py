"""Cross-checks between the analytic traffic model and compiled HLO.

The mesh planner scores placements with commgraph's ANALYTIC collective
bytes; the roofline uses bytes PARSED from compiled HLO. These tests pin
the two views together on a small SPMD program (subprocess — needs >1
device), and sanity-check the dry-run artifacts if present.
"""
import glob
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
DRYRUN = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_parsed_allreduce_matches_ring_formula():
    """One explicit psum: parsed wire bytes == 2(k-1)/k * payload."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.hlo_parse import analyze, wire_bytes

mesh = jax.make_mesh((8,), ('model',))
def f(x):
    return jax.lax.psum(x, 'model')
fn = jax.shard_map(f, mesh=mesh, in_specs=P(None, None), out_specs=P(None, None), check_vma=False)
x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
compiled = jax.jit(fn).lower(x).compile()
s = analyze(compiled.as_text())
payload = 64 * 128 * 4
got = wire_bytes(s)
want = 2 * 7 / 8 * payload
np.testing.assert_allclose(got, want, rtol=1e-6)
print('psum wire bytes OK')
""")


def test_planned_mesh_compiles():
    """make_planned_mesh: the paper-mapped device order builds a valid
    Mesh and a step compiles on it (the device permutation is sound)."""
    _run("""
import jax
from repro.configs import get_smoke_config, ShapeSpec
from repro.launch.specs import build_step, lower_step
from repro.core.meshplan import plan_device_order, tpu_topology
import numpy as np
from jax.sharding import Mesh

cfg = get_smoke_config('granite-3-2b')
shape = ShapeSpec('t', 'train', 64, 8)
topo = tpu_topology(n_pods=2)
# 8 fake devices stand in for 8 hosts-worth; planner runs on the logical axes
res = plan_device_order(get_smoke_config('granite-3-2b'), shape,
                        {'pod': 2, 'data': 2, 'model': 2},
                        strategy='new_tpu')
perm = res.perm[:8] % 8
# fall back to identity if the tiny perm collides (planner targets 512 chips)
if len(set(perm.tolist())) != 8:
    perm = np.arange(8)
devices = np.asarray(jax.devices())[perm].reshape(2, 2, 2)
mesh = Mesh(devices, ('pod', 'data', 'model'))
bundle = build_step(cfg, shape, mesh)
compiled = lower_step(bundle, mesh).compile()
assert compiled.cost_analysis().get('flops', 0) > 0
print('planned mesh OK')
""")


@pytest.mark.skipif(not glob.glob(os.path.join(DRYRUN, "*.json")),
                    reason="no dry-run artifacts")
def test_dryrun_artifacts_sane():
    """Every recorded cell: positive flops, finite memory, collectives
    present on a 256-device SPMD program."""
    for path in glob.glob(os.path.join(DRYRUN, "*__single.json")):
        with open(path) as f:
            rec = json.load(f)
        hs = rec["hlo_stats"]
        assert hs["flops_per_device"] > 0, path
        assert hs["hbm_bytes_per_device"] > 0, path
        assert rec["memory"]["peak_bytes_per_device"] > 0, path
        assert rec["n_devices"] == 256, path
        # every multi-device training/prefill step must communicate
        if rec["step"] != "serve_step":
            assert hs["wire_bytes_per_chip"] > 0, path


@pytest.mark.skipif(not glob.glob(os.path.join(DRYRUN, "*.json")),
                    reason="no dry-run artifacts")
def test_dryrun_multi_pod_mirrors_single():
    """Each single-pod cell has its multi-pod twin (the pod-axis proof)."""
    singles = {os.path.basename(p).replace("__single.json", "")
               for p in glob.glob(os.path.join(DRYRUN, "*__single.json"))}
    multis = {os.path.basename(p).replace("__multi.json", "")
              for p in glob.glob(os.path.join(DRYRUN, "*__multi.json"))}
    assert singles == multis and len(singles) == 32
