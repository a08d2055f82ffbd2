"""Sharding-rule tests: divisibility fallback, axis dedup, multi-device lowering."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.sharding import DEFAULT_RULES, axis_rules, resolve_spec


class FakeMesh:
    """Duck-typed mesh for resolve_spec (axis names + shape only)."""

    def __init__(self, shape, axes):
        self.axis_names = axes
        self.devices = np.zeros(shape)


MESH = FakeMesh((16, 16), ("data", "model"))
MESH3 = FakeMesh((2, 16, 16), ("pod", "data", "model"))


def test_divisible_dims_shard():
    spec = resolve_spec((2048, 6144), ("embed", "mlp"), MESH)
    assert spec == P("data", "model")


def test_non_divisible_falls_back_to_replicated():
    # 25 heads on a 16-way model axis (hymba) -> replicated
    spec = resolve_spec((4, 25, 64), ("batch", "heads", None), MESH)
    assert spec[1] is None
    # vocab 32001 (hymba) -> replicated
    spec = resolve_spec((32001, 1600), ("vocab", "embed"), MESH)
    assert spec[0] is None and spec[1] == "data"


def test_axis_used_once_per_tensor():
    # experts takes "model" first; mlp then cannot reuse it
    spec = resolve_spec((16, 6144, 10752), ("experts", "embed", "mlp"), MESH)
    assert spec == P("model", "data", None)


def test_batch_spans_pod_and_data_on_multipod():
    spec = resolve_spec((256, 4096), ("batch", "seq"), MESH3)
    assert spec[0] == ("pod", "data")


def test_batch_prefix_fallback():
    # batch=2 divides pod(2) but not pod*data(32) -> prefix ("pod",)
    spec = resolve_spec((2, 4096), ("batch", "seq"), MESH3)
    assert spec[0] == "pod"


def test_rules_override_context():
    with axis_rules(mlp=()):
        spec = resolve_spec((2048, 6144), ("embed", "mlp"), MESH)
        assert spec == P("data", None)
    spec = resolve_spec((2048, 6144), ("embed", "mlp"), MESH)
    assert spec == P("data", "model")


SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.launch.dryrun import lower_cell  # noqa: F401  (imports set up helpers)
from repro.config import SHAPES
from repro.config.base import ShapeConfig
from repro.configs.qwen3_1p7b import reduced
from repro.config.base import MeshConfig
from repro.launch.mesh import make_mesh
from repro.launch.sharding import tree_shardings
from repro.launch.steps import batch_axes, input_specs, make_train_step, opt_state_axes
from repro.config.base import TrainConfig, OptimizerConfig
from repro.models.layers import abstract_init
from repro.models.transformer import lm_init

cfg = reduced()
mesh = make_mesh(MeshConfig(shape=(2, 4), axes=("data", "model")))
shape = ShapeConfig("t", seq_len=32, global_batch=8, mode="train")
with abstract_init():
    ps, pa = lm_init(cfg, 0)
tc = TrainConfig(optimizer=OptimizerConfig(name="adamw"), microbatches=2)
step, opt_init = make_train_step(cfg, tc)
with mesh:
    p_shard = tree_shardings(mesh, ps, pa)
    specs = input_specs(cfg, shape)
    b_shard = tree_shardings(mesh, specs, batch_axes(cfg, shape))
    opt_shapes = jax.eval_shape(opt_init, ps)
    import repro.launch.dryrun as dr
    o_shard = dr._opt_shardings(mesh, opt_shapes, opt_state_axes(cfg, pa, tc.optimizer), p_shard)
    lowered = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                      out_shardings=(p_shard, o_shard, None)).lower(ps, opt_shapes, specs)
    compiled = lowered.compile()
    print("COMPILED_OK", compiled.memory_analysis().temp_size_in_bytes >= 0)
"""


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_multidevice_train_step_compiles():
    """8 virtual CPU devices in a subprocess (the XLA flag must be set
    before the child's backend starts; the child never touches a chip)."""
    env = dict(os.environ)
    env.update({"PYTHONPATH": os.path.join(REPO, "src"),
                "JAX_PLATFORMS": "cpu"})
    out = subprocess.run(
        [sys.executable, "-c", SUBPROC], capture_output=True, text=True,
        env=env, cwd=REPO, timeout=600)
    assert "COMPILED_OK True" in out.stdout, out.stderr[-2000:]
