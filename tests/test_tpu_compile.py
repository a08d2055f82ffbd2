"""The main path's kernels compile for a TPU v5e, with no chip attached.

The TPU compiler is installed alongside jax, and it compiles for a chip
that is described rather than present (``topologies.get_topology_desc``).
Interpret-mode tests cannot show what Mosaic refuses: the int8-tile mask
of ``sched_score`` passed every interpret test and was refused here. So the
kernels are compiled at the real widths of the main path:

- ``sched_score.plan_stats`` on dense int8 plans, P=512 x K=100,000 (the
  fleet-scoring shape) and a ragged P=100 x K=300;
- ``scatter_add`` at the size of VGG16's largest leaf (the 4096 x 4096
  ``fc`` weight), 10 devices uploading 1% top-k deltas;
- the jitted ``score_plans`` jax path at K=100,000.

Everything that touches the TPU library happens inside the fixtures and
tests below, never at import: only one process may load that library, and
the test workers all import this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # pragma: no cover - no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("P,K", [(512, 100_000), (100, 300)])
def test_sched_score_compiles_for_v5e(one_chip, no_persistent_cache, P, K):
    from repro.kernels import sched_score

    compiled = sched_score.plan_stats.lower(
        _sds((K,), jnp.float32, one_chip), _sds((K,), jnp.float32, one_chip),
        _sds((P, K), jnp.int8, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_scatter_add_compiles_at_vgg16_fc_leaf(one_chip, no_persistent_cache):
    from repro.kernels import scatter_add

    size, n = 4096 * 4096, 10
    k = round(0.01 * size)
    compiled = scatter_add.scatter_add.lower(
        _sds((n, k), jnp.float32, one_chip), _sds((n, k), jnp.int32, one_chip),
        _sds((n,), jnp.float32, one_chip), size=size).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_jax_score_plans_compiles_at_fleet_scale(one_chip,
                                                 no_persistent_cache):
    from repro.core import scoring

    P, K = 512, 100_000
    scalar = _sds((), jnp.float32, one_chip)
    compiled = scoring._jax_score_fn(True).lower(
        _sds((K,), jnp.float32, one_chip), _sds((K,), jnp.float32, one_chip),
        _sds((P, K), jnp.int8, one_chip), scalar, scalar, scalar,
        scalar).compile()
    assert compiled.memory_analysis() is not None
