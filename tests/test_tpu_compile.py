"""Compiles for a described TPU v5e: the scoring kernel, the scorer
bodies and the train step at the operator family's real widths.

Nothing runs; the TPU compiler installed with JAX refuses here what the
chip would refuse (Mosaic verification, scoped VMEM, HBM capacity, a
kernel XLA cannot partition). The topology is described inside a
module fixture, never at import: only the worker that runs this file
loads the TPU library, and every worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import operators
from repro.core.operators import OperatorArch, init_operator
from repro.core.runtime import OperatorRuntime, arch_signature
from repro.kernels.conv_scorer import conv_scorer

WIDEST = OperatorArch("widest", 5, 32, 64, 100)       # L5c32d64s100
HBM_BYTES = 16 * 10 ** 9                              # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # compiles for an absent chip can be written to a persistent cache
    # but never read back; keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _params_spec(arch, sharding, lead=()):
    shapes = jax.eval_shape(lambda: init_operator(arch, jax.random.PRNGKey(0)))
    return jax.tree_util.tree_map(
        lambda s: _spec(lead + s.shape, sharding, s.dtype), shapes)


def _fits(compiled, limit=HBM_BYTES):
    m = compiled.memory_analysis()
    used = m.temp_size_in_bytes + m.argument_size_in_bytes + \
        m.output_size_in_bytes
    assert used < limit, f"{used / 1e9:.2f} GB does not fit {limit / 1e9} GB"


@pytest.mark.parametrize("n,hw,cin,cout", [
    (64, 100, 3, 32),          # first layer of the widest operator
    (64, 50, 32, 32),          # a deep layer
    (64, 7, 32, 32),           # the smallest input a deep layer sees
])
def test_conv_scorer_compiles(one_chip, n, hw, cin, cout):
    compiled = jax.jit(conv_scorer).lower(
        _spec((n, hw, hw, cin), one_chip), _spec((3, 3, cin, cout), one_chip),
        _spec((cout,), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("layer,lead", [("bucketed", (1024,)),
                                        ("superbatch", (8, 1024))])
def test_pallas_scorer_body_compiles(one_chip, layer, lead):
    """The runtime's compiled scorer for the widest operator: one kernel
    per conv layer, inside the chip's memory."""
    rt = OperatorRuntime(backend="pallas")
    sig = arch_signature(WIDEST)
    fn = rt._bucket_fn(sig) if layer == "bucketed" else rt._super_fn(sig)
    params = _params_spec(WIDEST, one_chip, lead[:-1])
    compiled = fn.lower(params, _spec(lead + (100, 100, 3),
                                      one_chip)).compile()
    assert compiled.as_text().count("tpu_custom_call") == WIDEST.conv_layers
    _fits(compiled)


@pytest.mark.parametrize("group", [8, 3])
def test_sharded_superbatch_compiles(topo, group):
    """Over four chips the superbatch shards on its group axis (or, for
    a group that does not divide, replicates); XLA cannot partition the
    kernel itself, so the runtime must hand each device its members."""
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    rt = OperatorRuntime(backend="pallas", mesh=mesh)
    spec = P("data") if group % mesh.size == 0 else P()
    params = _params_spec(WIDEST, NamedSharding(mesh, P()), (group,))
    compiled = rt._super_fn(arch_signature(WIDEST)).lower(
        params, _spec((group, 128, 100, 100, 3),
                      NamedSharding(mesh, spec))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_adam_step_compiles(one_chip):
    """The fused train step at the widest operator and the batch
    ``train_operator`` picks for it (128)."""
    batch = 128
    params = _params_spec(WIDEST, one_chip)
    scalar = _spec((), one_chip)
    compiled = operators._adam_step().lower(
        params, params, params, _spec((batch, 100, 100, 3), one_chip),
        _spec((batch, 1, 1, 1), one_chip), _spec((batch,), one_chip),
        _spec((batch,), one_chip), scalar, scalar, scalar, scalar,
        True).compile()
    _fits(compiled)


def test_gather_step_compiles(one_chip):
    """A step's minibatch gather at the widest operator, the batch and
    steps ``train_operator`` runs it with (128, 150) and a training set
    at ``CloudTrainer.train``'s ``max_samples`` (4,000 crops)."""
    n, steps, batch = 4000, 150, 128
    compiled = operators._gather_step.lower(
        _spec((n, 100, 100, 3), one_chip), _spec((n,), one_chip),
        _spec((n,), one_chip), _spec((steps, batch), one_chip, jnp.int32),
        _spec((steps, batch, 1, 1, 1), one_chip), _spec((steps, 2), one_chip),
        _spec((), one_chip, jnp.int32)).compile()
    _fits(compiled)
