"""Compile-only guards for the TPU, run without one: libtpu is installed,
so XLA:TPU compiles here for a v5e that is described and not attached
(.claude/skills/verify/SKILL.md, "No chip needed to find compile
errors"). Nothing executes and nothing is timed. Skipped, not failed,
where the topology cannot be described. Keep such tests in this one file:
only one process at a time may load the TPU's library."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from p2pfl_tpu.models import get_model


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the runtime raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip: off around it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_femnist_cnn_step_has_no_conv1_sized_relayout(one_chip,
                                                      no_persistent_cache):
    """The north-star cell's own shapes (64 nodes, batch 336, bfloat16):
    one vmapped value-and-grad of femnist-cnn. Before PR 33 the optimized
    HLO held six copies and reshapes of conv1's 25-wide im2col patches
    and of its [64,336,28,28,32] output, 89 ms of every 206 ms round on
    the v5e; a later jax or edit must not bring them back unseen."""
    n, batch = 64, 336
    model = get_model("femnist-cnn", param_dtype=jnp.bfloat16)
    params = jax.eval_shape(jax.vmap(
        lambda key: model.init(key, jnp.zeros((1, 28, 28, 1)))),
        jax.random.split(jax.random.PRNGKey(0), n))

    def loss(p, x, y):
        logits = model.apply(p, x)
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), y[:, None], axis=1))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    hlo = jax.jit(jax.vmap(jax.value_and_grad(loss))).lower(
        on_chip(params),
        jax.ShapeDtypeStruct((n, batch, 28, 28, 1), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((n, batch), jnp.int32, sharding=one_chip),
    ).compile().as_text()

    conv1_elements = n * batch * 28 * 28 * 32
    relaid = []
    for m in re.finditer(
            r"= \w+\[([\d,]+)\]\S* (?:copy|reshape|transpose)\(", hlo):
        dims = [int(d) for d in m.group(1).split(",")]
        elements = 1
        for d in dims:
            elements *= d
        if 25 in dims or elements >= conv1_elements:
            relaid.append(m.group(0))
    assert "convolution" in hlo  # the text is the optimized module
    assert not relaid, relaid
