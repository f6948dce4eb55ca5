"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with jax, and it compiles for a chip that is
described rather than attached. That catches what interpret mode cannot:
block shapes that break the (8, 128) tiling rule and kernels that need
more scoped VMEM than the chip allows. Each case compiles the kernel module
directly with ``interpret=False`` at the widths the engine and the serving
plane use, and checks that the compiled program holds the Pallas kernel
(``tpu_custom_call``).

This is the only test file that loads the TPU compiler: the topology is
described inside a module fixture (never at import), so under pytest-xdist
only the worker given this file loads it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import cosine_sim, decode_attention, segment_aggregate


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # AOT compiles go to the persistent cache but cannot be read back
    # without a chip: keep the cache off while this file compiles
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _cosine(x, c):
    return cosine_sim.cosine_similarity(x, c, interpret=False)


def test_cosine_similarity_compiles(one_chip):
    text = _compiled_text(_cosine, one_chip, ((256, 128), jnp.float32),
                          ((8, 128), jnp.float32))
    assert "tpu_custom_call" in text


def test_cosine_similarity_vmapped_over_cohorts_compiles(one_chip):
    text = _compiled_text(jax.vmap(_cosine), one_chip, ((4, 256, 128), jnp.float32),
                          ((4, 8, 128), jnp.float32))
    assert "tpu_custom_call" in text


def test_segment_aggregate_compiles(one_chip):
    def fn(data, ids, w):
        return segment_aggregate.segment_aggregate(data, ids, 8, w,
                                                   interpret=False)

    text = _compiled_text(fn, one_chip, ((256, 128), jnp.float32),
                          ((256, 1), jnp.int32), ((256, 1), jnp.float32))
    assert "tpu_custom_call" in text


# (H, Hkv, hd): granite-3-2b and qwen3-8b attention widths
WIDTHS = {"granite-3-2b": (32, 8, 64), "qwen3-8b": (32, 8, 128)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("seq", [512, 4096, 8192])
@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_decode_attention_compiles(one_chip, arch, seq, dtype):
    H, Hkv, hd = WIDTHS[arch]
    lanes = 4

    def fn(q, k, v, n):
        return decode_attention.decode_attention(q, k, v, n, interpret=False)

    text = _compiled_text(
        fn, one_chip,
        ((lanes, H, hd), dtype),
        ((lanes, seq, Hkv, hd), dtype),
        ((lanes, seq, Hkv, hd), dtype),
        ((lanes,), jnp.int32),
    )
    assert "tpu_custom_call" in text
