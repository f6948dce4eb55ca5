"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with jax, and it compiles for a chip that is
described rather than attached. That catches what interpret mode cannot:
block shapes that break the (8, 128) tiling rule and kernels that need
more scoped VMEM than the chip allows. Each case compiles the kernel module
directly with ``interpret=False`` at the widths the engine and the serving
plane use, and checks that the compiled program holds the Pallas kernel
(``tpu_custom_call``).

The paged decode's whole fleet step is compiled too, as the decoder builds
it for a TPU, to check that it updates the KV cache in place, and for
granite-4.0-h that it updates the Mamba2 state beside it in place.

This is the only test file that loads the TPU compiler: the topology is
described inside a module fixture (never at import), so under pytest-xdist
only the worker given this file loads it.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import cosine_sim, decode_attention, segment_aggregate, ssm_decode
from repro.models import build_model
from repro.serve import CohortDecoder


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


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_decode_attention_stacked_cache_compiles(one_chip, arch):
    H, Hkv, hd = WIDTHS[arch]
    lanes, layers, seq = 4, 3, 2048

    def fn(q, k, v, n, layer):
        return decode_attention.decode_attention(q, k, v, n, layer,
                                                 interpret=False)

    text = _compiled_text(
        fn, one_chip,
        ((lanes, H, hd), jnp.bfloat16),
        ((layers, lanes, seq, Hkv * hd), jnp.float32),
        ((layers, lanes, seq, Hkv * hd), jnp.float32),
        ((lanes,), jnp.int32),
        ((), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_fleet_decode_step_updates_cache_in_place(one_chip, monkeypatch):
    """The fleet step at granite-3-2b widths (2 layers, a small vocabulary),
    as the decoder builds it on a TPU: both cache arguments alias outputs,
    no copy or fusion writes an f32 buffer of a layer's slice or more, and
    the attention kernel is there under the name the benchmark reads."""
    lanes, seq = 4, 2048
    cfg = get_config("granite-3-2b").replace(dtype=jnp.bfloat16, n_layers=2,
                                             vocab=4096)
    model = build_model(cfg)
    # the decoder and the kernel wrapper take their TPU branches: donation
    # on, Pallas compiled rather than interpreted
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dec = CohortDecoder(model, None, lambda: [0], lanes=lanes, page_size=128)
    shaped = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    bank = jax.eval_shape(lambda k: jax.tree.map(lambda a: a[None], model.init(k)),
                          jax.random.key(0))
    bank = jax.tree.map(lambda a: shaped(a.shape, a.dtype), bank)
    dec.cache.sync([0])
    dec.cache.ensure(seq)  # the cache's own storage form, at seq positions
    cache = shaped(dec.cache.k.shape, dec.cache.k.dtype)
    text = dec._fleet_step.lower(
        bank, shaped((1, lanes, 1), jnp.int32), cache, cache,
        shaped((1,), jnp.int32),
    ).compile().as_text()
    head = text.splitlines()[0]
    assert head.startswith("HloModule jit_step,"), head
    # (a) outputs 1 and 2 (the caches) alias the cache parameters, which
    # follow the bank's leaves and the tokens
    kc = len(jax.tree.leaves(bank)) + 1
    assert "input_output_alias=" in head, "the caches are not donated"
    alias = head.split("input_output_alias=")[1].split("entry_computation_layout")[0]
    assert f"{{1}}: ({kc}," in alias and f"{{2}}: ({kc + 1}," in alias, alias
    # (b) nothing copies a layer's slice of the cache, or more
    slice_elems = lanes * seq * cfg.n_kv_heads * cfg.hd
    big = []
    for m in re.finditer(r"^\s*(?:ROOT )?%(\S+) = (.+?) (copy|copy-start|fusion)\(",
                         text, re.M):
        for dims in re.findall(r"f32\[([\d,]+)\]", m.group(2)):
            n = 1
            for d in dims.split(","):
                n *= int(d)
            if n >= slice_elems:
                big.append(m.group(1))
    assert not big, big
    # (c) the kernel, labelled as the benchmark's trace reader finds it
    assert re.search(r"%decode_attention(\.\d+)? = .*tpu_custom_call", text)


def test_ssm_decode_compiles(one_chip):
    """The Mamba2 state update at granite-4.0-h widths (64 heads of 64, state
    128), 32 lanes, one layer of a 3-layer stack, with the state aliased."""
    lanes, layers, H, P, N = 32, 3, 64, 64, 128
    f32 = jnp.float32

    def fn(da, dtx, b, c, state, layer):
        return ssm_decode.ssm_decode(da, dtx, b, c, state, layer, interpret=False)

    text = _compiled_text(
        fn, one_chip,
        ((lanes, H * P), f32), ((lanes, H * P), f32),
        ((lanes, N), f32), ((lanes, N), f32),
        ((layers, lanes, N, H * P), f32), ((), jnp.int32),
    )
    assert re.search(r"%ssm_decode(\.\d+)? = .*tpu_custom_call", text)
    assert "output_to_operand_aliasing={{1}: (5, {})}" in text


def _in_place_fusions(text):
    """Fused computations whose root is a dynamic-update-slice: XLA's form of
    an in-place update, which writes only the slice although its shape is
    the whole buffer's."""
    names = set()
    for m in re.finditer(r"^(%\S+) \(.*?^\}", text, re.M | re.S):
        root = re.search(r"^\s*ROOT %\S+ = \S+ (\S+?)\(", m.group(0), re.M)
        if root and root.group(1) == "dynamic-update-slice":
            names.add(m.group(1))
    return names


def test_hybrid_fleet_decode_step_updates_state_in_place(one_chip, monkeypatch):
    """granite-4.0-h-micro's fleet step at published widths, one period (10
    layers: attention at 5, Mamba2 elsewhere), a small vocabulary, 32 lanes
    and 4,096 positions, as the decoder builds it on a TPU: the KV pages, the
    SSM state and the conv windows all alias outputs; no copy or fusion
    writes an f32 buffer of one layer's state or pages or more (a fusion
    rooted in a dynamic-update-slice writes only its slice in place); both
    kernels are there under their names."""
    lanes, seq = 32, 4096
    cfg = get_config("granite-4.0-h-micro").replace(
        dtype=jnp.bfloat16, n_layers=10, attn_layers=(5,), vocab=4096)
    model = build_model(cfg)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dec = CohortDecoder(model, None, lambda: [0], lanes=lanes, page_size=128)
    shaped = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    bank = jax.eval_shape(lambda k: jax.tree.map(lambda a: a[None], model.init(k)),
                          jax.random.key(0))
    bank = jax.tree.map(lambda a: shaped(a.shape, a.dtype), bank)
    dec.cache.sync([0])
    dec.cache.ensure(seq)
    state = [shaped(a.shape, a.dtype) for a in dec.cache.arrays]  # k, v, ssm, conv
    text = dec._fleet_step.lower(
        bank, shaped((1, lanes, 1), jnp.int32), *state, shaped((1,), jnp.int32),
    ).compile().as_text()
    head = text.splitlines()[0]
    assert head.startswith("HloModule jit_step,"), head
    # (a) outputs 1-4 alias the k, v, ssm and conv parameters, which follow
    # the bank's leaves and the tokens
    first = len(jax.tree.leaves(bank)) + 1
    assert "input_output_alias=" in head, "the state is not donated"
    alias = head.split("input_output_alias=")[1].split("entry_computation_layout")[0]
    for out in range(1, 5):
        assert f"{{{out}}}: ({first + out - 1}," in alias, alias
    # (b) nothing copies a layer's pages or state, or more: the smallest is
    # one layer's conv window
    layer_elems = min(a.size // a.shape[1] for a in state)
    in_place = _in_place_fusions(text)
    big = []
    for m in re.finditer(
            r"^\s*(?:ROOT )?%(\S+) = (.+?) (copy|copy-start|fusion)\((.*)$", text, re.M):
        calls = re.search(r"calls=(%[^,\s]+)", m.group(4))
        if m.group(3) == "fusion" and calls and calls.group(1) in in_place:
            continue
        for dims in re.findall(r"f32\[([\d,]+)\]", m.group(2)):
            n = 1
            for d in dims.split(","):
                n *= int(d)
            if n >= layer_elems:
                big.append(m.group(1))
    assert not big, big
    # (c) the kernels, labelled as the benchmark's trace readers find them
    assert re.search(r"%ssm_decode(\.\d+)? = .*tpu_custom_call", text)
    assert re.search(r"%decode_attention(\.\d+)? = .*tpu_custom_call", text)
