"""AOT compiles of the main-path Pallas kernels at real widths, for a TPU
v5e that is described, not attached.

The TPU compiler is installed with jax, so `jax.jit(...).lower(...)
.compile()` against a described v5e raises what the chip's compiler would
raise: block shapes off the (8, 128) tiling, unsupported casts or layouts,
more VMEM than a kernel may use.  The kernels are called through their
public wrappers with ``interpret=False``, so the tiling compiled here is
the tiling the chip runs (the interpret-mode tests on the CPU use the same
tiling rule).  Widths are qwen2.5-3b's: d_model 2048, d_ff 11008, vocab
151936, 16 query heads over 2 KV heads of dim 128; td_vmm at the paper's
chain length 576 and at 128 (the TD-attention head-dim clamp).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_gqa.decode_gqa import decode_gqa_pallas
from repro.kernels.flash_attn.flash_attn import flash_attn_pallas
from repro.kernels.td_vmm.td_vmm import td_vmm_pallas

D_MODEL, D_FF, VOCAB = 2048, 11008, 151936
HQ, HKV, HD = 16, 2, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Mosaic kernel
    return compiled


@pytest.mark.parametrize("k,n", [(D_MODEL, D_MODEL), (D_MODEL, D_FF),
                                 (D_MODEL, VOCAB), (D_FF, D_MODEL)])
@pytest.mark.parametrize("n_chain", [576, 128])
def test_td_vmm_compiles(one_chip, n_chain, k, n):
    m = 8                                   # a decode batch of slots
    k_pad = -(-k // n_chain) * n_chain

    def fn(x, w, params, seed):
        return td_vmm_pallas(x, w, params, seed, bits_a=4, bits_w=4,
                             n_chain=n_chain, k_true=k, interpret=False)

    spec = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    _compile(fn, spec((m, k_pad), jnp.int32), spec((k_pad, n), jnp.int32),
             spec((2,), jnp.float32), spec((), jnp.uint32))


def test_flash_attn_compiles(one_chip):
    b, s = 1, 2048

    def fn(q, k, v, kv_len, q_off):
        return flash_attn_pallas(q, k, v, kv_len, q_off, causal=True,
                                 interpret=False)

    spec = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    _compile(fn, spec((b, s, HQ, HD), jnp.bfloat16),
             spec((b, s, HKV, HD), jnp.bfloat16),
             spec((b, s, HKV, HD), jnp.bfloat16),
             spec((b,), jnp.int32), spec((), jnp.int32))


def test_decode_gqa_compiles(one_chip):
    b, s = 8, 4096

    def fn(q, k, v, length):
        return decode_gqa_pallas(q, k, v, length, interpret=False)

    spec = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    _compile(fn, spec((b, HQ, HD), jnp.bfloat16),
             spec((b, s, HKV, HD), jnp.bfloat16),
             spec((b, s, HKV, HD), jnp.bfloat16), spec((b,), jnp.int32))
