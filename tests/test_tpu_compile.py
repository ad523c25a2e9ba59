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

Beside the kernels, the serving engine's whole decode program is compiled
at those widths (2 layers), to pin how it treats the KV cache it is
donated: in place, with no loop over rows and no copy of a layer's cache;
and granite-4.0-h-micro's hybrid decode program (a Mamba2 and an
attention layer at its widths), to pin the same of its recurrent state.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as cfgs
from repro.kernels import common as kernels_common
from repro.kernels.decode_gqa.decode_gqa import decode_gqa_pallas
from repro.kernels.flash_attn.flash_attn import flash_attn_pallas
from repro.kernels.td_vmm.td_vmm import td_vmm_pallas
from repro.launch import steps
from repro.models import common, get_api, transformer

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


def test_serve_step_writes_kv_cache_in_place(one_chip, monkeypatch):
    """The continuous-batching engine's decode program (per-row caches,
    capacity 48, 2048-token slots, the state donated as the engine donates
    it) at qwen2.5-3b widths with 2 layers: each layer's lane-dense cache
    is read by the decode kernel as it is and the new row is written into
    it in place."""
    # the kernels inside the step resolve their mode from the backend:
    # compile them as on the chip, from this CPU process
    monkeypatch.setattr(kernels_common, "default_interpret", lambda: False)
    b, s_cache, n_layers = 48, 2048, 2
    arch = cfgs.get("qwen2.5-3b")
    cfg = dataclasses.replace(arch.model, n_layers=n_layers)
    arch = dataclasses.replace(arch, model=cfg)
    pol = common.resolve_arch_policy(arch)
    assert pol.mode == "precise"
    step = steps.build_serve_step(
        arch, steps.ShapeCfg("serve", s_cache, b, "decode"))
    params = jax.eval_shape(lambda: get_api(cfg)["init"](
        jax.random.key(0), cfg, pol, jnp.bfloat16))
    caches = jax.eval_shape(lambda: transformer.init_caches(
        b, s_cache, cfg, jnp.bfloat16, pol=pol, per_row_idx=True))
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    compiled = jax.jit(step, donate_argnums=(2,)).lower(
        on_chip(params), on_chip(jax.ShapeDtypeStruct((b, 1), jnp.int32)),
        on_chip({"layers": caches, "enc_out": None})).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text                 # decode_gqa, compiled

    layer_shape = (b, s_cache, HKV * HD)
    assert {c["k"].shape for c in caches} == {layer_shape}
    layer_bytes = b * s_cache * HKV * HD * 2
    entry = text[text.index("\nENTRY"):]
    whole = re.compile(r"= \w+\[([0-9,]+)\]\S* (reshape|copy)\(")
    copies = [ln for ln in entry.splitlines()
              if (m := whole.search(ln)) and np.prod(
                  [int(x) for x in m.group(1).split(",")]) == np.prod(
                      layer_shape)]
    assert not copies, copies                        # no re-tiling copy
    loops = [ln for ln in text.splitlines() if " while(" in ln
             and ("scatter" in ln or f"[{b},{s_cache}," in ln)]
    assert not loops, loops                          # no loop over rows
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer_bytes
    # every byte of the pool (K and V of each layer) is aliased in place
    assert mem.alias_size_in_bytes >= 2 * n_layers * layer_bytes


def test_hybrid_serve_step_updates_state_in_place(one_chip, monkeypatch):
    """The decode program of granite-4.0-h-micro's hybrid (one Mamba2 and
    one attention layer at its published widths, capacity 32, 2560-token
    slots, the state donated as the engine donates it): the per-slot conv
    and f32 SSM state is updated in place beside the KV cache, with no
    copy of a layer's state and the whole pool aliased."""
    monkeypatch.setattr(kernels_common, "default_interpret", lambda: False)
    b, s_cache = 32, 2560
    arch = cfgs.get("granite-4.0-h-micro")
    cfg = dataclasses.replace(arch.model, n_layers=2,
                              layer_pattern=("mamba2", "attn"))
    arch = dataclasses.replace(arch, model=cfg)
    pol = common.resolve_arch_policy(arch)
    step = steps.build_serve_step(
        arch, steps.ShapeCfg("serve", s_cache, b, "decode"))
    params = jax.eval_shape(lambda: get_api(cfg)["init"](
        jax.random.key(0), cfg, pol, jnp.bfloat16))
    caches = jax.eval_shape(lambda: transformer.init_caches(
        b, s_cache, cfg, jnp.bfloat16, pol=pol, per_row_idx=True))
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    compiled = jax.jit(step, donate_argnums=(2,)).lower(
        on_chip(params), on_chip(jax.ShapeDtypeStruct((b, 1), jnp.int32)),
        on_chip({"layers": caches, "enc_out": None})).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text                 # decode_gqa, compiled
    ssm = caches[0]["ssm"]
    assert ssm.shape == (b, 64, 64, 128) and ssm.dtype == jnp.float32
    ssm_bytes = int(np.prod(ssm.shape)) * 4
    entry = text[text.index("\nENTRY"):]
    copies = [ln for ln in entry.splitlines()
              if re.search(r"= f32\[32,64,64,128\]\S* copy\(", ln)]
    assert not copies, copies                        # no copy of the state
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < ssm_bytes
    pool = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for c in caches for k, a in c.items() if k != "idx")
    assert mem.alias_size_in_bytes >= pool
