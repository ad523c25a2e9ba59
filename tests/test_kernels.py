"""Per-kernel interpret=True validation against the ref.py oracles, with
shape/dtype sweeps (assignment requirement c).

Hypothesis is optional: only the property-based classes skip without it —
the deterministic oracle sweeps must run on a bare environment too.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on bare environments
    HAVE_HYPOTHESIS = False

from repro.kernels.decode_gqa.decode_gqa import decode_gqa_pallas
from repro.kernels.decode_gqa.ref import decode_gqa_ref
from repro.kernels.lsq_quant.lsq_quant import lsq_quant_pallas
from repro.kernels.lsq_quant.ref import lsq_quant_ref
from repro.kernels.td_vmm import ref as td_ref
from repro.kernels.td_vmm.td_vmm import td_vmm_pallas


def test_interpret_policy(monkeypatch):
    """One policy for every kernel: None follows the backend, and interpret
    mode is refused on a TPU backend."""
    from repro.kernels import common
    assert common.resolve_interpret(None) is common.default_interpret()
    assert common.resolve_interpret(False) is False
    monkeypatch.setattr(common, "default_interpret", lambda: False)
    assert common.resolve_interpret(None) is False
    with pytest.raises(ValueError, match="refused"):
        common.resolve_interpret(True)


class TestTdVmmKernel:
    @pytest.mark.parametrize("m,k,n,n_chain,bm,bn", [
        (16, 32, 16, 32, 16, 128),
        (48, 96, 40, 32, 16, 128),
        (33, 64, 17, 64, 16, 128),     # non-divisible M/N -> padding
        (128, 576, 64, 576, 64, 128),  # paper-baseline chain length
        (16, 70, 12, 32, 16, 128),     # ragged K -> masked tail segment
    ])
    @pytest.mark.parametrize("sigma,q", [(0.0, 1), (1.5, 1), (2.5, 3)])
    def test_matches_signed_ref(self, m, k, n, n_chain, bm, bn, sigma, q):
        """Runtime (sigma, q) operands against the fused signed oracle."""
        key = jax.random.PRNGKey(m * 1000 + n)
        kx, kw = jax.random.split(key)
        xi = jax.random.randint(kx, (m, k), -8, 8, jnp.int32)
        wi = jax.random.randint(kw, (k, n), -8, 8, jnp.int32)
        seed = jnp.uint32(77)
        r = td_ref.td_vmm_signed_ref(xi, wi, bits_a=4, bits_w=4,
                                     n_chain=n_chain, sigma=sigma, tdc_q=q,
                                     seed=seed)
        n_seg = -(-k // n_chain)
        xi_p = jnp.pad(xi, ((0, 0), (0, n_seg * n_chain - k)))
        wi_p = jnp.pad(wi, ((0, n_seg * n_chain - k), (0, 0)))
        p = td_vmm_pallas(xi_p, wi_p,
                          jnp.asarray([sigma, q], jnp.float32), seed,
                          bits_a=4, bits_w=4, n_chain=n_chain, k_true=k,
                          bm=bm, bn=bn)
        np.testing.assert_array_equal(np.asarray(r), np.asarray(p))

    @pytest.mark.parametrize("bits_a", [1, 2, 4, 8])
    def test_bit_widths(self, bits_a):
        key = jax.random.PRNGKey(bits_a)
        kx, kw = jax.random.split(key)
        lo, hi = -(2 ** (bits_a - 1)), 2 ** (bits_a - 1)
        xi = jax.random.randint(kx, (8, 64), lo, hi, jnp.int32)
        wi = jax.random.randint(kw, (64, 8), -8, 8, jnp.int32)
        r = td_ref.td_vmm_signed_ref(xi, wi, bits_a=bits_a, bits_w=4,
                                     n_chain=32, sigma=0.5, tdc_q=1,
                                     seed=jnp.uint32(3))
        p = td_vmm_pallas(xi, wi, jnp.asarray([0.5, 1.0], jnp.float32),
                          jnp.uint32(3), bits_a=bits_a, bits_w=4,
                          n_chain=32, bm=8, bn=128)
        np.testing.assert_array_equal(np.asarray(r), np.asarray(p))

    def test_runtime_sigma_q_one_program(self):
        """sigma / tdc_q are runtime operands: sweeping them must not leave
        the first compiled program (same static shapes -> same jit cache
        entry), and each point must match the oracle."""
        key = jax.random.PRNGKey(5)
        kx, kw = jax.random.split(key)
        xi = jax.random.randint(kx, (16, 64), -8, 8, jnp.int32)
        wi = jax.random.randint(kw, (64, 16), -8, 8, jnp.int32)
        seed = jnp.uint32(11)
        from repro.kernels.td_vmm.td_vmm import _td_vmm_call
        misses0 = _td_vmm_call._cache_size()
        for sigma, q in [(0.0, 1.0), (0.7, 1.0), (2.0, 4.0)]:
            p = td_vmm_pallas(xi, wi, jnp.asarray([sigma, q], jnp.float32),
                              seed, bits_a=4, bits_w=4, n_chain=32)
            r = td_ref.td_vmm_signed_ref(xi, wi, bits_a=4, bits_w=4,
                                         n_chain=32, sigma=sigma, tdc_q=q,
                                         seed=seed)
            np.testing.assert_array_equal(np.asarray(r), np.asarray(p))
        assert _td_vmm_call._cache_size() - misses0 <= 1

    def test_hash_noise_is_standard_normal(self):
        idx = jnp.arange(100000, dtype=jnp.uint32)
        z = np.asarray(td_ref.gauss_noise(idx, jnp.uint32(42)))
        assert abs(z.mean()) < 0.02
        assert abs(z.std() - 1.0) < 0.02
        # tail sanity (Gaussian: P(|z|>3) ~ 0.0027)
        assert 0.0005 < (np.abs(z) > 3).mean() < 0.008


class TestLsqQuantKernel:
    @pytest.mark.parametrize("shape", [(64,), (37, 53), (4, 5, 6)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("bits,signed", [(4, True), (8, True),
                                             (4, False)])
    def test_matches_ref(self, shape, dtype, bits, signed):
        key = jax.random.PRNGKey(sum(shape))
        x = (jax.random.normal(key, shape) * 2).astype(dtype)
        s = jnp.asarray(0.07, dtype)
        from repro.quant.lsq import qrange
        qn, qp = qrange(bits, signed)
        r = lsq_quant_ref(x, s, qn, qp)
        p = lsq_quant_pallas(x, s, qn=float(qn), qp=float(qp), bm=64)
        np.testing.assert_allclose(np.asarray(r, np.float32),
                                   np.asarray(p, np.float32), atol=1e-6)


class TestDecodeGqaKernel:
    @pytest.mark.parametrize("b,hq,hkv,d,s,bs", [
        (2, 8, 2, 64, 300, 128),
        (1, 4, 4, 32, 64, 64),
        (3, 16, 8, 128, 1000, 256),
        (2, 8, 1, 64, 127, 32),       # MQA + ragged length
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, b, hq, hkv, d, s, bs, dtype):
        key = jax.random.PRNGKey(b * 100 + s)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, hq, d)).astype(dtype)
        k = jax.random.normal(kk, (b, s, hkv, d)).astype(dtype)
        v = jax.random.normal(kv, (b, s, hkv, d)).astype(dtype)
        length = jnp.asarray([max(1, s - 11 * i) for i in range(b)],
                             jnp.int32)
        r = decode_gqa_ref(q, k, v, length)
        p = decode_gqa_pallas(q, k, v, length, bs=bs)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(r, np.float32),
                                   np.asarray(p, np.float32),
                                   atol=tol, rtol=tol)

if HAVE_HYPOTHESIS:
    class TestDecodeGqaProperties:
        @given(st.integers(1, 3), st.integers(30, 200))
        @settings(max_examples=10, deadline=None)
        def test_property_random_shapes(self, b, s):
            key = jax.random.PRNGKey(b * s)
            kq, kk, kv = jax.random.split(key, 3)
            q = jax.random.normal(kq, (b, 4, 32))
            k = jax.random.normal(kk, (b, s, 2, 32))
            v = jax.random.normal(kv, (b, s, 2, 32))
            length = jnp.full((b,), s, jnp.int32)
            r = decode_gqa_ref(q, k, v, length)
            p = decode_gqa_pallas(q, k, v, length, bs=64)
            np.testing.assert_allclose(np.asarray(r), np.asarray(p),
                                       atol=1e-4, rtol=1e-4)


class TestFlashAttnKernel:
    @pytest.mark.parametrize("b,s,hq,hkv,d,bq,bk,causal", [
        (2, 128, 8, 2, 64, 64, 64, True),
        (1, 256, 4, 4, 32, 128, 64, True),
        (2, 128, 8, 2, 64, 32, 128, False),
        (1, 128, 8, 1, 64, 64, 64, True),    # MQA
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, b, s, hq, hkv, d, bq, bk, causal, dtype):
        from repro.kernels.flash_attn.flash_attn import flash_attn_pallas
        from repro.kernels.flash_attn.ref import flash_attn_ref
        key = jax.random.PRNGKey(s + hq)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, s, hq, d)).astype(dtype)
        k = jax.random.normal(kk, (b, s, hkv, d)).astype(dtype)
        v = jax.random.normal(kv, (b, s, hkv, d)).astype(dtype)
        r = flash_attn_ref(q, k, v, causal)
        p = flash_attn_pallas(q, k, v, causal=causal, bq=bq, bk=bk)
        tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(r, np.float32),
                                   np.asarray(p, np.float32),
                                   atol=tol, rtol=tol)

    def test_matches_model_attention_route(self):
        """The production op the model calls (`ops.flash_attention`, with
        runtime kv_len/q_offset operands) agrees with the raw kernel and
        the oracle on a rectangular cache-prefill-style call."""
        from repro.kernels.flash_attn.flash_attn import flash_attn_pallas
        from repro.kernels.flash_attn.ops import flash_attention
        from repro.kernels.flash_attn.ref import flash_attn_ref
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        b, sq, skv, hq, hkv, d = 2, 48, 256, 8, 2, 64
        q = jax.random.normal(kq, (b, sq, hq, d))
        k = jax.random.normal(kk, (b, skv, hkv, d))
        v = jax.random.normal(kv, (b, skv, hkv, d))
        kv_len = jnp.asarray([200, 97], jnp.int32)
        q_off = jnp.asarray(40, jnp.int32)
        r = flash_attn_ref(q, k, v, True, kv_len, q_off)
        o = flash_attention(q, k, v, kv_len, q_off, causal=True)
        p = flash_attn_pallas(q, k, v, kv_len, q_off, causal=True,
                              bq=16, bk=64)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                   atol=2e-5, rtol=2e-5)
