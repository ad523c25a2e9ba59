"""Property tests for the Fig. 10 sigma_array_max search: the scalar
reference and the batched (single vmapped call) variant implement the same
interpolated 1 %-crossing.

Evals here are synthetic, deterministic drop curves (the key is ignored) so
scalar/batched parity is exact up to float32 promotion inside the vmapped
call; model-level noisy parity is exercised by the benchmark.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on bare environments
    # property tests skip individually; the deterministic endpoint/gc
    # tests below still run without hypothesis
    HAVE_HYPOTHESIS = False

    def given(*_a, **_k):
        return pytest.mark.skip(reason="hypothesis not installed")

    def settings(*_a, **_k):
        return lambda f: f

    class _NullStrategies:
        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _NullStrategies()

from repro.core import noise_tolerance as nt

SIGMAS = [0.25, 0.5, 1.0, 2.0, 4.0, 8.0]


def _ramp_eval(slope: float):
    """acc(sigma) = 1 - slope * sigma: crossing at 0.01 / slope."""
    def eval_fn(sigma, key):
        return 1.0 - slope * float(sigma)
    return eval_fn


# ---------------------------------------------------------------------------
# scalar reference properties
# ---------------------------------------------------------------------------
@settings(deadline=None, max_examples=25)
@given(slope=st.floats(1e-4, 0.5, allow_nan=False))
def test_crossing_bracketed_by_adjacent_grid_points(slope):
    key = jax.random.PRNGKey(0)
    res = nt.find_sigma_max(_ramp_eval(slope), SIGMAS, key, n_repeats=1)
    drop = res.rel_drop
    above = np.nonzero(drop > 0.01)[0]
    if len(above) == 0:
        assert res.sigma_max == SIGMAS[-1]
    elif above[0] == 0:
        assert res.sigma_max == SIGMAS[0]
    else:
        j = int(above[0])
        assert SIGMAS[j - 1] <= res.sigma_max <= SIGMAS[j]


@settings(deadline=None, max_examples=25)
@given(slope=st.floats(1e-3, 0.5),
       thr_lo=st.floats(0.002, 0.05), thr_hi=st.floats(0.002, 0.05))
def test_sigma_max_monotone_in_rel_drop_max(slope, thr_lo, thr_hi):
    """Loosening the accuracy budget never shrinks the tolerated sigma."""
    thr_lo, thr_hi = sorted((thr_lo, thr_hi))
    key = jax.random.PRNGKey(0)
    lo = nt.find_sigma_max(_ramp_eval(slope), SIGMAS, key,
                           rel_drop_max=thr_lo, n_repeats=1)
    hi = nt.find_sigma_max(_ramp_eval(slope), SIGMAS, key,
                           rel_drop_max=thr_hi, n_repeats=1)
    assert hi.sigma_max >= lo.sigma_max - 1e-12


def test_no_crossing_returns_last_grid_point():
    res = nt.find_sigma_max(_ramp_eval(0.0), SIGMAS, jax.random.PRNGKey(0),
                            n_repeats=1)
    assert res.sigma_max == SIGMAS[-1]


def test_single_point_grid_endpoints():
    """A one-sigma grid degenerates to that grid point either way."""
    for slope, want in ((0.5, 2.0), (0.0, 2.0)):
        res = nt.find_sigma_max(_ramp_eval(slope), [2.0],
                                jax.random.PRNGKey(0), n_repeats=1)
        assert res.sigma_max == want
    bres = nt.find_sigma_max_batched(_layered_eval([0.5, 0.0]), [2.0],
                                     jax.random.PRNGKey(0), n_layers=2,
                                     n_repeats=1)
    assert bres.sigma_max.tolist() == [2.0, 2.0]


def test_crossing_at_index_zero_returns_first_grid_point():
    res = nt.find_sigma_max(_ramp_eval(0.5), SIGMAS, jax.random.PRNGKey(0),
                            n_repeats=1)
    assert res.rel_drop[0] > 0.01
    assert res.sigma_max == SIGMAS[0]


def test_crossing_sigma_vectorized_matches_scalar_loop():
    rng = np.random.default_rng(0)
    sig = np.asarray(SIGMAS)
    drops = rng.uniform(0.0, 0.05, size=(32, len(sig)))
    batched = nt.crossing_sigma(sig, drops, 0.01)
    for i, drop in enumerate(drops):
        assert batched[i] == nt.crossing_sigma(sig, drop, 0.01)


# ---------------------------------------------------------------------------
# batched variant vs scalar, layer by layer
# ---------------------------------------------------------------------------
def _layered_eval(weights):
    """Deterministic per-layer drop: acc = 1 - sum_i w_i * sigma_i."""
    w = jnp.asarray(weights, jnp.float32)

    def eval_fn(sigma_vec, key):
        return 1.0 - jnp.sum(w * sigma_vec)
    return eval_fn


@settings(deadline=None, max_examples=10)
@given(weights=st.lists(st.floats(1e-3, 0.5), min_size=1, max_size=5))
def test_batched_matches_scalar_per_layer(weights):
    n_layers = len(weights)
    eval_fn = _layered_eval(weights)
    key = jax.random.PRNGKey(7)
    bres = nt.find_sigma_max_batched(eval_fn, SIGMAS, key,
                                     n_layers=n_layers, n_repeats=2)
    assert bres.sigma_max.shape == (n_layers,)
    assert bres.rel_drop.shape == (n_layers, len(SIGMAS))
    for l in range(n_layers):
        def scalar_l(s, k, l=l):
            sv = jnp.zeros(n_layers).at[l].set(s)
            return float(eval_fn(sv, k))
        sres = nt.find_sigma_max(scalar_l, SIGMAS,
                                 jax.random.fold_in(key, l), n_repeats=2)
        np.testing.assert_allclose(bres.sigma_max[l], sres.sigma_max,
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(bres.rel_drop[l], sres.rel_drop,
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(bres.acc_clean[l], sres.acc_clean,
                                   rtol=1e-6)


@settings(deadline=None, max_examples=10)
@given(weights=st.lists(st.floats(1e-3, 0.5), min_size=2, max_size=4),
       thr=st.floats(0.002, 0.05))
def test_batched_monotone_in_rel_drop_max(weights, thr):
    eval_fn = _layered_eval(weights)
    key = jax.random.PRNGKey(3)
    lo = nt.find_sigma_max_batched(eval_fn, SIGMAS, key, len(weights),
                                   rel_drop_max=0.5 * thr, n_repeats=1)
    hi = nt.find_sigma_max_batched(eval_fn, SIGMAS, key, len(weights),
                                   rel_drop_max=thr, n_repeats=1)
    assert (hi.sigma_max >= lo.sigma_max - 1e-12).all()


def test_batched_degenerate_endpoints():
    key = jax.random.PRNGKey(1)
    # layer 0 never crosses (w=0), layer 1 crosses before the first point
    res = nt.find_sigma_max_batched(_layered_eval([0.0, 0.9]), SIGMAS, key,
                                    n_layers=2, n_repeats=1)
    assert res.sigma_max[0] == SIGMAS[-1]
    assert res.sigma_max[1] == SIGMAS[0]


@settings(deadline=None, max_examples=8)
@given(weights=st.lists(st.floats(1e-3, 0.5), min_size=1, max_size=4),
       chunk=st.integers(1, 40))
def test_chunked_matches_unchunked(weights, chunk):
    """chunk_size (lax.map over vmapped chunks) is a pure memory knob: the
    padded-tail chunking must reproduce the flat vmap bit-for-bit."""
    eval_fn = _layered_eval(weights)
    key = jax.random.PRNGKey(5)
    full = nt.find_sigma_max_batched(eval_fn, SIGMAS, key,
                                     n_layers=len(weights), n_repeats=2)
    chunked = nt.find_sigma_max_batched(eval_fn, SIGMAS, key,
                                        n_layers=len(weights), n_repeats=2,
                                        chunk_size=chunk)
    np.testing.assert_array_equal(full.sigma_max, chunked.sigma_max)
    np.testing.assert_array_equal(full.rel_drop, chunked.rel_drop)
    np.testing.assert_array_equal(full.acc_clean, chunked.acc_clean)


def test_batched_keys_honoured():
    """A key-sensitive eval sees the scalar key schedule layer-by-layer."""
    def eval_fn(sigma_vec, key):
        # deterministic in (sigma, key): pseudo-noise from the key
        jitter = jax.random.uniform(key, ()) * 1e-3
        return 1.0 - 0.02 * jnp.sum(sigma_vec) - jitter

    key = jax.random.PRNGKey(11)
    n_layers = 3
    bres = nt.find_sigma_max_batched(eval_fn, SIGMAS, key,
                                     n_layers=n_layers, n_repeats=2)
    for l in range(n_layers):
        def scalar_l(s, k, l=l):
            sv = jnp.zeros(n_layers).at[l].set(s)
            return float(eval_fn(sv, k))
        sres = nt.find_sigma_max(scalar_l, SIGMAS,
                                 jax.random.fold_in(key, l), n_repeats=2)
        np.testing.assert_allclose(bres.sigma_max[l], sres.sigma_max,
                                   rtol=1e-5, atol=1e-5)


def test_jit_cache_releases_dead_eval_fns():
    """The jitted-runner cache is keyed weakly by eval_fn; the cached
    runner must not close over its own key (that pinned every eval_fn —
    and its jit executables — forever).  Dropping the last strong
    reference must actually evict the entry."""
    import gc
    import weakref

    def make_eval():
        def eval_fn(sigma_vec, key):
            return 1.0 - 0.1 * jnp.sum(sigma_vec)
        return eval_fn

    key = jax.random.PRNGKey(0)
    for chunk in (None, 4):          # both runner flavours must release
        fn = make_eval()
        nt.find_sigma_max_batched(fn, SIGMAS, key, n_layers=2,
                                  n_repeats=1, chunk_size=chunk)
        assert fn in nt._JIT_CACHE   # cached while alive (reuse contract)
        ref = weakref.ref(fn)
        del fn
        gc.collect()
        assert ref() is None, "jit cache still pins a dead eval_fn"


@pytest.mark.parametrize("probes", [1, 7, 13])
def test_top1_accuracy_vmapped_matches_host(probes):
    """`top1_accuracy` under vmap (how the batched sweep calls an eval_fn)
    equals the host's first-index argmax accuracy for every probe count,
    ties included."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    logits = jnp.round(jax.random.normal(k1, (probes, 8, 16, 32)) * 2.0)
    labels = jax.random.randint(k2, (8, 16), 0, 32)
    got = jax.jit(jax.vmap(lambda lo: nt.top1_accuracy(lo, labels)))(logits)
    want = (np.asarray(logits).argmax(-1) == np.asarray(labels)).mean((1, 2))
    np.testing.assert_array_equal(np.asarray(got), want.astype(np.float32))
