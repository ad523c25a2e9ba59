"""Noise-tolerance back-annotation (paper Fig. 10).

The paper injects Gaussian noise into the convolution outputs of LSQ-4bit
quantized ResNet20/CIFAR10 and ResNet18/ImageNet, measures the relative
accuracy drop 1 - Acc(sigma)/Acc(0), and defines sigma_array_max as the noise
level where the drop crosses 1 %.  That sigma is then fed back into the
design space (Fig. 11) to relax R and the TDC (q).

Two entry tiers:

  * `find_sigma_max`            -- scalar reference: one eval_fn(sigma, key)
                                   call per (sigma, repeat), python loop.
  * `find_sigma_max_batched`    -- the whole (layers x sigma-grid x repeats)
                                   sweep as ONE vmapped+jitted eval call.
                                   eval_fn takes a per-layer sigma *vector*
                                   (probe vectors are one-hot: layer l at
                                   sigma s means sigma * e_l), so per-layer
                                   sigma_array_max for every layer of a
                                   network comes out of a single device
                                   program -- the vector feeds straight into
                                   tdsim.policy.solve_network_policies
                                   (Fig. 10 -> Fig. 11 in one pass).

Both tiers share `crossing_sigma`, the vectorized interpolated 1 %-crossing,
and the same key-splitting scheme: batched layer l uses
fold_in(key, l) split exactly like the scalar call, so with a deterministic
or key-faithful eval_fn the two paths agree layer-by-layer to float
tolerance (property-tested).

This module is model-agnostic: it takes any eval function (built from the
tdsim layer for CNNs *and* -- beyond the paper -- for the assigned LM
architectures, where "accuracy" is next-token top-1).
"""
from __future__ import annotations

import dataclasses
import weakref
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

# jitted probe-runners keyed by eval_fn (weak: dies with the eval), then by
# chunk config — repeated sweeps over the same eval reuse the compiled
# program instead of retracing per call.  The cached runners must NOT hold
# a strong reference to eval_fn: a WeakKeyDictionary value that closes over
# its own key pins the key forever, turning the "weak" cache into a leak
# (every eval_fn ever swept, plus its jit executables, stays live).  The
# runners therefore close over a weakref and re-deref at trace time.
_JIT_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _jitted_runner(eval_fn, chunk_size, mesh=None):
    per_fn = _JIT_CACHE.setdefault(eval_fn, {})
    fn = per_fn.get((chunk_size, mesh))
    if fn is None:
        ref = weakref.ref(eval_fn)

        def call(v, k):
            target = ref()
            if target is None:  # pragma: no cover — key died mid-trace
                raise ReferenceError("eval_fn was garbage-collected")
            return jax.vmap(target)(v, k)

        body = call
        if chunk_size is not None:
            def body(cv, ck):
                return jax.lax.map(lambda c: call(c[0], c[1]), (cv, ck))
        if mesh is not None:
            # Pallas kernels cannot be partitioned automatically: each
            # device runs the eval on its own slice of the probe axis
            from repro.launch.mesh import dp_axes
            lead = (None,) if chunk_size is not None else ()
            spec = P(*lead, dp_axes(mesh))
            body = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                                 out_specs=spec, check_vma=False)
        fn = jax.jit(body)
        per_fn[(chunk_size, mesh)] = fn
    return fn


@dataclasses.dataclass(frozen=True)
class NoiseToleranceResult:
    sigmas: np.ndarray          # grid of injected sigma (output-LSB units)
    rel_drop: np.ndarray        # 1 - acc(sigma)/acc(0)
    acc_clean: float
    sigma_max: float            # interpolated 1 %-drop crossing (Fig. 10b)


@dataclasses.dataclass(frozen=True)
class BatchedNoiseToleranceResult:
    """Per-layer Fig. 10 sweep from one vmapped eval call."""
    sigmas: np.ndarray          # (S,) shared sigma grid
    rel_drop: np.ndarray        # (L, S) per-layer relative drop curves
    acc_clean: np.ndarray       # (L,) clean accuracy per layer probe
    sigma_max: np.ndarray       # (L,) interpolated 1 %-crossings
    n_evals: int                # evals folded into the single batched call

    def layer(self, l: int) -> NoiseToleranceResult:
        """Scalar-result view of one layer (for parity checks / reports)."""
        return NoiseToleranceResult(self.sigmas, self.rel_drop[l],
                                    float(self.acc_clean[l]),
                                    float(self.sigma_max[l]))


def top1_accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean of argmax(logits) == labels, the accuracy an eval_fn returns.

    The logits pass an optimization barrier before the argmax.  Without
    it the TPU compiler (jax 0.9.0 on a v5e) fuses the argmax into the
    producing matmul and, at some vmapped probe counts (5, 7, 13, 26 in
    the Fig. 10 LM sweep), returns an index that is not a maximum for
    about half the rows, so a probe's accuracy depended on how many
    probes shared its program."""
    logits = jax.lax.optimization_barrier(logits)
    return (jnp.argmax(logits, -1) == labels).mean()


def crossing_sigma(sigmas: np.ndarray, rel_drop: np.ndarray,
                   rel_drop_max: float = 0.01) -> np.ndarray:
    """Vectorized first-crossing of the drop threshold with linear
    interpolation; `rel_drop` is (..., S) over the shared (S,) sigma grid.

    Degenerate cases match the scalar reference: no crossing -> last grid
    point; crossing already at index 0 -> first grid point.
    """
    sig = np.asarray(sigmas, np.float64)
    drop = np.asarray(rel_drop, np.float64)
    above = drop > rel_drop_max                       # (..., S)
    any_above = above.any(axis=-1)
    j = np.argmax(above, axis=-1)                     # first True (0 if none)
    # safe gather index: the interpolated value is only selected when
    # 1 <= j <= S-1, so clamping covers the endpoint branches (and S == 1)
    jm = np.minimum(np.maximum(j, 1), len(sig) - 1)
    d0 = np.take_along_axis(drop, (jm - 1)[..., None], axis=-1)[..., 0]
    d1 = np.take_along_axis(drop, jm[..., None], axis=-1)[..., 0]
    t = (rel_drop_max - d0) / np.maximum(d1 - d0, 1e-12)
    interp = sig[jm - 1] + t * (sig[jm] - sig[jm - 1])
    out = np.where(j == 0, sig[0], interp)
    return np.where(any_above, out, sig[-1])


def find_sigma_max(eval_fn: Callable[[float, jax.Array], float],
                   sigmas: Sequence[float],
                   key: jax.Array,
                   rel_drop_max: float = 0.01,
                   n_repeats: int = 3) -> NoiseToleranceResult:
    """Sweep the sigma grid, average repeated noisy evals, interpolate the
    crossing of the relative-accuracy-drop threshold (paper: 1 %)."""
    keys = jax.random.split(key, len(sigmas) * n_repeats + 1)
    acc_clean = float(eval_fn(0.0, keys[-1]))
    accs = []
    for i, s in enumerate(sigmas):
        vals = [float(eval_fn(float(s), keys[i * n_repeats + r]))
                for r in range(n_repeats)]
        accs.append(float(np.mean(vals)))
    accs = np.asarray(accs)
    drop = 1.0 - accs / max(acc_clean, 1e-9)
    sig = np.asarray(list(sigmas), dtype=np.float64)
    sigma_max = float(crossing_sigma(sig, drop, rel_drop_max))
    return NoiseToleranceResult(sig, drop, acc_clean, sigma_max)


def probe_vectors(sigmas: Sequence[float], n_layers: int,
                  n_repeats: int) -> np.ndarray:
    """(L, S*R + 1, L) per-layer sigma vectors: row (i*R + r) of layer l is
    sigmas[i] * e_l, the last row is the all-zero clean probe."""
    sig = np.asarray(list(sigmas), np.float64)
    s, l, r = len(sig), int(n_layers), int(n_repeats)
    vecs = np.zeros((l, s * r + 1, l), np.float64)
    for li in range(l):
        vecs[li, : s * r, li] = np.repeat(sig, r)
    return vecs


def _run_probes(eval_fn, flat_v: jax.Array, flat_k: jax.Array,
                chunk_size: int | None, mesh=None) -> jax.Array:
    """Evaluate all (probe, key) pairs: one flat vmap, or -- with
    `chunk_size` -- a lax.map over equal-size vmapped chunks so only
    chunk_size evals are live at once.

    With `mesh`, the probe axis (the within-chunk axis when chunked) is
    split over the mesh data axis by `jax.shard_map` -- each probe is an
    independent eval, so the sweep data-parallelizes across devices with no
    cross-probe collectives and bit-identical per-probe results.  The axis
    is padded (with repeats of the first probe, discarded) to a multiple
    of the data-axis size, so every device gets an equal share.
    """
    t = flat_v.shape[0]
    if chunk_size is not None and chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
    n_dev = 1
    if mesh is not None:
        from repro.launch.mesh import dp_size
        n_dev = dp_size(mesh)
    chunked = chunk_size is not None and chunk_size < t
    unit = -(-(chunk_size if chunked else t) // n_dev) * n_dev
    pad = (-t) % unit
    if pad:
        flat_v = jnp.concatenate(
            [flat_v, jnp.broadcast_to(flat_v[:1], (pad,) + flat_v.shape[1:])])
        flat_k = jnp.concatenate(
            [flat_k, jnp.broadcast_to(flat_k[:1], (pad,) + flat_k.shape[1:])])
    if not chunked:
        return _jitted_runner(eval_fn, None, mesh)(flat_v, flat_k)[:t]
    n_chunks = (t + pad) // unit
    cv = flat_v.reshape((n_chunks, unit) + flat_v.shape[1:])
    ck = flat_k.reshape((n_chunks, unit) + flat_k.shape[1:])
    return _jitted_runner(eval_fn, unit, mesh)(cv, ck).reshape(-1)[:t]


def find_sigma_max_batched(eval_fn: Callable[[jax.Array, jax.Array], jax.Array],
                           sigmas: Sequence[float],
                           key: jax.Array,
                           n_layers: int,
                           rel_drop_max: float = 0.01,
                           n_repeats: int = 3,
                           chunk_size: int | None = None,
                           mesh=None
                           ) -> BatchedNoiseToleranceResult:
    """Per-layer sigma_array_max for all layers in ONE vmapped+jitted call.

    eval_fn(sigma_vec, key) -> scalar accuracy must be jax-traceable, where
    sigma_vec is a (n_layers,) array of per-layer injected noise std (in
    output-LSB units).  The sweep probes one layer at a time (one-hot
    sigma vectors) over the full (layers x sigma-grid x repeats [+ clean])
    product, vmapped into a single device program -- no python loop, no
    per-sigma recompile.

    Key discipline matches the scalar path exactly: layer l draws
    split(fold_in(key, l), S*R + 1), eval (i, r) uses keys[i*R + r] and the
    clean eval uses keys[-1] -- so a scalar `find_sigma_max` run of layer l
    with key fold_in(key, l) sees identical (sigma, key) pairs.

    `chunk_size` bounds live memory for large (transformer-scale) evals:
    the flat probe axis is processed `chunk_size` probes at a time via
    `lax.map` over equal chunks (the tail is padded with repeats of the
    first probe and discarded), each chunk vmapped -- still one jitted
    device program, results bit-identical to the unchunked call.

    `mesh` splits the probe batch over the mesh data axis with
    `jax.shard_map` (the big-LM per-layer sweep becomes mesh-parallel; the
    Pallas TD kernel cannot be partitioned automatically): probes are
    independent evals, so sharding composes with `chunk_size` (the
    within-chunk axis shards, rounded up to a multiple of the data axis;
    chunks stay sequential) and results are bit-identical to the unsharded
    call.
    """
    sig = np.asarray(list(sigmas), np.float64)
    s, l, r = len(sig), int(n_layers), int(n_repeats)
    per = s * r + 1
    vecs = probe_vectors(sig, l, r)                       # (L, per, L)
    layer_keys = jnp.stack([jax.random.split(jax.random.fold_in(key, li),
                                             per) for li in range(l)])
    flat_v = jnp.asarray(vecs.reshape(l * per, l), jnp.float32)
    flat_k = layer_keys.reshape((l * per,) + layer_keys.shape[2:])
    accs = _run_probes(eval_fn, flat_v, flat_k, chunk_size, mesh)
    accs = np.asarray(accs, np.float64).reshape(l, per)
    acc_clean = accs[:, -1]
    acc = accs[:, : s * r].reshape(l, s, r).mean(axis=-1)
    drop = 1.0 - acc / np.maximum(acc_clean[:, None], 1e-9)
    sigma_max = crossing_sigma(sig, drop, rel_drop_max)
    return BatchedNoiseToleranceResult(sig, drop, acc_clean, sigma_max,
                                       n_evals=l * per)
