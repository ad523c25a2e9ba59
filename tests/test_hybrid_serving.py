"""granite-4.0-h-micro's hybrid stack (Mamba2 mixers beside GQA attention)
through the serving engine's programs, at smoke size on the CPU.

The slot path is the engine's: the bucketed prefill with the true length
(`model_api` prefill), the engine's insert step into a row of the
per-slot state, and the single-token decode step.  It is compared by
logits with a plain full forward of the same weights (no cache: the
chunked SSD and full causal attention over the whole sequence).  These
comparisons run in f32, so that what is compared is the algorithm and
not bf16 rounding: the tolerance, 1e-5 on logits of order 0.01-0.1 (the
smoke model's; errors read 5e-8), covers f32 sums taken in another order
(the chunked SSD against the per-token recurrence, a flash prefill
against flash decode); bf16 arithmetic (1.6e-3 here), a stale state (6e-2)
or a state stopped at the wrong position misses it.
The engine itself runs in bf16 as deployed: its served tokens are
compared bit for bit between a reused slot and a fresh engine.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as cfgs
from repro.launch import steps, td_cli
from repro.launch.scheduler import ContinuousBatchingEngine, Request
from repro.models import common, get_api, transformer
from repro.roofline import model as roofline_model

ARCH = td_cli.apply_td_args(cfgs.get_smoke("granite-4.0-h-micro"),
                            "precise", None)
CFG = ARCH.model
POL = common.resolve_arch_policy(ARCH)
API = get_api(CFG)
CAPACITY, S_CACHE = 3, 96
TOL = 1e-5      # f32 logits, sums reassociated (module docstring)


@pytest.fixture(scope="module")
def params():
    return API["init"](jax.random.key(0), CFG, POL, jnp.float32)


def _seq(n: int, salt: int = 0) -> jnp.ndarray:
    return ((jnp.arange(n, dtype=jnp.int32) * 37 + 11 + salt * 101)
            % CFG.vocab)


@jax.jit
def _prefill(params, toks, true_len):
    return API["prefill"](params, {"tokens": toks}, CFG, POL,
                          s_cache=toks.shape[1], cache_dtype=jnp.float32,
                          true_len=true_len)


@jax.jit
def _decode(params, tok, state):
    return API["decode_step"](params, tok, state, CFG, POL)


def _broken_insert():
    """The engine's insert with the recurrent leaves left as they were:
    an admission that does not overwrite the slot's state."""
    real = steps.build_insert_step()

    def insert(dst, src, slot, length):
        new = real(dst, src, slot, length)
        keep = {jax.tree_util.DictKey("conv"), jax.tree_util.DictKey("ssm")}
        return jax.tree_util.tree_map_with_path(
            lambda path, n, d: d if path[-1] in keep else n, new, dst)
    return insert


class Slots:
    """A per-slot decode state of CAPACITY rows, filled as the engine
    fills it: prefill at a bucket, insert at a slot, then decode steps of
    the whole batch."""

    def __init__(self, params, insert=None):
        self.params = params
        self.insert = jax.jit(insert or steps.build_insert_step())
        self.state = {"layers": transformer.init_caches(
            CAPACITY, S_CACHE, CFG, jnp.float32, pol=POL, per_row_idx=True),
            "enc_out": None}
        self.tok = jnp.zeros((CAPACITY, 1), jnp.int32)

    def admit(self, slot: int, prompt, pad: int):
        n = len(prompt)
        padded = jnp.zeros((1, pad), jnp.int32).at[0, :n].set(prompt)
        logits, pstate = _prefill(self.params, padded,
                                  jnp.asarray(n, jnp.int32))
        self.state = self.insert(self.state, pstate,
                                 jnp.asarray(slot, jnp.int32),
                                 jnp.asarray(n, jnp.int32))
        return logits[:, 0], pstate

    def decode(self, feed: dict):
        """One step; `feed` maps slot -> the token it is given."""
        for slot, t in feed.items():
            self.tok = self.tok.at[slot, 0].set(t)
        logits, self.state = _decode(self.params, self.tok, self.state)
        return logits


def _teacher_forced(params, slots, slot, seq, n_prompt, pad, others=None):
    """Admit seq[:n_prompt] into `slot`, decode the rest of seq through it
    (other slots decoding their own feeds), and return the largest logit
    error against the full forward of seq."""
    full, _, _ = transformer.forward(params, {"tokens": seq[None]}, CFG, POL)
    first, _ = slots.admit(slot, seq[:n_prompt], pad)
    errs = [float(jnp.abs(first[0] - full[0, n_prompt - 1]).max())]
    for t in range(n_prompt, len(seq)):
        feed = {slot: seq[t], **(others(t) if others else {})}
        lg = slots.decode(feed)
        errs.append(float(jnp.abs(lg[slot] - full[0, t]).max()))
    return max(errs)


@pytest.mark.parametrize("n_prompt,pad", [(21, 32), (5, 32), (32, 32),
                                          (2, 48)])
def test_prefill_then_decode_matches_full_forward(params, n_prompt, pad):
    """A prompt shorter than its bucket (pad positions after the true
    length), one that fills it, and one shorter than the conv window."""
    slots = Slots(params)
    # another request decodes in slot 0 meanwhile, as in the engine
    slots.admit(0, _seq(9, salt=3), 16)
    err = _teacher_forced(params, slots, 2, _seq(n_prompt + 12), n_prompt,
                          pad, others=lambda t: {0: _seq(40, 3)[t]})
    assert err < TOL, err


def test_pad_invariance(params):
    """The same prompt at two buckets: the same state and logits."""
    prompt = _seq(21)
    outs = []
    for pad in (32, 48):
        padded = jnp.zeros((1, pad), jnp.int32).at[0, :21].set(prompt)
        outs.append(_prefill(params, padded, jnp.asarray(21, jnp.int32)))
    (lg_a, st_a), (lg_b, st_b) = outs
    assert float(jnp.abs(lg_a - lg_b).max()) < TOL
    for a, b, mix in zip(st_a["layers"], st_b["layers"],
                         (CFG.mixer_at(i) for i in range(CFG.n_layers))):
        if mix == "mamba2":
            for k in ("conv", "ssm"):
                assert float(jnp.abs(a[k] - b[k]).max()) < TOL, (k, mix)
        else:
            for k in ("k", "v"):     # the KV up to the true length
                assert float(jnp.abs(a[k][:, :21] - b[k][:, :21]).max()) \
                    < TOL
    # and the state really moved from its zero start
    assert float(jnp.abs(st_a["layers"][0]["ssm"]).max()) > 1e-3


def _reuse_error(params, insert=None):
    """A long request through slot 1, then a short one admitted into the
    same slot: its logits against the full forward."""
    slots = Slots(params, insert)
    _teacher_forced(params, slots, 1, _seq(40, salt=5), 30, 32)
    return _teacher_forced(params, slots, 1, _seq(14, salt=7), 6, 16)


def test_slot_reuse_matches_full_forward(params):
    assert _reuse_error(params) < TOL


def test_admission_that_keeps_stale_state_fails(params):
    """The same comparison with an insert that leaves the slot's conv
    and SSM state as the last request left them: far outside TOL."""
    assert _reuse_error(params, _broken_insert()) > 100 * TOL


def _engine(capacity=1):
    return ContinuousBatchingEngine(ARCH, capacity=capacity, s_cache=64,
                                    prompt_pad=32, seed=0, kv_block=8)


def test_reused_slot_serves_as_a_fresh_engine():
    """Through the engine itself (bf16, compiled programs): a short
    request served in the slot a long one just left gets the same tokens,
    bit for bit, as on a fresh engine."""
    long = Request(rid=0, prompt=np.asarray(_seq(30, 5)), max_new_tokens=20)
    short = dict(prompt=np.asarray(_seq(7, 9)), max_new_tokens=8)
    used = _engine()
    used.run([long, Request(rid=1, **short)])
    fresh = _engine()
    fresh.run([Request(rid=1, **short)])
    assert used.done[1].generated == fresh.done[1].generated
    assert len(fresh.done[1].generated) == 8


@pytest.mark.parametrize("name", ["rwkv6-1.6b", "zamba2-1.2b"])
def test_other_recurrent_mixers_are_refused(name):
    """RWKV6 and shared attention (zamba2) are still refused."""
    arch = td_cli.apply_td_args(cfgs.get_smoke(name), "precise", None)
    with pytest.raises(ValueError, match="attention and Mamba2"):
        ContinuousBatchingEngine(arch, capacity=1, s_cache=32)


def test_plan_counts_recurrent_state():
    """The slot planner counts what the engine stores per slot: KV of the
    attention layers and conv + SSM state of the Mamba2 layers."""
    eng = _engine(capacity=2)
    stored = sum(a.nbytes for c in eng._state["layers"]
                 for k, a in c.items() if k != "idx")
    assert eng.kv_plan.bytes_per_slot * eng.capacity == stored
    ssm = [c["ssm"] for c in eng._state["layers"] if "ssm" in c]
    assert {a.dtype for a in ssm} == {jnp.dtype(jnp.float32)}
    assert eng.state_bytes * eng.capacity == sum(
        c[k].nbytes for c in eng._state["layers"] if "ssm" in c
        for k in ("conv", "ssm"))
    # at granite-4.0-h-micro's widths: 36 x (3 x 4352 bf16 + 64x64x128
    # f32) of state beside 4 layers of K and V, heads padded to 128 lanes
    full = roofline_model.plan_kv_cache(cfgs.get("granite-4.0-h-micro")
                                        .model, 32, 2560, block=64)
    assert full.bytes_per_slot == 36 * (3 * 4352 * 2 + 64 * 64 * 128 * 4) \
        + 2 * 4 * 2560 * 8 * 128 * 2


def test_spans_carry_state_bytes(tmp_path):
    """`engine.insert` carries one slot's recurrent state, `engine.decode`
    that of its occupied rows."""
    import glob
    from jax.profiler import ProfileData
    eng = _engine(capacity=2)
    eng.warmup()
    reqs = [Request(rid=i, prompt=np.asarray(_seq(n, i)), max_new_tokens=g)
            for i, (n, g) in enumerate([(5, 3), (12, 5), (3, 2)])]
    with jax.profiler.trace(str(tmp_path)):
        eng.run(reqs)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(e.name, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name in ("engine.insert", "engine.decode")]
    inserts = [st for n, st in spans if n == "engine.insert"]
    decodes = [st for n, st in spans if n == "engine.decode"]
    assert len(inserts) == 3 and decodes
    assert {st["state_bytes"] for st in inserts} == {eng.state_bytes} \
        and eng.state_bytes > 0
    assert all(st["state_bytes"] == st["rows"] * eng.state_bytes
               for st in decodes)


def _sha(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()


# sha256 of qwen2.5-3b's smoke forward logits, and of its serving
# programs' outputs (prefill tokens, decode tokens, the final per-slot
# state), computed before the hybrid's changes to the shared code
QWEN_LOGITS_SHA = \
    "ad683e32f75fad14a5e0a0971928d0cb29d322ce3cb990315105c39ba928013d"
QWEN_SERVE_SHA = \
    "6244e32de142077f800a1819abd85d7ef6355edaf1324db8a90dba39a8372cd0"


def test_qwen_programs_are_unchanged():
    """The multipliers, the NoPE flag, the softmax-scale fold and the
    recurrent-state plumbing leave a model that uses none of them
    bit-identical."""
    arch = td_cli.apply_td_args(cfgs.get_smoke("qwen2.5-3b"), "precise",
                                None)
    cfg = arch.model
    pol = common.resolve_arch_policy(arch)
    params = get_api(cfg)["init"](jax.random.key(0), cfg, pol, jnp.bfloat16)
    toks = (jnp.arange(24, dtype=jnp.int32) * 37 + 5) % cfg.vocab
    logits, _, _ = transformer.forward(params, {"tokens": toks[None]}, cfg,
                                       pol)
    prefill = jax.jit(steps.build_ragged_prefill_step(arch, 16))
    insert = jax.jit(steps.build_insert_step())
    serve = jax.jit(steps.build_serve_step(
        arch, steps.ShapeCfg("serve", 64, 3, "decode")))
    state = {"layers": transformer.init_caches(3, 64, cfg, jnp.bfloat16,
                                               pol=pol, per_row_idx=True),
             "enc_out": None}
    tok = jnp.zeros((3, 1), jnp.int32)
    firsts = []
    for slot, n in ((1, 11), (0, 16), (2, 3)):
        padded = jnp.zeros((1, 16), jnp.int32).at[0, :n].set(toks[:n] + slot)
        t, ps = prefill(params, padded, jnp.asarray(n, jnp.int32))
        state = insert(state, ps, jnp.asarray(slot, jnp.int32),
                       jnp.asarray(n, jnp.int32))
        tok = tok.at[slot].set(t[0])
        firsts.append(t)
    outs = []
    for _ in range(4):
        tok, state = serve(params, tok, state)
        outs.append(tok)
    assert _sha(logits) == QWEN_LOGITS_SHA
    assert _sha(*firsts, *outs, *jax.tree.leaves(state)) == QWEN_SERVE_SHA
