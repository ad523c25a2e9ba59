"""The family lookup (`refs.load`): a configuration brings its weight
draw, reference, stated keys and sizes by its `model_type`; the qwen2
family's numbers are pinned to those of the single-model harness it
replaced."""
import hashlib
import sys

import jax
import numpy as np
import pytest

import refs
import renamed_family
import run as harness
import smoke
from work import decode_gqa, model

SEED = 2 ** 31 + 77

# sha256 of the smoke configuration's weights (leaves in tree order) and
# of its reference logits on `TOKS`, positions 5-12, computed with the
# harness before the families (`chipbench/model.py`)
WEIGHTS_SHA = \
    "54e7679b25704910a65af5f6bcd4b910b447bab2702f5f500736b89758feba7c"
LOGITS_SHA = {
    False: "4e3b1e10a5737ef073846951033c5b2fa7a8d76b3278e57076ba9294ce717dd7",
    True: "52f2ae85738670296b0c8f1209eaabc9014aff625501ca9a453e80c305e37337"}


def _sha(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.fixture
def renamed(monkeypatch):
    """The test-only family put in as `refs.qwen2_renamed`, and the smoke
    configuration in its keys."""
    monkeypatch.setitem(sys.modules, f"refs.{renamed_family.MODEL_TYPE}",
                        renamed_family)
    return renamed_family.rename(smoke.arch_and_cfg()[1])


def test_qwen2_weights_are_pinned():
    _, cfg = smoke.arch_and_cfg()
    params = refs.load(cfg).make_weights(cfg, harness.seed_words(SEED))
    assert _sha(*jax.tree.leaves(params)) == WEIGHTS_SHA


@pytest.mark.parametrize("lowp", [False, True])
def test_qwen2_reference_logits_are_pinned(lowp):
    _, cfg = smoke.arch_and_cfg()
    fam = refs.load(cfg)
    params = fam.make_weights(cfg, harness.seed_words(SEED))
    toks = ((np.arange(32) * 7919 + 13) % cfg["vocab_size"]).astype(np.int32)
    lg = fam.Reference(cfg, 32, 8).logits(params, toks, 5, 13, lowp=lowp)
    assert lg.shape == (8, cfg["vocab_size"])
    assert _sha(lg) == LOGITS_SHA[lowp]


def test_unknown_model_type_fails_before_weights(monkeypatch):
    def built(*_a, **_k):
        raise AssertionError("the engine was built for an unknown family")
    monkeypatch.setattr(harness, "build_arch", built)
    _, cfg = smoke.arch_and_cfg()
    cfg["model_type"] = "no_such_family"
    with pytest.raises(ModuleNotFoundError,
                       match="chipbench/refs/no_such_family.py"):
        smoke.run("poisson", cfg=cfg)


def test_a_family_the_harness_has_never_seen(renamed):
    """Every published key renamed but `vocab_size`: the run reads them
    only through the family, and its served tokens check against the
    family's reference."""
    assert not {"hidden_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "intermediate_size", "rope_theta",
                "rms_norm_eps", "tie_word_embeddings"} & set(renamed)
    out = smoke.run("poisson", seed=2 ** 31 + 91, seconds=3.0, cfg=renamed)
    assert out["checks"]["tokens_checked"]["value"] > 0
    assert out["correct"], out["checks"]


def test_work_counts_follow_the_family(renamed):
    _, cfg = smoke.arch_and_cfg()
    kv = [5, 17, 100]
    assert model.decode_flops(renamed, kv) == model.decode_flops(cfg, kv)
    assert decode_gqa.work(renamed, kv) == decode_gqa.work(cfg, kv)
    assert refs.load(renamed).dims(renamed) == refs.load(cfg).dims(cfg)


@pytest.mark.parametrize("family", ["qwen2", "renamed"])
def test_a_departing_program_is_refused(family, renamed):
    """The program is held to every key its family states, nested ones
    too: one head too many in the configuration and the run stops."""
    arch, cfg = smoke.arch_and_cfg()
    if family == "renamed":
        cfg, key = renamed, "attn_config.kv_n_heads"
    else:
        key = "num_key_value_heads"
    assert harness.build_arch(cfg, arch) is arch
    smoke.assign(cfg, {key: refs.at(cfg, key) + 1})
    with pytest.raises(RuntimeError, match=key):
        harness.build_arch(cfg, arch)
