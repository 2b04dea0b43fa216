"""The plain reference against the program in float32 on the CPU, at
two tiny shapes: the 7B cell's (4 query heads to 2 KV heads) and the
15B cell's block (12 query heads to 1 KV head, FFN four times the
hidden size, 10 layers, capture layers 2, 5 and 7): logits, capture
features and three draft train steps."""
import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SHAPES = ["tiny", "tiny-15b"]
STOPS = (4, 11, 20)      # prefix lengths the program scores alone


def _doc(name):
    with open(os.path.join(DATA, name + ".json")) as f:
        doc = json.load(f)
    doc["name"] = name
    doc["torch_dtype"] = "float32"
    return doc, importlib.import_module("bench.configs." + doc["reference"])


def _program32(doc, ref, seed):
    from repro.models import transformer as T
    cfg = dataclasses.replace(ref.program_config(doc), dtype="float32")
    params = weights.make(jax.eval_shape(
        lambda: T.init(cfg, jax.random.key(0))), seed, "target")
    return cfg, params


def test_15b_block_shape():
    doc, ref = _doc("tiny-15b")
    cfg = ref.program_config(doc)
    assert cfg.num_heads // cfg.num_kv_heads == 12
    assert cfg.d_ff == 4 * cfg.d_model
    assert list(cfg.captures) == doc["draft"]["capture_layers"] == [2, 5, 7]


@pytest.mark.parametrize("name", SHAPES)
def test_reference_logits_and_features_match_program(name):
    from repro.models import transformer as T
    doc, ref = _doc(name)
    seed = 2 ** 31 + 17
    cfg, params = _program32(doc, ref, seed)
    toks = np.random.default_rng(3).integers(0, cfg.vocab_size, 20)
    with jax.default_matmul_precision("highest"):
        out = T.prefill(cfg, params, jnp.asarray(toks[None]))
        want = [np.asarray(T.prefill(cfg, params, jnp.asarray(
            toks[None, :t]), want_caps=False)["logits"][0])
            for t in STOPS]
    keep = [np.arange(3, 20)]
    logits, feats = next(ref.forward(doc, seed)([toks], keep=keep))
    np.testing.assert_allclose(np.asarray(logits)[[t - 1 for t in STOPS]],
                               np.stack(want), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(feats, np.asarray(out["captures"][0, 3:]),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", SHAPES)
def test_reference_draft_steps_match_program(name):
    from repro.core import eagle
    from repro.training.draft_trainer import DraftTrainer
    doc, ref = _doc(name)
    seed = 2 ** 31 + 19
    cfg, params = _program32(doc, ref, seed)
    dcfg = eagle.draft_config(cfg)
    dparams = weights.make(jax.eval_shape(
        lambda: eagle.draft_init(dcfg, jax.random.key(0))), seed, "draft")
    tr = DraftTrainer(cfg, dcfg, params["embed"])
    rng = np.random.default_rng(4)
    batches = [(rng.standard_normal((4, 8, 3 * cfg.d_model)).astype(
        np.float32), rng.integers(0, cfg.vocab_size, (4, 8)).astype(
        np.int32)) for _ in range(3)]
    p, opt, losses = dparams, tr.opt.init(dparams), []
    with jax.default_matmul_precision("highest"):
        for i, (x, t) in enumerate(batches):
            p, opt, loss, _ = tr._step(p, opt, params["embed"],
                                       jnp.asarray(x), jnp.asarray(t),
                                       jnp.int32(i))
            losses.append(float(loss))
            if i == 0:
                grad = {weights.path_str(k): float(jnp.linalg.norm(
                    v.ravel())) / (1 - doc["draft_training"]["beta1"])
                    for k, v in jax.tree_util.tree_flatten_with_path(
                        opt["m"])[0]}
    change = {weights.path_str(k): float(jnp.linalg.norm((a - b).ravel()))
              for (k, a), (_, b) in zip(
                  jax.tree_util.tree_flatten_with_path(p)[0],
                  jax.tree_util.tree_flatten_with_path(dparams)[0])}
    want = ref.draft_train(doc, seed, batches)
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-4)
    assert set(grad) == set(want["grad"]) == set(change)
    for k in grad:
        np.testing.assert_allclose(grad[k], want["grad"][k], rtol=1e-3,
                                   atol=1e-7)
        np.testing.assert_allclose(change[k], want["change"][k],
                                   rtol=1e-3, atol=1e-7)
