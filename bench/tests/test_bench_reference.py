"""The plain reference against the program at a tiny size on the CPU,
on weights the benchmark makes: same logits in float32, and the weights
the reference makes layer by layer equal the tree made in one call."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.configs import starcoder2

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _tiny(dtype):
    with open(os.path.join(DATA, "tiny.json")) as f:
        doc = json.load(f)
    doc["name"] = "tiny"
    doc["torch_dtype"] = dtype
    return doc


def test_layer_weights_made_alone_equal_the_tree():
    from repro.models import transformer as T
    doc = _tiny("bfloat16")
    cfg = starcoder2.program_config(doc)
    tree = weights.make(jax.eval_shape(
        lambda: T.init(cfg, jax.random.key(0))), 2 ** 31 + 9, "target")
    root = weights.root_key(2 ** 31 + 9, "target")
    for l in range(cfg.num_layers):
        got = weights.leaf(root, "body/pos0/ffn/w_up",
                           (cfg.d_model, cfg.d_ff), jnp.bfloat16, l)
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(tree["body"]["pos0"]["ffn"]["w_up"][l], np.float32))


def test_reference_matches_program_forward():
    from repro.models import transformer as T
    doc = _tiny("float32")
    cfg = starcoder2.program_config(doc)
    cfg = dataclasses.replace(cfg, dtype="float32")
    seed = 123
    params = weights.make(jax.eval_shape(
        lambda: T.init(cfg, jax.random.key(0))), seed, "target")
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, 24)
    with jax.default_matmul_precision("highest"):
        want = []
        for t in range(4, toks.size + 1):
            out = T.prefill(cfg, params, jnp.asarray(toks[None, :t]),
                            want_caps=False)
            want.append(np.asarray(out["logits"][0]))
    got = np.asarray(next(starcoder2.forward(doc, seed)([toks]))[0])
    np.testing.assert_allclose(got[3:], np.stack(want), rtol=2e-4,
                               atol=2e-4)


def _program32(doc, seed):
    from repro.models import transformer as T
    cfg = dataclasses.replace(starcoder2.program_config(doc),
                              dtype="float32")
    params = weights.make(jax.eval_shape(
        lambda: T.init(cfg, jax.random.key(0))), seed, "target")
    return cfg, params


def test_reference_features_match_program_captures():
    from repro.models import transformer as T
    doc = _tiny("float32")
    seed = 321
    cfg, params = _program32(doc, seed)
    assert list(cfg.captures) == doc["draft"]["capture_layers"]
    toks = np.random.default_rng(1).integers(0, cfg.vocab_size, 20)
    with jax.default_matmul_precision("highest"):
        want = T.prefill(cfg, params, jnp.asarray(toks[None]))["captures"]
    keep = [np.arange(3, 20)]
    _, got = next(starcoder2.forward(doc, seed)([toks], keep=keep))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want[0, 3:]), rtol=2e-4,
                               atol=2e-4)


def test_reference_draft_steps_match_program_trainer():
    from repro.core import eagle
    from repro.training.draft_trainer import DraftTrainer
    doc = _tiny("float32")
    seed = 654
    cfg, params = _program32(doc, seed)
    dcfg = eagle.draft_config(cfg)
    dparams = weights.make(jax.eval_shape(
        lambda: eagle.draft_init(dcfg, jax.random.key(0))), seed, "draft")
    tr = DraftTrainer(cfg, dcfg, params["embed"])
    rng = np.random.default_rng(2)
    f = 3 * cfg.d_model
    batches = [(rng.standard_normal((4, 8, f)).astype(np.float32),
                rng.integers(0, cfg.vocab_size, (4, 8)).astype(np.int32))
               for _ in range(3)]
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
    change = {weights.path_str(k): float(jnp.linalg.norm(
        (a - b).ravel())) for (k, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(p)[0],
        jax.tree_util.tree_flatten_with_path(dparams)[0])}
    ref = starcoder2.draft_train(doc, seed, batches)
    np.testing.assert_allclose(losses, ref["losses"], rtol=1e-4)
    assert set(grad) == set(ref["grad"]) == set(change)
    for k in grad:
        np.testing.assert_allclose(grad[k], ref["grad"][k], rtol=1e-3,
                                   atol=1e-7)
        np.testing.assert_allclose(change[k], ref["change"][k], rtol=1e-3,
                                   atol=1e-7)
