"""Draft Model Training Engine (paper §3.3).

Consumes SignalBatches from the shared store and fine-tunes the EAGLE-3
draft on the captured target hidden states — no target forward pass and no
target weights on the training devices (only the frozen token-embedding
table is read).  FSDP-style sharding of the draft params happens through
the same logical-axis rules when run under a mesh; on CPU it runs as-is.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import eagle
from repro.core.signals import SignalBatch
from repro.models.config import ModelConfig
from repro.obs.trace import DEFAULT_TRACER
from repro.training.optimizer import Optimizer, adamw


class DraftTrainer:
    """Asynchronous draft training cycles (one per controller trigger)."""

    def __init__(self, tcfg: ModelConfig, dcfg: ModelConfig, embed_params,
                 opt: Optional[Optimizer] = None, batch_size: int = 8,
                 ttt: bool = True):
        self.tcfg = tcfg
        self.dcfg = dcfg
        self.embed_params = embed_params     # frozen target embeddings
        self.opt = opt or adamw(lr=1e-3, weight_decay=0.0)
        self.batch_size = batch_size
        self.ttt = ttt

        # the frozen embeddings are an argument, not a closure: jit bakes
        # a closed-over array into the program as a constant (the whole
        # vocab x d_model table, at real widths), and a constant cannot
        # follow the trainer onto its own device
        def loss_fn(dparams, embed_params, feats, tokens):
            with jax.named_scope("loss"):
                return eagle.draft_train_loss(
                    self.dcfg, dparams, embed_params, feats, tokens,
                    ttt=self.ttt)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        # the optimizer state is private to one cycle: donating it lets
        # the update reuse the moment buffers in place
        @functools.partial(jax.jit, donate_argnums=(1,))
        def step(dparams, opt_state, embed_params, feats, tokens, it):
            with jax.named_scope("gradient"):
                (loss, metrics), grads = grad_fn(dparams, embed_params,
                                                 feats, tokens)
            with jax.named_scope("adamw_update"):
                dparams, opt_state = self.opt.update(dparams, grads,
                                                     opt_state, it)
            return dparams, opt_state, loss, metrics["accuracy"]

        self._step = step

        @jax.jit
        def eval_acc(dparams, embed_params, feats, tokens):
            _, metrics = eagle.draft_train_loss(
                self.dcfg, dparams, embed_params, feats, tokens,
                ttt=False)
            return metrics["accuracy"]

        self._eval = eval_acc

    # ---------------------------------------------------------------- data
    @staticmethod
    def _stack(batches: List[SignalBatch]) -> Tuple[np.ndarray, np.ndarray]:
        s = min(b.feats.shape[0] for b in batches)
        feats = np.stack([b.feats[:s] for b in batches])
        toks = np.stack([b.tokens[:s] for b in batches])
        return feats, toks

    def make_arrays(self, batches: List[SignalBatch], eval_frac: float = 0.1):
        """Split collected signals 9:1 into train/eval (paper Alg. 1)."""
        feats, toks = self._stack(batches)
        n = feats.shape[0]
        n_eval = max(1, int(n * eval_frac)) if n > 1 else 0
        return ((feats[:n - n_eval], toks[:n - n_eval]),
                (feats[n - n_eval:], toks[n - n_eval:]))

    # --------------------------------------------------------------- cycle
    def train_cycle(self, dparams, batches: List[SignalBatch], *,
                    epochs: int = 2, min_steps: int = 80,
                    seed: int = 0, tracer=DEFAULT_TRACER) -> Dict:
        """One training cycle on the drained signal buffer.  ``epochs`` is
        a floor — small buffers get extra epochs until ``min_steps``
        optimizer steps have run (training-until-saturation, paper Fig. 5).
        ``tracer`` times its phases: ``train.stack`` (host arrays,
        optimizer state and every epoch's sample order), then per step
        ``train.feed`` (the batch built and copied to the device),
        ``train.step`` (the dispatch) and ``train.read`` (the host's
        wait for its accuracy), and ``train.eval``.
        Returns dict(dparams, train_acc, eval_acc, steps, seconds)."""
        with tracer.span("train.stack"):
            (tf, tt), (ef, et) = self.make_arrays(batches)
            opt_state = self.opt.init(dparams)
            bs = min(self.batch_size, max(tf.shape[0], 1))
            steps_per_epoch = max(tf.shape[0] // bs, 1)
            epochs = max(epochs, -(-min_steps // steps_per_epoch))
            rng = np.random.default_rng(seed)
            orders = [rng.permutation(tf.shape[0]) for _ in range(epochs)]
        t0 = time.perf_counter()
        it = 0
        last_acc = 0.0
        for order in orders:
            for s0 in range(0, len(order) - bs + 1, bs):
                with tracer.span("train.feed"):
                    sel = order[s0:s0 + bs]
                    feats, toks = jnp.asarray(tf[sel]), jnp.asarray(tt[sel])
                    step_no = jnp.int32(it)
                with tracer.span("train.step"):
                    dparams, opt_state, _, acc = self._step(
                        dparams, opt_state, self.embed_params, feats, toks,
                        step_no)
                with tracer.span("train.read"):
                    last_acc = float(acc)
                it += 1
        with tracer.span("train.eval"):
            eval_acc = (float(self._eval(dparams, self.embed_params,
                                         jnp.asarray(ef), jnp.asarray(et)))
                        if ef.shape[0] else last_acc)
        return {"dparams": dparams, "train_acc": last_acc,
                "eval_acc": eval_acc, "steps": it,
                "seconds": time.perf_counter() - t0}
