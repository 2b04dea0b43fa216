"""Signal transport between the serving and training engines.

The decoupled training service (``training/service.py``) consumes
training signals *off the serving path*.  The ``SignalChannel`` is the
seam: the serving engine's superstep unpack pushes packed
``SignalBatch`` windows into a bounded, drop-oldest ring; the training
service blocks on the other end.  Dropping oldest under backpressure is
the correct policy for online adaptation — a slow trainer should see
the *freshest* distribution, and serving must never block on training
(TIDE's decoupling contribution).

Placement: on a single-device host the channel is a host ring buffer
and the trainer interleaves as a background thread (jitted train steps
release the GIL, so train compute overlaps serving host work at
superstep boundaries).  When the local jax platform exposes more than
one device, ``pick_training_device`` carves a training submesh with the
``core/hetero`` allocation model and the channel ``device_put``s each
batch onto the trainer's device as it is enqueued — the copy happens
asynchronously, off the serving path, and the train loop never touches
serving-device memory.  When the trainer lives in another *process*
(``repro.fleet``), ``RemoteSignalChannel`` subclasses this channel: the
same bounded drop-oldest ring becomes the socket send queue (the
``_prepare`` hook skips device placement) and a sender thread frames
batches over the wire, so the serving-path contract — never block,
never sync — is identical in-process and out.
"""
from __future__ import annotations

import threading
from typing import Optional

from repro.core.signals import SignalBatch, SignalStore
from repro.obs.trace import DEFAULT_TRACER


def pick_training_device(s: float = 1.2):
    """Place the draft trainer: carve a training submesh out of the
    local device set with the paper's allocation model
    (``hetero.plan_tpu_submesh``), or return None on a single-device
    host (→ background-thread interleaving).  ``s`` is the speculative
    speedup assumed unlocked by online training (paper Fig. 12)."""
    import jax

    from repro.core.hetero import plan_tpu_submesh

    devs = jax.devices()
    if len(devs) < 2:
        return None
    plan = plan_tpu_submesh(len(devs), s)
    n_train = max(plan.train_chips, 1)   # ≥1 chip once we decide to train
    return devs[len(devs) - n_train]


class SignalChannel(SignalStore):
    """Bounded drop-oldest channel from the signal extractor to the
    training service.

    Duck-types ``SignalStore`` (``add``/``drain``/``peek_count``) so the
    ``SignalExtractor`` writes into it unchanged, and adds: a capacity
    bound with drop-oldest semantics + drop accounting (backpressure
    stats), a condition variable so a consumer can block for samples
    (``wait``), optional producer-side ``device_put`` onto the trainer's
    device, and ``close`` to wake blocked consumers at shutdown.  Each
    ``add`` samples two cumulative counters on ``tracer``:
    ``signals.captured`` (windows offered) and ``signals.dropped``
    (windows the bound dropped)."""

    def __init__(self, capacity: int = 512, device=None,
                 spill_dir: Optional[str] = None, tracer=DEFAULT_TRACER):
        super().__init__(spill_dir=spill_dir, max_samples=capacity)
        self.capacity = capacity
        self.device = device
        self.tracer = tracer
        self.dropped = 0
        self.rejected_after_close = 0
        self.closed = False
        self._cond = threading.Condition(self._lock)

    # ------------------------------------------------------------- produce
    def _prepare(self, batch: SignalBatch) -> SignalBatch:
        """Producer-side placement hook, run outside the lock.  The base
        channel ``device_put``s onto the trainer's device (async enqueue
        — the serving thread never blocks on the copy); subclasses
        override to stage for other transports (e.g. the fleet's
        ``RemoteSignalChannel`` keeps batches as host arrays for the
        socket sender)."""
        if self.device is None:
            return batch
        import jax
        return SignalBatch(
            feats=jax.device_put(batch.feats, self.device),
            tokens=jax.device_put(batch.tokens, self.device))

    def add(self, batch: SignalBatch):
        if self.closed:
            # a closed channel has no consumer left — buffering would
            # grow a ring nobody drains.  Drop-and-count so a straggling
            # producer (e.g. a superstep unpacked after shutdown) is
            # visible in stats() instead of silently retained.
            with self._cond:
                self.rejected_after_close += 1
            return
        batch = self._prepare(batch)
        with self._cond:
            if self.closed:   # close() raced the device_put above
                self.rejected_after_close += 1
                return
            self._buf.append(batch)
            self.total_added += 1
            self.total_bytes += batch.feats.nbytes + batch.tokens.nbytes
            while len(self._buf) > self.capacity:
                self._buf.pop(0)
                self.dropped += 1
            added, dropped = self.total_added, self.dropped
            self._cond.notify_all()
        # cumulative counts, for readers of a traced window
        self.tracer.counter("signals.captured", value=added)
        self.tracer.counter("signals.dropped", value=dropped)

    # ------------------------------------------------------------- consume
    def drain(self, n=None):
        """Pop up to ``n`` (default: all) buffered batches.  On a closed
        channel this is deterministic: ``add`` rejects post-``close``
        writes, so the drained set is exactly the batches buffered
        before ``close`` — one final drain empties the channel and every
        later drain returns ``[]``."""
        return super().drain(n)

    def wait(self, min_count: int = 1,
             timeout: Optional[float] = None) -> int:
        """Block until at least ``min_count`` batches are buffered, the
        channel is closed, or ``timeout`` elapses.  Returns the buffered
        count at wake-up."""
        with self._cond:
            self._cond.wait_for(
                lambda: self.closed or len(self._buf) >= min_count,
                timeout=timeout)
            return len(self._buf)

    def close(self):
        with self._cond:
            self.closed = True
            self._cond.notify_all()

    def reset(self):
        """Back to the post-construction state: empty buffer, zeroed
        push/drop/byte counters (``closed`` is preserved)."""
        with self._cond:
            self._buf.clear()
            self.total_added = 0
            self.total_bytes = 0
            self.dropped = 0
            self.rejected_after_close = 0

    # --------------------------------------------------------------- stats
    @property
    def depth(self) -> int:
        return self.peek_count()

    def stats(self) -> dict:
        return {"pushed": self.total_added, "dropped": self.dropped,
                "depth": self.peek_count(), "bytes": self.total_bytes,
                "rejected_after_close": self.rejected_after_close}

    def register_metrics(self, registry):
        """Expose the channel under the ``train.*`` metrics namespace as
        callback gauges (evaluated at snapshot time only — recording
        adds nothing to the push/drain paths)."""
        registry.gauge("train.signals_pushed", fn=lambda: self.total_added)
        registry.gauge("train.signals_dropped", fn=lambda: self.dropped)
        registry.gauge("train.signals_rejected",
                       fn=lambda: self.rejected_after_close)
        registry.gauge("train.signal_bytes", fn=lambda: self.total_bytes)
        registry.gauge("train.channel_depth", fn=self.peek_count)
