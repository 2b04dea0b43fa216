"""The StarCoder2 reference (``starcoder2``) for a deployment whose draft
trainer holds a chip of its own.

The reference's mathematics is ``starcoder2``'s, unchanged.  Only where
it computes differs: ``forward`` and ``draft_train`` run on the second
device of the host, which the deployment leaves idle (it serves on the
first and trains on the last).  The run compares after its window while
the system it served with is still held, and the draft's float32 AdamW
steps need about 13 GB at the 15B widths: more than the serving chip has
left beside the target.  Their products leave that chip uncommitted, so
the comparison's own programs take them to the default device as
before.  With fewer than three devices they compute where
``starcoder2``'s would.
"""
import jax

from bench.configs import starcoder2

program_config = starcoder2.program_config


def reference_device():
    """The second device, when the host has a device besides the
    serving (first) and the training (last) one; else None."""
    devs = jax.devices()
    return devs[1] if len(devs) >= 3 else None


def forward(doc: dict, seed: int, fp8: bool = False):
    """``starcoder2.forward``, each of its steps computed on the
    reference device."""
    dev = reference_device()
    if dev is None:
        return starcoder2.forward(doc, seed, fp8)
    with jax.default_device(dev):
        run = starcoder2.forward(doc, seed, fp8)

    def run_there(*args, **kwargs):
        it = run(*args, **kwargs)
        while True:
            with jax.default_device(dev):
                try:
                    item = next(it)
                except StopIteration:
                    return
            yield item

    return run_there


def draft_train(doc: dict, seed: int, batches, fp8: bool = False) -> dict:
    """``starcoder2.draft_train``, computed on the reference device."""
    dev = reference_device()
    if dev is None:
        return starcoder2.draft_train(doc, seed, batches, fp8)
    with jax.default_device(dev):
        return starcoder2.draft_train(doc, seed, batches, fp8)
