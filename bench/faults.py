"""Faults planted in the timed path, each of which has to make a run's
``correct`` come out false: ``fault(sys_)`` is called once set-up is
done, before the stream starts (``cell.run(..., fault=...)``).  The
benchmark's own runs plant none; ``bench/tests/test_bench_faults.py``
plants each at a tiny size on the CPU, and ``bench/control.py --fault``
at the cell's own size on the chip.
"""
import numpy as np


def altered_token(sys_):
    """Shift every token the superstep emits for lane 0 by one."""
    eng = sys_.engine
    unpack = eng._unpack_superstep

    def altered(ys, *a, **k):
        ys = dict(ys)
        toks = np.array(ys["tokens"])
        toks[:, 0] = (toks[:, 0] + 1) % eng.cfg.vocab_size
        ys["tokens"] = toks
        return unpack(ys, *a, **k)

    eng._unpack_superstep = altered


def unchanged_state(sys_):
    """The draft train step hands back the draft it was given."""
    tr = sys_.trainer
    step = tr._step

    def frozen(dparams, opt_state, *a):
        out = step(dparams, opt_state, *a)
        return (dparams,) + tuple(out[1:])

    tr._step = frozen


def half_batch(sys_):
    """The draft train step leaves out half of its batch and takes the
    mean over the rest."""
    tr = sys_.trainer
    step = tr._step

    def half(dparams, opt_state, embed, feats, tokens, it):
        k = feats.shape[0] // 2
        return step(dparams, opt_state, embed, feats[:k], tokens[:k], it)

    tr._step = half


FAULTS = {f.__name__: f for f in (altered_token, unchanged_state,
                                  half_batch)}
