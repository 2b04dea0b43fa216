"""Training: the device seconds of the draft trainer's optimizer step
(``jit_step``) and eval (``jit_eval_acc``) programs over the traced
window, in percent: how much of its chip a trainer that holds a chip of
its own keeps busy.  Only the trainer's chip runs these programs; a
window in which neither ran reads 0."""

PROGRAMS = ("jit_step", "jit_eval_acc")


def read(ctx):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0:
        return None
    busy = sum(t["modules"][p]["s"] for p in PROGRAMS if p in t["modules"])
    return 100.0 * busy / t["window_s"]
