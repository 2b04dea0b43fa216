"""Per-layer metric readers, one file per metric, found by name.

Each ``<name>.py`` defines ``read(ctx) -> float | None``.  ``ctx`` is
built by ``bench.cell`` after a run (see ``Context`` there): the config
file, the mix, the chip's peaks, the window's requests, counter
snapshots at the window's and the traced window's edges, and the
reduced device trace.  A reader that finds nothing to read returns
None, and the metric is left out of the result line.
"""
