"""One reader per per-layer metric, found by the metric's name.

Each module has ``read(ctx)`` returning the metric's value, or None when
the run holds nothing to read (the harness then leaves it out). ``ctx``
carries the reduced trace (``trace``, ``t0``, ``t1``, ``window_s``,
``busy_s``), the window's counts (``window``), the configuration
(``config``), ``counts`` (the configuration's family module,
``bench/families/<family>.py``, whose work counts fit its architecture),
the device's ``peaks`` and ``decode_flops``.
"""
