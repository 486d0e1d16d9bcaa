"""Architecture families: the program side of each, found by the
``"family"`` key of a configuration file.

``bench/families/<family>.py`` maps the file's published keys onto the
program and counts the work its numbers rest on; its float32 reference
is ``bench/reference/<family>.py``, which imports nothing of the
program. A family module has:

* ``arch_config(cfg)`` -- the program's ``ArchConfig`` for the file;
* ``plain_weights(params, cfg)`` -- the program's parameter tree laid
  out as the reference reads it;
* ``monitor_sites(plain)`` -- the two weight arrays the power accountant
  prices, as ``lm.pick_monitor_weights`` picks them, and
  ``embed_multiplier(cfg)``, the scale of the embedded tokens it prices;
* the work counts the per-layer readers take as ``ctx.counts``:
  ``n_params``, ``token_flops``, ``prefill_flops``, ``decode_step_bytes``,
  ``kv_bytes_per_position``, ``monitored_sites``,
  ``counter_bytes_decode`` and ``counter_bytes_prefill``.

The reference module has ``forward(weights, cfg, tokens, low)`` and
``token_gaps(weights, cfg, seq, start, pad_to, control)``. Adding a
family is adding these two files.
"""
