"""Per-layer metric readers, found by name (``layer_metrics/<m>.json`` names
its ``reader``).  Each module has ``read(ctx, **args) -> float | None``;
``ctx`` is the traced run's evidence:

  ctx["header"], ctx["records"]   the load generator's records
  ctx["spans"]                    the program's spans that ended inside the
                                  measured window (``/debug/traces``)
  ctx["counters"]                 {"before", "after"}: ``/metrics`` summed
                                  over labels at the window's two ends
  ctx["trace"]                    ``tracereduce.reduce_trace`` of the traced
                                  slice, or None

A reader that finds nothing to read returns None and the metric is left out.
"""
