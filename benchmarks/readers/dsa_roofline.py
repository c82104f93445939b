"""The share of its roofline of a part of a stack that SELECTS its keys,
from the traced slice:

    100 * the least seconds the chip could take / the device time under
    the part's scopes

The time is ``device_parts.py``'s (self time of every operation under the
named ``llmd.*`` scopes, whatever implements the part); the work is
``dsawork.py``'s, from the counts the program hands to its
``llmd.dispatch`` annotations on the ``/host:CPU`` plane of the same trace
(``index_pairs``, ``kv_selected_tokens``, ``kv_read_tokens``,
``kv_held_tokens``), summed apart for pure-decode dispatches
(``prefill_tokens`` 0) and for those with prefill tokens.

args: part (a function of dsawork.py: "index", "sparse_attention"), scopes
(the scopes whose time it is), config (the configuration whose geometry the
work is counted with).

None where there is no device plane (a CPU rehearsal), no operation under
the scopes, or no annotation that carries ``index_pairs`` (a program
without a selection: the parent, every other configuration).
"""

import functools

from readers import kernel_roofline as kr


@functools.lru_cache(maxsize=2)
def annotation_counts(path):
    """{"decode": {count: sum}, "prefill": {...}} over the slice's
    ``llmd.dispatch`` annotations that carry ``index_pairs``; None where
    none does."""
    import dsawork
    total = {regime: dict.fromkeys(dsawork.COUNTS, 0)
             for regime in ("decode", "prefill")}
    seen = False
    for plane in kr.load(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != kr.DISPATCH:
                    continue
                stats = dict(ev.stats)
                if "index_pairs" not in stats:
                    continue
                seen = True
                regime = total["prefill" if int(stats.get(
                    "prefill_tokens", 0)) else "decode"]
                for name in dsawork.COUNTS:
                    regime[name] += int(stats.get(name, 0))
    return total if seen else None


def share(path, part, scopes, conf, peaks):
    import dsawork
    from readers import device_parts
    seconds = device_parts.scoped_seconds(path, tuple(scopes))
    counts = annotation_counts(path)
    if not seconds or counts is None:
        return None
    least = getattr(dsawork, part)(conf, counts, peaks)
    return 100.0 * least / seconds if least > 0 else None


def read(ctx, part, scopes, config):
    if not ctx["trace"]:
        return None
    import jax

    import modelcfg
    from readers.idle_under import newest_xplane
    path = newest_xplane()
    peaks = modelcfg.load_json("peaks.json").get(
        jax.devices()[0].device_kind)
    if path is None or peaks is None:
        return None
    return share(path, part, scopes, modelcfg.load_config(config), peaks)
