"""A part's share of its roofline, from the traced slice:

    100 * the least seconds the chip could take / the device time under
    the part's scopes

The time is ``device_parts.py``'s: the self time of every operation under
the named ``llmd.*`` scopes, whatever kernels and fusions implement the part
today, so a share stays true when work moves between kernels.  The work is
``partwork.py``'s, from what the program hands to the annotations of its
loop on the ``/host:CPU`` plane of the same trace (engine/step_clock.py):

  llmd.dispatch   kv_read_tokens, prefill_tokens (the step launched)
  llmd.post       moe_experts_touched, moe_pairs (the step retired)

Both cover the slice's iterations, as the operations on the chip's line do.

args: part (a function of partwork.py: "experts", "mla_decode",
"mla_prefill"), scopes (the scopes whose time it is), config (the
configuration whose geometry the work is counted with).

None where there is no device plane (a CPU rehearsal), no operation under
the scopes, or no annotation that carries the counts (a program without
them: the parent of PR 36).
"""

import functools

from readers import kernel_roofline as kr

POST = "llmd.post"


@functools.lru_cache(maxsize=2)
def annotation_counts(path):
    """Sums over the slice's annotations; a key is absent where no
    annotation carried its count."""
    total = {}

    def add(key, value):
        total[key] = total.get(key, 0) + int(value)

    for plane in kr.load(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == kr.DISPATCH:
                    stats = dict(ev.stats)
                    if "kv_read_tokens" in stats:
                        decode = int(stats.get("prefill_tokens", 0)) == 0
                        add(("decode" if decode else "prefill")
                            + "_kv_read_tokens", stats["kv_read_tokens"])
                elif ev.name == POST:
                    stats = dict(ev.stats)
                    for key in ("moe_experts_touched", "moe_pairs"):
                        if key in stats:
                            add(key, stats[key])
    return total


def share(path, part, scopes, conf, peaks):
    """The share from the trace at ``path``, for a configuration and one
    entry of peaks.json."""
    import partwork
    from readers import device_parts
    seconds = device_parts.scoped_seconds(path, tuple(scopes))
    counts = annotation_counts(path)
    if not seconds or not all(k in counts for k in partwork.COUNTS[part]):
        return None
    least = getattr(partwork, part)(conf, counts, peaks)
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
