"""The share of its roofline of a device computation of a
decoder-hybrid-decoder stack, from the traced slice:

    100 * the least seconds the chip could take / its device time

The work is ``phi4flashwork.py``'s, from the counts the program hands to its
``llmd.dispatch`` annotations on the ``/host:CPU`` plane of the same trace
(``ssm_decode_rows``, ``ssm_prefill_rows``, ``ssm_prefill_tokens``,
``xattn_read_tokens``).  The time is EITHER one kernel's own events on the
chip's ``XLA Ops`` line (``kernel``: the name of its HLO custom call) OR
``device_parts.py``'s self time of every operation under named ``llmd.*``
scopes (``scopes``), whatever implements the part.

args: bound (a function of phi4flashwork.py: "scan", "decode", "xattn"),
config (the configuration whose geometry the work is counted with), and
kernel or scopes.

None where there is no device plane (a CPU rehearsal), no such kernel event
or no operation under the scopes (a program without the part, a geometry
the kernel does not serve), or no annotation that carries the counts (a
program without them: the parent).
"""

from readers import kernel_roofline as kr


def annotation_counts(data, names):
    """Sums of ``names`` over the ``llmd.dispatch`` annotations that carry
    the first of them; None where none does."""
    total, seen = dict.fromkeys(names, 0), False
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != kr.DISPATCH:
                    continue
                stats = dict(ev.stats)
                if names[0] in stats:
                    seen = True
                    for name in names:
                        total[name] += int(stats.get(name, 0))
    return total if seen else None


def share(path, bound, conf, peaks, kernel=None, scopes=None):
    """The share from the trace at ``path``, for a configuration and one
    entry of peaks.json."""
    import phi4flashwork
    data = kr.load(path)
    counts = annotation_counts(data, phi4flashwork.COUNTS[bound])
    if counts is None:
        return None
    if kernel is not None:
        chips = kr.kernel_events(data, kernel)
        if not chips:
            return None
        seconds = sum(d for evs in chips for _, d in evs) / len(chips) / 1e9
    else:
        from readers import device_parts
        seconds = device_parts.scoped_seconds(path, tuple(scopes))
    least = getattr(phi4flashwork, bound)(conf, counts, peaks)
    if not seconds or least <= 0:
        return None
    return 100.0 * least / seconds


def read(ctx, bound, config, kernel=None, scopes=None):
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
    return share(path, bound, modelcfg.load_config(config), peaks,
                 kernel=kernel, scopes=scopes)
