"""A state-space kernel's share of its roofline, from the traced slice:

    100 * the least time the chip could take / the kernel's device time

The program hands what each step asks of the state pool to its
``llmd.dispatch`` annotation (``ssm_decode_rows``, ``ssm_prefill_rows``,
``ssm_prefill_tokens``), on the ``/host:CPU`` plane of the same trace as the
kernel's events on the chip's ``XLA Ops`` line: both cover the slice's
iterations.  The work is ``ssmwork.py``'s, of real rows and tokens only.

args: kernel (the name of its HLO custom call), bound ("decode": the
one-token update, bytes of ``ssmwork.decode_state_bytes`` over the HBM
bandwidth, for the rows of EVERY step of the slice, since rows of one token
take the kernel in mixed steps too; "scan": the chunked scan, the larger of
``ssmwork.scan_flops`` over the bf16 peak and ``ssmwork.scan_bytes`` over
the HBM bandwidth), config (the configuration whose geometry the work is
counted with).

None where there is no device plane (a CPU rehearsal), no such kernel event
(a program without the kernel, or a geometry it does not serve), or no
annotation that carries the counts (a program without them: the parent).
"""

from readers import kernel_roofline as kr

COUNTS = ("ssm_decode_rows", "ssm_prefill_rows", "ssm_prefill_tokens")


def step_counts(data):
    """Sums of COUNTS over the ``llmd.dispatch`` annotations that carry them."""
    total = dict.fromkeys(COUNTS, 0)
    seen = False
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != kr.DISPATCH:
                    continue
                stats = dict(ev.stats)
                if COUNTS[0] in stats:
                    seen = True
                    for name in COUNTS:
                        total[name] += int(stats.get(name, 0))
    return total if seen else None


def least_seconds(bound, conf, counts, peaks):
    import ssmwork
    if bound == "decode":
        return ssmwork.decode_state_bytes(
            conf, counts["ssm_decode_rows"]) / peaks["hbm_bytes_per_s"]
    if bound == "scan":
        return max(
            ssmwork.scan_flops(conf, counts["ssm_prefill_tokens"])
            / peaks["bf16_flops"],
            ssmwork.scan_bytes(conf, counts["ssm_prefill_rows"],
                               counts["ssm_prefill_tokens"])
            / peaks["hbm_bytes_per_s"])
    raise ValueError(f"unknown bound {bound!r}")


def read(ctx, kernel, bound, config):
    if not ctx["trace"]:
        return None
    import jax

    import modelcfg
    from readers.idle_under import newest_xplane
    path = newest_xplane()
    if path is None:
        return None
    data = kr.load(path)
    chips = kr.kernel_events(data, kernel)
    counts = step_counts(data)
    peaks = modelcfg.load_json("peaks.json").get(
        jax.devices()[0].device_kind)
    if not chips or counts is None or peaks is None:
        return None
    least = least_seconds(bound, modelcfg.load_config(config), counts, peaks)
    busy_s = sum(d for evs in chips for _, d in evs) / len(chips) / 1e9
    if least <= 0 or busy_s <= 0:
        return None
    return 100.0 * least / busy_s
