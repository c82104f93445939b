"""A linear-attention state kernel's share of its roofline, from the traced
slice:

    100 * the least time the chip could take / the kernel's device time

``readers/ssm_roofline.py`` with ``kdawork.py``'s work: the counts are the
same ones (``ssm_decode_rows``, ``ssm_prefill_rows``, ``ssm_prefill_tokens``
on the ``llmd.dispatch`` annotations count rows through the state pool,
whatever the update), the geometry is a LINEAR layer's.

args: kernel (the name of its HLO custom call), bound ("decode": the
one-token update, ``kdawork.decode_state_bytes`` over the HBM bandwidth, for
the rows of EVERY step of the slice; "scan": the chunked form, the larger of
``kdawork.scan_flops`` over the bf16 peak and ``kdawork.scan_bytes`` over the
HBM bandwidth), config.

None where there is no device plane (a CPU rehearsal), no such kernel event
(a program without the kernel: the parent; a geometry it does not serve), or
no annotation that carries the counts.
"""

from readers import kernel_roofline as kr
from readers import ssm_roofline


def least_seconds(bound, conf, counts, peaks):
    import kdawork
    if bound == "decode":
        return kdawork.decode_state_bytes(
            conf, counts["ssm_decode_rows"]) / peaks["hbm_bytes_per_s"]
    if bound == "scan":
        return max(
            kdawork.scan_flops(conf, counts["ssm_prefill_tokens"])
            / peaks["bf16_flops"],
            kdawork.scan_bytes(conf, counts["ssm_prefill_rows"],
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
    counts = ssm_roofline.step_counts(data)
    peaks = modelcfg.load_json("peaks.json").get(
        jax.devices()[0].device_kind)
    if not chips or counts is None or peaks is None:
        return None
    least = least_seconds(bound, modelcfg.load_config(config), counts, peaks)
    busy_s = sum(d for evs in chips for _, d in evs) / len(chips) / 1e9
    if least <= 0 or busy_s <= 0:
        return None
    return 100.0 * least / busy_s
