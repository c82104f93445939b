"""An attention kernel's share of a peak of the chip over ALL of the traced
slice's steps: ``kernel_roofline`` with no split by ``prefill_tokens``.

For a kernel that serves every step of a cell.  A block-diffusion engine's
rows bring a whole block of queries to every step, so the prefill kernel
runs its denoising and commit passes (no prefill tokens) as well as its
prompt chunks, and ``kernel_roofline``'s "prefill_flops" would count the
work of the steps with prefill tokens alone against the kernel's time in
all of them.  The program's ``kv_read_tokens`` already counts the visible
(query, key) pairs under the block mask.

args: as ``kernel_roofline`` (kernel, work, peak, config).  None where that
reads None: no device plane, no such kernel event, no annotation with the
counts.
"""

from readers import kernel_roofline as kr


def read(ctx, kernel, work, peak, config):
    if not ctx["trace"]:
        return None
    import jax

    import kernelwork
    import modelcfg
    from readers.idle_under import newest_xplane
    path = newest_xplane()
    if path is None:
        return None
    data = kr.load(path)
    chips = kr.kernel_events(data, kernel)
    tokens = sum(int(s["kv_read_tokens"]) for s in kr.step_counts(data))
    peaks = modelcfg.load_json("peaks.json").get(
        jax.devices()[0].device_kind)
    if not chips or not tokens or peaks is None:
        return None
    busy_s = sum(d for evs in chips for _, d in evs) / len(chips) / 1e9
    done = getattr(kernelwork, work)(modelcfg.load_config(config), tokens)
    return 100.0 * done / peaks[peak] / busy_s
