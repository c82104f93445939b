"""An attention kernel's share of a peak of the chip, from the traced slice:

    100 * necessary work of the slice's steps / peak / the kernel's device time

The program hands what each step asks of the KV cache to its
``llmd.dispatch`` annotation (``kv_read_tokens``, ``prefill_tokens``), so the
counts lie on the ``/host:CPU`` plane of the same trace as the kernel's
events on the chip's ``XLA Ops`` line: both cover the slice's iterations.
A step without prefill tokens runs the decode kernel, any other the prefill
kernel (decode rows of a mixed step ride it too, and are counted with it).
The work is ``kernelwork.py``'s: visible keys only, so grid padding and dead
page reads lower the share and it cannot pass 100.

args: kernel (the name of its HLO custom call), work ("decode_read_bytes" or
"prefill_flops", functions of kernelwork.py), peak (a key of peaks.json),
config (the configuration whose head geometry the work is counted with).

None where there is no device plane (a CPU rehearsal), no such kernel event,
or no annotation that carries the counts (a program without them).

``python3 benchmarks/readers/kernel_roofline.py <xplane.pb> <kernel> <n>``
prints the kernel's median device time by position in a step's n calls: the
layers of a stack that one traced call serves, window and full side by side.
"""

import functools
import statistics
import sys

DISPATCH = "llmd.dispatch"


@functools.lru_cache(maxsize=1)
def load(path):
    """The trace, parsed once for the metrics that read it."""
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def kernel_events(data, kernel):
    """(start, duration) in ns of the kernel's calls, per device plane."""
    import tracereduce
    out = []
    for plane in data.planes:
        if not plane.name.startswith(tracereduce.DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == tracereduce.OPS_LINE:
                out.append(sorted(
                    (int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events
                    if tracereduce.short_name(ev.name).split(" ")[0]
                    == kernel))
    return [evs for evs in out if evs]


def step_counts(data):
    """The stats of every ``llmd.dispatch`` annotation that carries any."""
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == DISPATCH:
                    stats = dict(ev.stats)
                    if "kv_read_tokens" in stats:
                        out.append(stats)
    return out


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
    data = load(path)
    chips = kernel_events(data, kernel)
    decode = work == "decode_read_bytes"
    tokens = sum(int(s["kv_read_tokens"]) for s in step_counts(data)
                 if (int(s.get("prefill_tokens", 0)) == 0) == decode)
    if not chips or not tokens:
        return None
    peaks = modelcfg.load_json("peaks.json").get(
        jax.devices()[0].device_kind)
    if peaks is None:
        return None
    busy_s = sum(d for evs in chips for _, d in evs) / len(chips) / 1e9
    done = getattr(kernelwork, work)(modelcfg.load_config(config), tokens)
    return 100.0 * done / peaks[peak] / busy_s


def by_position(path, kernel, n):
    evs = kernel_events(load(path), kernel)[0]
    return [statistics.median(d for _, d in evs[i::n]) / 1e3
            for i in range(n)], len(evs)


if __name__ == "__main__":
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    med, count = by_position(sys.argv[1], sys.argv[2], int(sys.argv[3]))
    print(f"{sys.argv[2]}: {count} calls; median us by position in a step: "
          + " ".join(f"{m:.1f}" for m in med))
