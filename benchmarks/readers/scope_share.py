"""Device time under named ``llmd.*`` scopes as a share of the chip's busy
time, from the traced slice (``device_parts.py``'s self times by innermost
scope): for a scope that is in none of ``device_parts.GROUPS``, or for a
part some of whose kernels carry no scope.

args: scopes (a list of scope names, e.g. ["llmd.attn.index"]); kernels
(optional: names of operations that count too wherever they lie, by their
beginning, e.g. ["ragged-dot"]: XLA rewrites a grouped product into custom
calls of its own, ``ragged-dot-metadata`` and ``ragged-dot-none``, that keep
nothing of the scope the product was called under).

None where there is no device plane (a CPU rehearsal), where the program
names no operation at all, or where nothing ran under the scopes (a program
without the part: the parent, a stack that selects no keys).
"""


def read(ctx, scopes, kernels=()):
    if not ctx["trace"]:
        return None
    from readers import device_parts
    from readers.idle_under import newest_xplane
    path = newest_xplane()
    if path is None:
        return None
    times = device_parts.by_scope(path)
    if not times or set(times) <= {device_parts.UNSCOPED}:
        return None
    chips = device_parts.self_times(path)
    ps = sum(
        t for by_op, ops in chips for key, t in by_op.items()
        if device_parts.scope_of(ops.get(key, {}).get("tf_op")) in scopes
        or ops.get(key, {}).get("name", "").lstrip("%").startswith(
            tuple(kernels) or ("\0",)))
    if not ps:
        return None
    return 100.0 * ps / 1e12 / len(chips) / sum(times.values())
