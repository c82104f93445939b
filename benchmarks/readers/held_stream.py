"""How close the experts a rank HOLDS in bf16 come to being streamed once at
the HBM bandwidth, from the traced slice:

    100 * (sum of moe_experts_touched x one expert's three bf16 matrices
           / hbm_bytes_per_s) / the device time of the experts' part

``moe_experts_touched`` is what the program hands to its ``llmd.post``
annotations (``part_roofline.annotation_counts``): the distinct experts, of
those HELD, that the step's real rows select, summed over MoE layers and
steps.  The time is ``device_parts.py``'s self time of every operation
under ``scopes`` plus the operations whose name begins with one of
``kernels``, wherever they lie (XLA rewrites a grouped product into custom
calls of its own, ``ragged-dot-*``, that keep no scope: the form the kernels
of ``ops/pallas/moe_held.py`` replace, so a tree without them reads too).
``partwork.experts`` cannot serve here: it counts int8 bytes and the dots of
all k pairs of a token, most of which go to experts held elsewhere.  Only
touched experts, each once: padded tiles, second reads and the glue lower
the share and nothing can push it past 100.

args: scopes, kernels (as ``scope_share``), config (whose hidden size and
expert width an expert's bytes are counted with).

None where there is no device plane (a CPU rehearsal), nothing ran under
the scopes or the names, or no annotation carries the count.
"""


def share(path, scopes, kernels, conf, peaks):
    import modelcfg
    from readers import device_parts, part_roofline
    touched = part_roofline.annotation_counts(path).get(
        "moe_experts_touched")
    chips = device_parts.self_times(path)
    ps = sum(
        t for by_op, ops in chips for key, t in by_op.items()
        if device_parts.scope_of(ops.get(key, {}).get("tf_op")) in scopes
        or ops.get(key, {}).get("name", "").lstrip("%").startswith(
            tuple(kernels) or ("\0",)))
    if not touched or not ps:
        return None
    f = modelcfg.model_config_fields(conf)
    stream = touched * 3 * f["hidden_size"] * f["moe_intermediate_size"] \
        * 2 / peaks["hbm_bytes_per_s"]
    return 100.0 * stream / (ps / 1e12 / len(chips))


def read(ctx, scopes, kernels, config):
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
    return share(path, tuple(scopes), tuple(kernels),
                 modelcfg.load_config(config), peaks)
