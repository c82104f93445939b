"""A benchmark configuration: ``configs/<name>.json`` -> the program's types.

The file holds the model's published ``config.json`` keys at its top level
(so the published widths can be compared key by key with the source), and
beside them what the harness needs: how those keys map onto the program's
``ModelConfig`` (``model_config_map`` / ``model_config_const``), the
``llmd-serve`` flags of the deployment (``serve_args``), ``reduced``,
``assumed`` and a tiny ``rehearsal`` preset for the CPU.  A new
configuration is a new file; nothing here names a model.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_config(name: str) -> Dict[str, Any]:
    return load_json("configs", f"{name}.json")


def model_config_fields(conf: Dict[str, Any], rehearse: bool = False
                        ) -> Dict[str, Any]:
    """``ModelConfig`` keyword arguments of a configuration file.  With
    ``rehearse`` the file's tiny preset replaces the published sizes."""
    src = dict(conf)
    if rehearse:
        src.update(conf["rehearsal"]["sizes"])
    fields = {field: src[key] for field, key in
              conf["model_config_map"].items()}
    fields.update(conf.get("model_config_const", {}))
    fields["name"] = conf["name"]
    return fields


def serve_args(conf: Dict[str, Any], rehearse: bool = False) -> List[str]:
    return list(conf["rehearsal"]["serve_args"] if rehearse
                else conf["serve_args"])


def make_init_fn(model_config, quantization):
    """One function of a PRNG key that returns the whole parameter tree as
    it is served: the program's own ``init_params`` with, for int8 experts,
    the program's own quantizer behind it.  Jitted by the caller into ONE
    device program, so nothing is initialised leaf by leaf or on the host
    and the bf16 originals never outlive the call."""
    from llm_d_tpu.models import get_model
    model = get_model(model_config)

    def init(key):
        params = model.init_params(model_config, key)
        if quantization == "int8":
            from llm_d_tpu.ops.quant import quantize_moe_experts
            params = quantize_moe_experts(params)
        return params

    return init
