"""Layered configuration: base file -> hardware overlays -> CLI flags.

The reference layers helmfile environments over shared
``common-configurations/*.yaml`` over per-guide values over hardware
overlays (``values_tpu.yaml`` etc.) over kustomize patches (reference:
SURVEY.md §5 config system; modelservice.md:21,47 formalizes preset-values
vs model-values layering).  The TPU stack's equivalent for a single
process: deep-merged YAML layers with later layers winning, then explicit
CLI flags on top.

    llmd-serve --config base.yaml --config-overlay tpu-v5e.yaml --port 9000

Merge semantics: dicts merge recursively; scalars and lists replace.
"""

from __future__ import annotations

import copy
import logging
import os
from typing import Any, Dict, List, Optional, Sequence

import yaml

logger = logging.getLogger(__name__)


def env_int(name: str, default: int) -> int:
    """Integer env knob with invalid-value fallback: a malformed value
    (``LLMD_PEER_FAILURE_LIMIT=banana``) must degrade to the shipped
    default, not crash the serving path."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        return int(raw)
    except ValueError:
        logger.warning("%s=%r is not an int; using default %s",
                       name, raw, default)
        return default


def env_float(name: str, default: float) -> float:
    """Float env knob with invalid-value fallback (see :func:`env_int`)."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        return float(raw)
    except ValueError:
        logger.warning("%s=%r is not a float; using default %s",
                       name, raw, default)
        return default


def env_choice(name: str, default: str, choices: Sequence[str]) -> str:
    """Enumerated string env knob with invalid-value fallback: an unknown
    value (``LLMD_COLLECTIVE_DTYPE=fp4``) must degrade to the shipped default
    with a warning, not crash the serving path (see :func:`env_int`)."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    val = raw.strip().lower()
    if val in choices:
        return val
    logger.warning("%s=%r is not one of %s; using default %r",
                   name, raw, tuple(choices), default)
    return default


def deep_merge(base: Dict[str, Any], overlay: Dict[str, Any]) -> Dict[str, Any]:
    """Recursive merge; overlay wins, dicts merge, everything else replaces."""
    out = copy.deepcopy(base)
    for k, v in overlay.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = deep_merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def load_layers(paths: Sequence[str]) -> Dict[str, Any]:
    """Load + merge YAML config layers in order (later wins)."""
    merged: Dict[str, Any] = {}
    for path in paths:
        with open(path) as f:
            doc = yaml.safe_load(f) or {}
        if not isinstance(doc, dict):
            raise ValueError(f"{path}: config layer must be a mapping")
        merged = deep_merge(merged, doc)
    return merged


def apply_file_config(args, parser, merged: Dict[str, Any],
                      argv: Optional[Sequence[str]] = None) -> None:
    """Overlay file config onto argparse results, CLI flags still winning.

    A file key ``max-num-seqs`` (or ``max_num_seqs``) maps to the argparse
    dest.  A flag counts as CLI-set when its option string appears in
    ``argv`` — comparing values against defaults would wrongly let the
    file override an explicit flag that happens to equal the default."""
    import sys
    argv = list(argv if argv is not None else sys.argv[1:])
    explicit = set()
    all_actions = parser._actions
    for token in argv:
        if not token.startswith("--"):
            continue
        base = token.split("=", 1)[0]
        # argparse resolution order: an EXACT option match always wins
        # (--config is not ambiguous with --config-overlay); otherwise an
        # unambiguous prefix abbreviation counts (--num-block).
        exact = {a.dest for a in all_actions if base in a.option_strings}
        if exact:
            explicit |= exact
            continue
        hits = {a.dest for a in all_actions
                for opt in a.option_strings if opt.startswith(base)}
        if len(hits) == 1:
            explicit.add(next(iter(hits)))
    defaults = {a.dest: a.default for a in parser._actions}
    for key, value in merged.items():
        dest = key.replace("-", "_")
        if dest not in defaults:
            raise ValueError(f"unknown config key {key!r}")
        if dest not in explicit:          # CLI wins
            setattr(args, dest, value)
