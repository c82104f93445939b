"""Aux features: top-N logprobs, multistep rollback, layered config.

Round-2 review items: weak #8 (logprobs had no top-N alternatives), weak
#9 (multistep speculative blocks leaked on fallback), aux #32 (no layered
config overlays).
"""

import numpy as np
import pytest

from llm_d_tpu.engine.engine import EngineConfig, EngineCore
from llm_d_tpu.engine.request import Request
from llm_d_tpu.ops.sampling import SamplingParams
from llm_d_tpu.utils.config import deep_merge, load_layers

ENGINE_KW = dict(model="tiny", block_size=4, num_blocks=64, max_num_seqs=8,
                 max_num_batched_tokens=64, min_token_bucket=16,
                 min_seq_bucket=4)


def test_top_logprobs_returned_and_consistent():
    engine = EngineCore(EngineConfig(**ENGINE_KW))
    req = Request(request_id="lp", prompt_token_ids=[5, 6, 7],
                  sampling=SamplingParams(temperature=0.0, max_tokens=3,
                                          ignore_eos=True, logprobs=5))
    engine.add_request(req)
    outs = []
    while engine.has_work():
        outs.extend(engine.step())
    tokens = [t for o in outs for t in o.new_token_ids]
    tops = [t for o in outs for t in (o.top_logprobs or [])]
    chosen = [v for o in outs for v in (o.logprobs or [])]
    assert len(tokens) == len(tops) == len(chosen) == 3
    for tok, top, lp in zip(tokens, tops, chosen):
        assert len(top) == 5
        # Greedy: the chosen token IS the argmax -> best alternative.
        assert tok in top
        assert abs(max(top.values()) - top[tok]) < 1e-5
        assert abs(top[tok] - lp) < 1e-4
        assert all(v <= 0.0 for v in top.values())


def test_multistep_fallback_releases_speculative_blocks():
    """When K-step pre-allocation fails mid-way, earlier requests' tail
    blocks must return to the pool (weak #9: held until finish)."""
    engine = EngineCore(EngineConfig(
        model="tiny", block_size=4, num_blocks=14, max_num_seqs=4,
        max_num_batched_tokens=64, min_token_bucket=16, min_seq_bucket=4,
        num_scheduler_steps=8, enable_prefix_caching=False))
    # Two requests sized so prefill fits but K=8 speculative growth cannot.
    reqs = [Request(request_id=f"m{i}", prompt_token_ids=list(range(1, 20)),
                    sampling=SamplingParams(temperature=0.0, max_tokens=30,
                                            ignore_eos=True))
            for i in range(2)]
    for r in reqs:
        engine.add_request(r)
    baseline_free = None
    for _ in range(200):
        if not engine.has_work():
            break
        engine.step()
        # Invariant after every step: blocks held == blocks the requests'
        # computed tokens need (+ at most the current in-flight growth);
        # speculative K-token tails from failed fusion must not linger.
        held = sum(len(r.block_ids) for r in engine.scheduler.running)
        needed = sum(-(-max(r.num_computed_tokens, 1) // 4) + 2
                     for r in engine.scheduler.running)
        assert held <= needed, (held, needed)
    assert all(len(r.output_token_ids) == 30 for r in reqs)


def test_deep_merge_semantics():
    base = {"a": 1, "b": {"x": 1, "y": 2}, "c": [1, 2]}
    over = {"b": {"y": 3, "z": 4}, "c": [9], "d": True}
    m = deep_merge(base, over)
    assert m == {"a": 1, "b": {"x": 1, "y": 3, "z": 4}, "c": [9], "d": True}
    assert base["b"] == {"x": 1, "y": 2}          # no mutation


def test_layered_config_files(tmp_path):
    (tmp_path / "base.yaml").write_text(
        "model: qwen3-0.6b\nblock-size: 16\nnum-blocks: 1024\n")
    (tmp_path / "tpu.yaml").write_text(
        "num-blocks: 4096\ntensor-parallel-size: 4\n")
    merged = load_layers([str(tmp_path / "base.yaml"),
                          str(tmp_path / "tpu.yaml")])
    assert merged == {"model": "qwen3-0.6b", "block-size": 16,
                      "num-blocks": 4096, "tensor-parallel-size": 4}


def test_config_file_wires_into_server_args(tmp_path):
    import argparse
    from llm_d_tpu.utils.config import apply_file_config

    p = argparse.ArgumentParser()
    p.add_argument("--model", default="tiny")
    p.add_argument("--num-blocks", type=int, default=256)
    p.add_argument("--port", type=int, default=8200)
    argv = ["--port", "9999", "--model", "tiny"]  # explicit, one == default
    args = p.parse_args(argv)
    apply_file_config(args, p, {"model": "llama3-8b", "num-blocks": 4096,
                                "port": 1234}, argv=argv)
    # Explicit flags win even when their value equals the parser default.
    assert args.model == "tiny"
    assert args.num_blocks == 4096
    assert args.port == 9999
    with pytest.raises(ValueError):
        apply_file_config(args, p, {"nonsense-key": 1}, argv=argv)


def test_envvar_lint_gate_passes():
    """The env-var registry linter (scripts/lint-envvars.py) must pass:
    every LLMD_*/LWS_* knob read in code is documented in docs/ENVVARS.md
    and vice versa (reference doctrine: scripts/lint-envvars.py)."""
    import pathlib
    import subprocess
    import sys
    repo = pathlib.Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, str(repo / "scripts" / "lint-envvars.py")],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_dockerfile_lint_gate_passes():
    """scripts/lint-dockerfile.py (the reference's lint-dockerfile-envvars
    role): shipped Dockerfiles are clean."""
    import pathlib
    import subprocess
    import sys
    repo = pathlib.Path(__file__).resolve().parent.parent
    r = subprocess.run(
        [sys.executable, str(repo / "scripts" / "lint-dockerfile.py")],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr


def test_dockerfile_lint_catches_violations(tmp_path, monkeypatch):
    """The linter actually rejects: unregistered env knob, latest tag,
    root user, ADD, apt without cleanup."""
    import importlib.util
    import pathlib
    repo = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "lint_dockerfile", repo / "scripts" / "lint-dockerfile.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    bad = tmp_path / "Dockerfile.bad"
    bad.write_text(
        "FROM python:latest\n"
        "ENV LLMD_NOT_A_REAL_KNOB=1\n"
        "ADD local.tar /app\n"
        "RUN apt-get update && apt-get install -y foo\n"
        "USER root\n")
    errs = mod.lint(bad, {"LLMD_MOE_DISPATCH": "auto"})
    text = "\n".join(errs)
    assert "unpinned base image" in text
    assert "LLMD_NOT_A_REAL_KNOB" in text
    assert "COPY instead of ADD" in text
    assert "apt-get install without" in text
    assert "non-root" in text


def test_documents_name_paths_that_exist():
    """Every backticked token of README.md and CONTRIBUTING.md that reads
    as a path of this checkout (no space, ends in .py / .md / .sh / .json /
    .yaml or a slash) resolves from the root or from llm_d_tpu/: a
    document that still sends its reader to a deleted file fails here."""
    import pathlib
    import re
    repo = pathlib.Path(__file__).resolve().parent.parent
    missing = []
    for doc in ("README.md", "CONTRIBUTING.md"):
        tokens = re.findall(r"`([^`\s]+(?:\.py|\.md|\.sh|\.json|\.yaml|/))`",
                            (repo / doc).read_text())
        assert tokens, doc
        missing += [(doc, t) for t in tokens
                    if not ((repo / t).exists()
                            or (repo / "llm_d_tpu" / t).exists())]
    assert not missing, missing
