"""The benchmark's data files, checked on the CPU inside tier-1.

``benchmarks/rehearse.py`` is the benchmark's own gate, but its
``generator()`` expects ``round(rate x 30)`` requests of every open-loop
file in ``traffic/`` and a mix with ``sessions`` has ``asks`` times that:
since PR 27 added such a mix the stock rehearsal stops there (an edit of
``rehearse.py`` is a ``benchmark`` issue's).  Until then these cases run
its other parts one by one, and the generator's checks with the right
count, so that a later PR still has a gate.
"""

import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmarks"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tools"))

import rehearse  # noqa: E402
import traffic  # noqa: E402

MIXES = sorted(p.name for p in (BENCH / "traffic").glob("*.json"))


@pytest.mark.parametrize("part", ["arithmetic", "warm_plan",
                                  "trace_reduction", "benchmark_json"])
def test_rehearsal_part(part, capsys):
    getattr(rehearse, part)()       # a failed check raises SystemExit
    assert "ok:" in capsys.readouterr().out


@pytest.mark.parametrize("name", MIXES)
def test_mix_sizes_do_not_depend_on_the_seed(name):
    mix = json.loads((BENCH / "traffic" / name).read_text())
    a = traffic.build_schedule(mix, 1, 30, "window")["requests"]
    b = traffic.build_schedule(mix, 2**31 + 77, 30, "window")["requests"]
    for k in ("own_tokens", "max_tokens"):
        assert sorted(r[k] for r in a) == sorted(r[k] for r in b)
    pinned = "order_seed" in mix
    assert ([r["own_tokens"] for r in a]
            != [r["own_tokens"] for r in b]) != pinned
    if mix["loop"] == "open":
        asks = mix.get("sessions", {}).get("asks", 1)
        assert len(a) == len(b) == asks * round(mix["rate_rps"] * 30)
        assert all(0 <= r["due"] < 30 for r in a)
    if "sessions" in mix:
        docs = {}
        for r in a:
            docs.setdefault(r["session"], set()).add(r["session_tokens"])
        assert all(len(v) == 1 for v in docs.values())
        assert len(docs) * mix["sessions"]["asks"] == len(a)


def test_new_cell_reads_a_decode_step_metric_of_its_own():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = {m["name"]: m for m in bench["per_layer"]}
    assert entry["step_ms.decode.docqa"]["workloads"] == [
        "trinity-mini.docqa"]
    # the accepted lists are as the parent has them
    assert entry["gen_late_p95_ms"]["workloads"] == ["qwen3moe.chat"]
    rate = json.loads((BENCH / "traffic" / "docqa-trinity.json")
                      .read_text())["rate_rps"]
    assert rate == 0.2              # 0.8 x the knee (0.25), ISSUE 27's rule


def test_mechanism_check_controls():
    import jax.numpy as jnp

    import clean_plays
    import mechanism_check
    lp = {"w_up_q": jnp.arange(-128, 128, dtype=jnp.int8),
          "w_up_s": jnp.ones(4)}
    out = mechanism_check.int4_experts(lp)
    assert out["w_up_q"].dtype == jnp.int8
    assert len(set(out["w_up_q"].tolist())) == 16
    assert out["w_up_s"] is lp["w_up_s"]
    x = jnp.asarray([[0.5, -1.0, 0.25]])
    assert jnp.allclose(mechanism_check.int8_rows(x), x, atol=1 / 127)
    assert clean_plays.parse_plays("0.25:7,0.3:3000000001") == [
        (0.25, 7), (0.3, 3000000001)]


def test_pinned_order_keeps_the_median_behind_a_chunk():
    """``trinity-mini.docqa``: the nearest-rank median of the pinned trace
    must be a request that waits behind prefill chunks (device-timed), with
    room on both sides; on the edge of the warm asks that meet none the
    driver's check refused the cell (PERF.md section 6, PR 27)."""
    import order_scan
    mix = json.loads((BENCH / "traffic" / "docqa-trinity.json").read_text())
    reqs = order_scan.schedule(mix, float(mix["rate_rps"]), 45.0)
    ttft, rows = order_scan.play(reqs, order_scan.FIT)
    assert len(ttft) == 36 and rows <= 16
    fast = sum(t < order_scan.FAST_MS for t in ttft)
    assert fast <= 16 and len(ttft) - fast >= 18
    assert order_scan.nearest_rank(ttft, 50) > 4 * order_scan.FAST_MS


# ---- sdar-30b-a3b / sdar.batch (PR 31) ------------------------------------

def test_sdar_cell_and_its_files():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == "sdar.batch")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar-30b-a3b", "batch", 1)
    conf = json.loads((BENCH / "configs" / "sdar-30b-a3b.json").read_text())
    # every number of the catalog row's config, but the depth
    qwen = json.loads((BENCH / "configs" / "qwen3-30b-a3b.json").read_text())
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "num_experts", "num_experts_per_tok",
                "moe_intermediate_size", "vocab_size", "intermediate_size"):
        assert conf[key] == qwen[key]
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["model_type"] == "sdar_moe" and conf["reference"] == "sdar_moe"
    assert conf["serve_args"] == qwen["serve_args"] + [
        "--precompile-step-shapes"]
    chk = conf["correctness"]
    assert {n % 4 for n in chk["prompt_lens"]} == {0, 1, 2, 3}
    assert max(chk["prompt_lens"]) > 2048 and any(k % 4 for k in chk["ks"])
    entry = {m["name"]: m for m in bench["per_layer"]}
    new = ["step_ms.denoise", "denoise_reveal_share", "itl_p95_ms.sdar",
           "device_idle_share.sdar", "attn_denoise_mxu_share"]
    assert all(entry[n]["workloads"] == ["sdar.batch"] for n in new)
    # the accepted lists are as the parent has them
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["out_tok_s"]["workloads"] == ["kanana2.batch"]
    assert entry["attn_prefill_mxu_share"]["workloads"] == [
        "trinity-mini.docqa"]
    sys.path.insert(0, str(BENCH))
    import modelcfg
    from llm_d_tpu.models.config import ModelConfig
    for rehearse, mask in ((False, 151669), (True, 511)):
        mc = ModelConfig(**modelcfg.model_config_fields(conf, rehearse))
        assert (mc.diffusion_block_length, mc.diffusion_steps,
                mc.mask_token_id, mc.diffusion_remasking) == (
                    4, 4, mask, "sequential")


@pytest.mark.parametrize("trace", [0, 1])
def test_sdar_cell_rehearses_on_the_cpu(trace):
    """``run.py --rehearse --workload sdar.batch``: the harness's whole
    path (server, load generator, the checks (a)-(d) against
    ``references/sdar_moe.py``) at the tiny preset; with ``--trace 1`` the
    new span attributes feed the new metrics."""
    import os
    import subprocess
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "sdar.batch",
         "--seed", str(2**31 + 4242), "--seconds", "4", "--trace",
         str(trace), "--rehearse"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    got = {k.removeprefix("cpu_rehearsal.") for k in last["metrics"]}
    if trace:
        assert {"step_ms.denoise", "denoise_reveal_share", "itl_p95_ms.sdar",
                "step_ms.mixed", "attn_query_fill_share"} <= got
        share = last["metrics"]["cpu_rehearsal.denoise_reveal_share"]["value"]
        assert 19.0 < share < 26.0      # 4 of 20 slots; cut last blocks
    else:
        assert got == {"ttft_p50_ms", "ttft_p95_ms", "setup_s"}


def test_sdar_reference_tail_logprobs_agree_with_its_generation_loop():
    """``tail_logprobs`` (what correctness.py (d) calls, the token ids
    alone) gives the logprob the reference's own generation loop revealed
    each token with, under ``sequential``."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    import references.sdar_moe as ref
    from llm_d_tpu.models import get_model
    from llm_d_tpu.models.config import get_config
    c = dataclasses.replace(get_config("tiny-sdar"), dtype="float32")
    params = get_model(c).init_params(c, jax.random.PRNGKey(3))
    for n, n_gen in ((9, 7), (8, 6), (14, 5)):
        prompt = list(range(3, 3 + n))
        ids, lps = ref.generate(params, c, prompt, n_gen)
        tokens = jnp.asarray(prompt + ids[:-1], jnp.int32)
        lp = ref.tail_logprobs(params, c, tokens, n_gen)
        assert lp.shape == (n_gen, c.vocab_size)
        assert lp.argmax(-1).tolist() == ids
        assert jnp.allclose(lp[jnp.arange(n_gen), jnp.asarray(ids)],
                            jnp.asarray(lps), atol=1e-4)
    # what the mechanism check gets wrong is wrong here too
    try:
        for wrong in ("causal_mask", "stale_pass", "denoise_keys"):
            ref.WRONG = wrong
            bad = ref.tail_logprobs(params, c, tokens, n_gen)
            assert float(jnp.abs(bad - lp).max()) > 1e-2, wrong
    finally:
        ref.WRONG = ""


def test_kernel_roofline_all_reads_nothing_without_a_trace():
    from readers import kernel_roofline_all
    assert kernel_roofline_all.read(
        {"trace": None}, "flash_prefill_paged", "prefill_flops",
        "bf16_flops", "sdar-30b-a3b") is None
