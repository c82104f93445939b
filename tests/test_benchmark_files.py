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


# ---- falcon-h1-34b / falcon-h1.batch (PR 34) -------------------------------

FALCON_METRICS = ["step_ms.decode.falcon", "device_idle_share.falcon",
                  "itl_p95_ms.falcon", "run_ahead_share.falcon",
                  "ssm_decode_hbm_share", "ssm_scan_roofline_share"]


def _catalog_row(name):
    path = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not path.exists():
        pytest.skip("no catalog beside the model-configs guide here")
    return next(r for r in map(json.loads, path.read_text().splitlines())
                if r.get("name") == name)


def test_falcon_cell_and_its_files():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"]
                if w["name"] == "falcon-h1.batch")
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "falcon-h1.batch", "falcon-h1-34b", "batch", 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == "falcon-h1-34b")
    conf = json.loads((REPO / entry["file"]).read_text())
    assert entry["name"] == conf["name"] == "falcon-h1-34b"
    assert entry["reduced"] == conf["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == conf["source"] and len(entry["why"]) <= 200
    assert conf["reference"] == conf["model_type"] == "falcon_h1"
    assert "--quantization" not in conf["serve_args"]       # bf16 as published
    assert conf["num_hidden_layers"] in (5, 6)              # floor 4
    for key in ("reduced_why", "assumed", "deployment", "memory_account"):
        assert conf[key], key
    assert {"state_dtype", "init_scales"} <= set(conf["assumed"])
    chk = conf["correctness"]
    assert max(chk["prompt_lens"]) > 2048       # a chunk boundary mid-prompt
    assert min(chk["prompt_lens"]) < 128        # shorter than a scan piece
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(FALCON_METRICS[0])     # appended together, in order
    assert names[at:at + len(FALCON_METRICS)] == FALCON_METRICS
    for name in FALCON_METRICS:
        assert per_layer[name]["workloads"] == ["falcon-h1.batch"]
        d = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
        assert (BENCH / "readers" / f"{d['reader']}.py").exists()
    # the accepted lists are as the parent has them
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["out_tok_s"]["workloads"] == ["kanana2.batch"]
    assert e2e["itl_p95_ms"]["workloads"] == ["kanana2.batch"]
    assert per_layer["run_ahead_share"]["workloads"] == ["kanana2.batch"]
    assert per_layer["step_ms.denoise"]["workloads"] == ["sdar.batch"]


def test_falcon_config_holds_every_published_key():
    row = _catalog_row("Falcon-H1-34B-Instruct")
    conf = json.loads((BENCH / "configs" / "falcon-h1-34b.json").read_text())
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in conf["reduced"]:
            assert conf[key] == value, key
    assert row["config"]["num_hidden_layers"] == 72


@pytest.mark.parametrize("rehearse", [False, True])
def test_falcon_config_maps_onto_the_program(rehearse):
    import modelcfg
    from llm_d_tpu.models import get_model
    from llm_d_tpu.models.config import ModelConfig
    conf = json.loads((BENCH / "configs" / "falcon-h1-34b.json").read_text())
    mc = ModelConfig(**modelcfg.model_config_fields(conf, rehearse))
    assert mc.has_recurrent_state and not mc.is_moe
    assert get_model(mc).__name__.endswith("models.ssm")
    assert mc.ssm_multipliers == tuple(conf["ssm_multipliers"])
    assert mc.mlp_multipliers == tuple(conf["mlp_multipliers"])
    assert (mc.embed_scale, mc.key_multiplier, mc.lm_head_multiplier) == (
        conf["embedding_multiplier"], conf["key_multiplier"],
        conf["lm_head_multiplier"])
    if not rehearse:
        assert (mc.num_heads, mc.num_kv_heads, mc.head_dim_) == (20, 4, 128)
        assert (mc.ssm_num_heads, mc.ssm_head_dim, mc.ssm_state_size,
                mc.ssm_num_groups, mc.ssm_conv_kernel, mc.ssm_chunk_size) == (
                    32, 128, 256, 2, 4, 128)
        assert mc.ssm_conv_channels == 5120 and mc.rope_theta == 1e11
        # the program's own preset is the published model, all 72 layers
        import dataclasses
        from llm_d_tpu.models.config import get_config
        assert dataclasses.replace(get_config("falcon-h1-34b"),
                                   num_layers=mc.num_layers) == mc
        from llm_d_tpu.ops.ssm import pallas_ineligible_reason
        assert not pallas_ineligible_reason(32, 128, 256, 2, 128)


def test_ssm_work_counts_real_rows_and_tokens():
    import ssmwork
    from readers import ssm_roofline
    conf = json.loads((BENCH / "configs" / "falcon-h1-34b.json").read_text())
    L = conf["num_hidden_layers"]
    assert ssmwork.state_bytes(conf) == 32 * 128 * 256 * 4 == 4 << 20
    assert ssmwork.conv_tail_bytes(conf) == 3 * 5120 * 2
    assert ssmwork.decode_state_bytes(conf, 64) == 64 * L * (
        (8 << 20) + 30720)
    assert ssmwork.scan_flops(conf, 1000) == 1000 * L * (
        4 * 32 * 256 * 128 + 2 * 2 * 256 + 2 * 32 * 128)
    assert ssmwork.scan_bytes(conf, 2, 0) == 2 * L * (4 << 20)
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    counts = {"ssm_decode_rows": 64, "ssm_prefill_rows": 1,
              "ssm_prefill_tokens": 300}
    # a 64-row decode step's floor: 3.9 ms at six layers (ISSUE 34's 3.2 GB)
    assert 3.5e-3 < ssm_roofline.least_seconds(
        "decode", conf, counts, peaks) * 6 / L < 4.2e-3
    assert ssm_roofline.least_seconds("scan", conf, counts, peaks) > 0
    # without a trace a reader reads nothing (the parent, a CPU rehearsal)
    assert ssm_roofline.read({"trace": None}, "ssm_decode_update", "decode",
                             "falcon-h1-34b") is None


@pytest.mark.parametrize("trace", [0, 1])
def test_falcon_cell_rehearses_on_the_cpu(trace):
    """``run.py --rehearse --workload falcon-h1.batch``: the harness's whole
    path (server, load generator, the checks (a)-(d) against
    ``references/falcon_h1.py``) at the tiny preset, every slot taken so
    that steps run ahead; with ``--trace 1`` the new span attributes feed
    the new metrics."""
    import os
    import subprocess
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "falcon-h1.batch", "--seed", str(2**31 + 3434), "--seconds", "4",
         "--trace", str(trace), "--rehearse"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    got = {k.removeprefix("cpu_rehearsal."): v["value"]
           for k, v in last["metrics"].items()}
    if trace:
        assert {"step_ms.decode.falcon", "itl_p95_ms.falcon",
                "run_ahead_share.falcon", "step_ms.mixed",
                "attn_query_fill_share", "prefix_hit_share"} <= set(got)
        assert got["run_ahead_share.falcon"] > 10.0
        assert got["prefix_hit_share"] == 0.0       # no hit is granted
        # device metrics are read from a device trace only
        assert not {"ssm_decode_hbm_share", "ssm_scan_roofline_share",
                    "device_idle_share.falcon"} & set(got)
    else:
        assert set(got) == {"ttft_p50_ms", "ttft_p95_ms", "setup_s"}


def test_ssm_mechanism_check_names_each_fault():
    import references.falcon_h1 as ref
    import ssm_mechanism_check
    faults = {f for _, f in ssm_mechanism_check.WRONG if f}
    assert faults == {"bf16_state", "zero_conv_tail", "no_mup_vector",
                      "no_key_multiplier", "swap_groups", "no_softplus",
                      "int8_weights", "int8_kv"}
    assert ssm_mechanism_check.WRONG[0] == ("as published", None)
    # what the chip's readings cannot refuse is held by tests/test_ssm_hybrid.py
    assert faults - ssm_mechanism_check.MUST_REFUSE == {
        "bf16_state", "zero_conv_tail", "int8_kv"}
    tol = json.loads((BENCH / "configs" / "falcon-h1-34b.json").read_text())[
        "correctness"]["reference_tolerance"]
    # between the largest served reading and the int8-weights one (PR 34)
    assert 0.0075 < tol["median"] < 0.0126 and 0.0158 < tol["p90"] < 0.0464
    assert ref.FAULTS == set()          # nothing wrong in a served comparison
    # the convolution's fault touches only tokens just behind a boundary
    import jax.numpy as jnp
    u = jnp.ones((10, 3))
    w, b = jnp.ones((3, 4)), jnp.zeros((3,))
    right = ref.causal_conv(u, w, b)
    try:
        ref.FAULTS, ref.FAULT_CHUNK = {"zero_conv_tail"}, 6
        wrong = ref.causal_conv(u, w, b)
    finally:
        ref.FAULTS, ref.FAULT_CHUNK = set(), 2048
    assert right[:, 0].tolist() == [1, 2, 3, 4, 4, 4, 4, 4, 4, 4]
    assert wrong[:, 0].tolist() == [1, 2, 3, 4, 4, 4, 1, 2, 3, 4]


@pytest.mark.parametrize("name,moves,counts,cell", [
    ("attn_key_fill_share.mla", "itl_p95_ms", "attn_k",
     "kanana2.batch"),                                              # PR 35
    ("attn_decode_key_fill_share.mla", "out_tok_s", "attn_dk",
     "kanana2.batch"),                                              # PR 37
    ("attn_window_key_fill_share", "ttft_p95_ms", "attn_wk",
     "dots3.longdoc"),                                              # PR 40
    ("dsa_index_key_fill_share", "ttft_p95_ms", "idx_k",
     "dots3.longdoc"),                                              # PR 43
])
def test_mla_key_fill_share_is_a_data_file(name, moves, counts, cell):
    """A PR's one per-layer metric: an entry appended to BENCHMARK.json and
    a file for the reader that is there, no reader code."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry == {
        "name": name, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "attention kernels",
        "moves": moves, "workloads": [cell]}
    d = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
    assert all(d[k] == entry[k] for k in (
        "name", "unit", "better", "source", "layer", "moves"))
    assert d["reader"] == "span_ratio" and d["args"] == {
        "span": "engine.step", "numerator": f"{counts}_real",
        "denominator": [f"{counts}_slots"]}
    moved = next(m for m in bench["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved.get(
        "workloads", [w["name"] for w in bench["workloads"]]))


# ---------------------------------------------------------------------------
# dots3-note-prev and dots3.longdoc (PR 39)
# ---------------------------------------------------------------------------

PHI4_METRICS = ["device_idle_share.phi4flash", "xdec_row_share",
                "device_part_share.state.phi4flash",
                "device_part_share.cross", "device_part_share.gmu",
                "ssm1_scan_roofline_share", "ssm1_decode_hbm_share",
                "xattn_hbm_share", "kv_window_dead_share.phi4flash"]
# ling3.longdoc's (PR 49), appended last
LING_METRICS = ["device_part_share.linear", "device_part_share.experts.ling3",
                "lin_scan_roofline_share", "lin_decode_hbm_share",
                "mla_prefill_mxu_share.ling3", "mla_decode_hbm_share.ling3",
                "step_ms.decode.ling3", "device_idle_share.ling3",
                "itl_p95_ms.ling3"]
DOTS_METRICS = ["device_idle_share.dots3", "device_part_share.index",
                "device_part_share.experts.dots3", "dsa_selected_share",
                "dsa_index_roofline_share", "mla_sparse_roofline_share"]


def test_dots3_cell_and_its_files():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == "dots3.longdoc")
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "dots3.longdoc", "dots3-note-prev", "longdoc", 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"]
                 if c["name"] == "dots3-note-prev")
    conf = json.loads((REPO / entry["file"]).read_text())
    assert entry["name"] == conf["name"] == "dots3-note-prev"
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size"]
    assert entry["source"] == conf["source"] and len(entry["why"]) <= 200
    assert conf["reference"] == "dots3_note" == conf["model_type"]
    assert "--quantization" not in conf["serve_args"]       # bf16 as published
    assert conf["num_hidden_layers"] in (5, 6)              # floor 4 + dense
    assert len(conf["layer_types"]) == conf["num_hidden_layers"]
    assert conf["published"]["n_routed_experts"] == conf["router_experts"] == 256
    assert conf["published"]["vocab_size"] == 152064
    assert conf["published"]["num_hidden_layers"] == 46
    for key in ("reduced_why", "assumed", "deployment", "memory_account"):
        assert conf[key], key
    assert {"lora_rescale", "headwise_gate", "indexer"} <= set(conf["assumed"])
    chk = conf["correctness"]
    assert min(chk["prompt_lens"]) < 2048 < sorted(chk["prompt_lens"])[1]
    assert max(chk["prompt_lens"]) >= 6000
    mix = json.loads((BENCH / "traffic" / "longdoc.json").read_text())
    assert (mix["loop"], mix["clients"]) == ("closed", 16)
    assert mix["clients"] == int(conf["serve_args"][
        conf["serve_args"].index("--max-num-seqs") + 1])
    assert mix["prompt_tokens"] == {"dist": "loguniform", "min": 4096,
                                    "max": 16384}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 128,
                                    "sigma": 0.5, "min": 32, "max": 256}
    assert (mix["shared_prefix_tokens"], mix["warmup_seconds"],
            mix["drain_seconds"]) == (0, 20, 60)
    assert "sessions" not in mix and "order_seed" in mix
    # every prompt is 2 to 8 times index_topk
    assert mix["prompt_tokens"]["min"] == 2 * conf["index_topk"]
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(DOTS_METRICS[0])
    assert names[at:at + len(DOTS_METRICS)] == DOTS_METRICS   # in order
    assert names[at + len(DOTS_METRICS):] == [
        "attn_window_key_fill_share"] + PHI4_METRICS + [
        "moe_held_hbm_share",                       # PRs 40, 41, 42, 43,
        "dsa_index_key_fill_share",                 # 46, 47, 48 appended
        *MELLUM_METRICS, "prefill_ahead_share", "moe_one_pass_share",
        *LING_METRICS]                              # PR 49
    held = json.loads((BENCH / "layer_metrics"
                       / "moe_held_hbm_share.json").read_text())
    assert per_layer["moe_held_hbm_share"]["workloads"] == ["dots3.longdoc"]
    assert (held["reader"], held["moves"]) == ("held_stream", "ttft_p50_ms")
    # the scopes and names the accepted experts' share of the cell reads
    experts = json.loads((BENCH / "layer_metrics"
                          / "device_part_share.experts.dots3.json").read_text())
    assert (held["args"]["scopes"], held["args"]["kernels"]) == (
        experts["args"]["scopes"], experts["args"]["kernels"])
    for name in DOTS_METRICS:
        assert per_layer[name]["workloads"] == ["dots3.longdoc"]
        d = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
        assert (BENCH / "readers" / f"{d['reader']}.py").exists()
    # the accepted lists are as the parent has them
    assert per_layer["device_part_share.experts"]["workloads"] == [
        "qwen3moe.chat", "kanana2.batch", "trinity-mini.docqa", "sdar.batch"]
    assert per_layer["device_idle_share.falcon"]["workloads"] == [
        "falcon-h1.batch"]


def test_dots3_config_holds_every_published_key():
    row = _catalog_row("dots3-note-prev")
    conf = json.loads((BENCH / "configs" / "dots3-note-prev.json").read_text())
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in conf["reduced"]:
            assert conf[key] == value, key
    assert conf["layer_types"] == row["config"]["layer_types"][
        :conf["num_hidden_layers"]]
    assert row["config"]["n_routed_experts"] == 8 * conf["n_routed_experts"]
    assert row["config"]["vocab_size"] == 8 * conf["vocab_size"]


@pytest.mark.parametrize("rehearse", [False, True])
def test_dots3_config_maps_onto_the_program(rehearse):
    import modelcfg
    from llm_d_tpu.models import get_model
    from llm_d_tpu.models.config import FULL, SLIDING, ModelConfig
    conf = json.loads((BENCH / "configs" / "dots3-note-prev.json").read_text())
    mc = ModelConfig(**modelcfg.model_config_fields(conf, rehearse))
    model = get_model(mc)
    assert model.__name__.endswith("models.moe") and mc.use_mla
    assert mc.mla_layer_kinds == (FULL, SLIDING)
    assert mc.layer_types == tuple(conf["layer_types"])
    assert mc.attn_head_gate and mc.mla_lora_rescale
    assert mc.first_dense_layers == 1 and mc.first_local_expert == 0
    if not rehearse:
        assert (mc.num_experts, mc.num_held_experts,
                mc.num_experts_per_tok) == (256, 32, 8)
        full, swa = mc.mla_geometry(FULL), mc.mla_geometry(SLIDING)
        assert full == (128, 1024, 512, 128, 64, 128, 8e7, 0, 2048)
        assert swa == (64, 1024, 1024, 192, 64, 128, 5e4, 513, 0)
        assert model.kv_cache_layout(mc) == {"kv": 640, "idx": 128,
                                             "kv_swa": 1152}
        assert model.kv_cache_layers(mc) == {"kv": 3, "idx": 3, "kv_swa": 3}
        assert (mc.index_n_heads, mc.index_head_dim) == (64, 128)
        assert mc.vocab_size == 19008 and mc.max_model_len == 32768


def test_dsa_work_counts_real_pairs():
    import dsawork
    from readers import dsa_roofline, scope_share
    conf = json.loads((BENCH / "configs" / "dots3-note-prev.json").read_text())
    assert dsawork.layers(conf) == (3, 3)
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    zero = dict.fromkeys(dsawork.COUNTS, 0)
    # one decode row at a context of 8,192 in 3 + 3 layers
    dec = {"index_pairs": 3 * 8192, "kv_selected_tokens": 3 * 2048,
           "kv_read_tokens": 3 * 2048 + 3 * 513, "kv_held_tokens": 6 * 8192}
    counts = {"decode": dec, "prefill": zero}
    flops = 3 * 8192 * 64 * 128 * 2
    key_bytes = 3 * 8192 * 128 * 2
    assert dsawork.index(conf, counts, peaks) == max(
        flops / peaks["bf16_flops"], key_bytes / peaks["hbm_bytes_per_s"])
    row_bytes = 3 * 2048 * 576 * 2 + 3 * 513 * 1088 * 2
    pair_flops = (3 * 2048 * 128 * (2 * 576 + 2 * 512)
                  + 3 * 513 * 64 * (2 * 1088 + 2 * 1024))
    assert dsawork.sparse_attention(conf, counts, peaks) == max(
        pair_flops / peaks["bf16_flops"],
        row_bytes / peaks["hbm_bytes_per_s"])
    # a prefill chunk is held to its dots alone
    counts = {"decode": zero, "prefill": dec}
    assert dsawork.sparse_attention(conf, counts, peaks) == (
        pair_flops / peaks["bf16_flops"])
    # without a trace a reader reads nothing (the parent, a CPU rehearsal)
    assert dsa_roofline.read({"trace": None}, "index", ["llmd.attn.index"],
                             "dots3-note-prev") is None
    assert scope_share.read({"trace": None}, ["llmd.attn.index"]) is None


def test_dots3_cell_rehearses_on_the_cpu():
    """``run.py --rehearse --workload dots3.longdoc --trace 1``: the
    harness's whole path (server, load generator, the checks (a)-(d)
    against ``references/dots3_note.py``) at the tiny preset; the new span
    attributes feed ``dsa_selected_share``."""
    import os
    import subprocess
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "dots3.longdoc", "--seed", str(2**31 + 3939), "--seconds", "4",
         "--trace", "1", "--rehearse"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=600)
    assert out.returncode == 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    got = {k.removeprefix("cpu_rehearsal."): v["value"]
           for k, v in last["metrics"].items()}
    assert {"dsa_selected_share", "step_ms.mixed", "attn_query_fill_share",
            "prefix_hit_share", "queue_wait_p95_ms"} <= set(got)
    # prompts of 40-150 tokens at a top-k of 16
    assert 10.0 < got["dsa_selected_share"] < 60.0
    # device metrics are read from a device trace only
    assert not {"device_part_share.index", "dsa_index_roofline_share",
                "mla_sparse_roofline_share",
                "device_idle_share.dots3"} & set(got)


def test_dsa_mechanism_check_names_each_fault():
    import references.dots3_note as ref
    import dsa_mechanism_check
    faults = {f for _, f in dsa_mechanism_check.WRONG if f}
    assert faults == {"no_rescale", "no_gate", "dense_full",
                      "window_plus_one", "int8_weights", "int8_kv"}
    assert dsa_mechanism_check.WRONG[0] == ("as published", None)
    assert dsa_mechanism_check.MUST_REFUSE <= faults
    assert ref.FAULTS == set()          # nothing wrong in a served comparison
    tol = json.loads((BENCH / "configs" / "dots3-note-prev.json").read_text())[
        "correctness"]["reference_tolerance"]
    # PR 39, on the chip: twenty seeds served (median to 0.0559, p90 to
    # 0.2422) pass both limits, and each of the ten int8-weights readings
    # (median, p90) is refused by one of them
    assert 0.0559 < tol["median"] and 0.2422 < tol["p90"]
    int8 = [(0.1069, 0.3207), (0.0842, 0.3781), (0.1098, 0.3424),
            (0.0822, 0.1833), (0.0764, 0.3402), (0.1024, 0.3018),
            (0.1077, 0.3600), (0.0984, 0.3700), (0.1095, 0.3290),
            (0.0705, 0.3177)]
    assert all(m > tol["median"] or p > tol["p90"] for m, p in int8)


# ---- phi4-mini-flash / phi4flash.longdoc (PR 41) ----------------------------



def _phi4_conf():
    return json.loads((BENCH / "configs" / "phi4-mini-flash.json").read_text())


def test_phi4flash_cell_and_its_files():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = bench["workloads"][-3]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "phi4flash.longdoc", "phi4-mini-flash", "longdoc-8", 1)
    assert len(cell["why"]) <= 200
    entry = bench["configs"][-3]
    conf = _phi4_conf()
    assert entry["name"] == conf["name"] == "phi4-mini-flash"
    assert entry["reduced"] == conf["reduced"] == []        # nothing is cut
    assert entry["source"] == conf["source"] and len(entry["why"]) <= 200
    assert conf["reference"] == conf["model_type"] == "phi4flash"
    assert conf["serve_args"] == [
        "--max-num-seqs", "8", "--max-num-batched-tokens", "2048",
        "--block-size", "32", "--kv-cache-hbm-gb", "5"]
    for key in ("reduced_why", "assumed", "deployment", "memory_account"):
        assert conf[key], key
    chk = conf["correctness"]
    assert min(chk["prompt_lens"]) < conf["sliding_window"]
    assert sorted(chk["prompt_lens"])[1] > 2048 > conf["sliding_window"]
    assert max(chk["prompt_lens"]) > 3 * 2048 and chk["n_gen"] == 16
    mix = json.loads((BENCH / "traffic" / "longdoc-8.json").read_text())
    longdoc = json.loads((BENCH / "traffic" / "longdoc.json").read_text())
    assert (mix["loop"], mix["clients"]) == ("closed", 8)
    for key in ("prompt_tokens", "output_tokens", "shared_prefix_tokens",
                "warmup_seconds", "drain_seconds", "order_seed",
                "closed_list_len", "rehearsal"):
        assert mix[key] == longdoc[key], key
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(PHI4_METRICS[0])
    assert names[at:at + len(PHI4_METRICS)] == PHI4_METRICS   # in order
    assert names[at + len(PHI4_METRICS):] == [
        "moe_held_hbm_share", "dsa_index_key_fill_share",       # PRs 42, 43
        *MELLUM_METRICS, "prefill_ahead_share",                 # PRs 46, 47
        "moe_one_pass_share", *LING_METRICS]                    # PRs 48, 49
    for name in PHI4_METRICS:
        assert per_layer[name]["workloads"] == ["phi4flash.longdoc"]
        d = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
        assert (BENCH / "readers" / f"{d['reader']}.py").exists()
        assert {k: d[k] for k in ("unit", "better", "source", "layer",
                                  "moves")} == {
            k: per_layer[name][k] for k in ("unit", "better", "source",
                                            "layer", "moves")}
    # the accepted lists are as the parent has them
    assert per_layer["kv_window_dead_share"]["workloads"] == [
        "trinity-mini.docqa"]
    assert per_layer["device_part_share.state"]["workloads"] == [
        "falcon-h1.batch"]
    assert {m["name"]: m for m in bench["end_to_end"]}["out_tok_s"][
        "workloads"] == ["kanana2.batch"]


def test_phi4flash_config_holds_every_published_key():
    row = _catalog_row("Phi-4-mini-flash-reasoning")
    conf = _phi4_conf()
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert conf[key] == value, key
    named = " ".join(conf["assumed"])
    for key in set(conf["model_config_map"].values()) - set(row["config"]):
        assert key in named or key == "max_model_len", key


@pytest.mark.parametrize("rehearse", [False, True])
def test_phi4flash_config_maps_onto_the_program(rehearse):
    import dataclasses

    import modelcfg
    from llm_d_tpu.models import get_model
    from llm_d_tpu.models.config import ModelConfig, get_config
    mc = ModelConfig(**modelcfg.model_config_fields(_phi4_conf(), rehearse))
    assert mc.mixer_by_layer and mc.has_recurrent_state and not mc.is_moe
    assert get_model(mc).__name__.endswith("models.hybrid_decoder")
    preset = get_config("tiny-hybrid-decoder" if rehearse
                        else "phi4-mini-flash")
    assert dataclasses.replace(preset, name=mc.name) == mc
    if not rehearse:
        from llm_d_tpu.ops.attention import pallas_ineligible_reason
        from llm_d_tpu.ops.ssm import ssm1_pallas_ineligible_reason
        assert not ssm1_pallas_ineligible_reason(5120, 16, 128)
        assert pallas_ineligible_reason(32, 1280) is None
        assert mc.attn_head_dim == 128 and mc.layer_types[17] == \
            "full_attention"


def test_phi4flash_work_counts_real_rows_and_tokens():
    import phi4flashwork as work
    from readers import hybrid_roofline
    conf = _phi4_conf()
    assert work.mamba_layers(conf) == 9
    assert work.state_bytes(conf) == 16 * 5120 * 4 == 327680
    assert work.conv_tail_bytes(conf) == 3 * 5120 * 2
    assert work.kv_token_bytes(conf) == 5120
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    bw = peaks["hbm_bytes_per_s"]
    assert work.decode(conf, {"ssm_decode_rows": 8}, peaks) == pytest.approx(
        8 * 9 * (2 * 327680 + 30720) / bw)
    assert work.scan(conf, {"ssm_prefill_rows": 1,
                            "ssm_prefill_tokens": 2048}, peaks) == \
        pytest.approx(9 * (327680 + 2048 * (10240 + 32 + 160) * 2) / bw)
    # ISSUE 41's reckoning: 8 rows at 9k tokens read 0.37 GB of the shared
    # plane eight times: 3 GB
    assert work.xattn(conf, {"xattn_read_tokens": 8 * 8 * 9000}, peaks) \
        == pytest.approx(8 * 8 * 9000 * 5120 / bw)
    assert 2.9e9 < 8 * 8 * 9000 * 5120 < 3.0e9
    assert set(work.COUNTS) == {"scan", "decode", "xattn"}
    # without a trace a reader reads nothing (the parent, a CPU rehearsal)
    assert hybrid_roofline.read({"trace": None}, "scan", "phi4-mini-flash",
                                kernel="ssm1_chunk_scan") is None
    # a trace of a program without the counts (the parent's) reads nothing
    # and does not raise
    parts = str(BENCH / "testdata" / "v5e_parts.xplane.pb")
    assert hybrid_roofline.share(parts, "scan", conf, peaks,
                                 kernel="ssm1_chunk_scan") is None
    assert hybrid_roofline.share(parts, "xattn", conf, peaks,
                                 scopes=["llmd.attn.cross"]) is None
    # hand-made annotations: sums over the dispatches that carry the counts
    from types import SimpleNamespace as NS

    def dispatch(**stats):
        return NS(name="llmd.dispatch", stats=list(stats.items()))

    data = NS(planes=[
        NS(name="/host:CPU", lines=[NS(events=[
            dispatch(prefill_tokens=2048, ssm_prefill_rows=1,
                     ssm_prefill_tokens=2048, ssm_decode_rows=7),
            dispatch(prefill_tokens=0, ssm_prefill_rows=0,
                     ssm_prefill_tokens=0, ssm_decode_rows=8),
            dispatch(prefill_tokens=5),             # a span without them
            NS(name="llmd.post", stats=[("ssm_decode_rows", 99)])])]),
        NS(name="/device:TPU:0", lines=[NS(events=[
            dispatch(ssm_decode_rows=1000)])])])
    assert hybrid_roofline.annotation_counts(
        data, work.COUNTS["scan"]) == {"ssm_prefill_rows": 1,
                                       "ssm_prefill_tokens": 2048}
    assert hybrid_roofline.annotation_counts(
        data, work.COUNTS["decode"]) == {"ssm_decode_rows": 15}
    assert hybrid_roofline.annotation_counts(
        data, work.COUNTS["xattn"]) is None


def test_phi4flash_span_metrics_on_hand_made_spans():
    from readers import span_ratio

    def step(**attrs):
        return {"name": "engine.step", "dur": 0.1, "attrs": attrs}

    spans = [step(prefill_tokens=2048, decode_tokens=0, xdec_rows=0,
                  kv_dead_tokens=0, kv_held_tokens=9 * 2048),
             step(prefill_tokens=1000, decode_tokens=7, xdec_rows=8,
                  kv_dead_tokens=8 * 2537, kv_held_tokens=9 * 3048),
             step(prefill_tokens=0, decode_tokens=8, xdec_rows=8,
                  kv_dead_tokens=8 * 8 * 600, kv_held_tokens=9 * 8 * 1111),
             {"name": "engine.step", "dur": 0.1,         # the parent's spans
              "attrs": {"prefill_tokens": 5, "decode_tokens": 1}}]

    def read(name):
        d = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
        assert d["reader"] == "span_ratio"
        return span_ratio.read({"spans": spans}, **d["args"])

    assert read("xdec_row_share") == pytest.approx(100 * 16 / 3063)
    assert read("kv_window_dead_share.phi4flash") == pytest.approx(
        100 * (8 * 2537 + 64 * 600) / (9 * (2048 + 3048 + 8888)))
    assert span_ratio.read({"spans": spans[3:]}, "engine.step", "xdec_rows",
                           ["prefill_tokens", "decode_tokens"]) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_phi4flash_cell_rehearses_on_the_cpu(trace):
    """``run.py --rehearse --workload phi4flash.longdoc``: the harness's
    whole path (server, load generator, the checks (a)-(d) against
    ``references/phi4flash.py``) at the tiny preset; with ``--trace 1`` the
    new span attributes feed the new metrics."""
    import os
    import subprocess
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "phi4flash.longdoc", "--seed", str(2**31 + 4141), "--seconds", "4",
         "--trace", str(trace), "--rehearse"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    got = {k.removeprefix("cpu_rehearsal."): v["value"]
           for k, v in last["metrics"].items()}
    if trace:
        assert {"xdec_row_share", "kv_window_dead_share.phi4flash",
                "step_ms.mixed", "attn_query_fill_share", "queue_wait_p95_ms",
                "prefix_hit_share"} <= set(got)
        # prompts of 40-150 tokens, answers of 4-16: a tenth of the rows
        assert 2.0 < got["xdec_row_share"] < 30.0
        assert 10.0 < got["kv_window_dead_share.phi4flash"] < 8 / 9 * 100
        assert got["prefix_hit_share"] == 0.0       # no hit is granted
        # device metrics are read from a device trace only
        assert not {"ssm1_scan_roofline_share", "ssm1_decode_hbm_share",
                    "xattn_hbm_share", "device_part_share.cross",
                    "device_part_share.gmu"} & set(got)
    else:
        assert set(got) == {"ttft_p50_ms", "ttft_p95_ms", "setup_s"}


def test_phi4flash_mechanism_check_names_each_fault():
    import phi4flash_mechanism_check as tool
    import references.phi4flash as ref
    faults = {f for _, f in tool.WRONG if f}
    assert faults == {"int8_weights", "no_diff_term", "window_off_by_one",
                      "gmu_reads_gated", "cross_misses_chunk", "bf16_state"}
    assert tool.WRONG[0] == ("as published", None)
    assert tool.MUST_REFUSE <= faults and "int8_weights" in tool.MUST_REFUSE
    assert ref.FAULTS == set()          # nothing wrong in a served comparison
    tol = _phi4_conf()["correctness"]["reference_tolerance"]
    # between the largest served reading and the int8-weights one (PR 41)
    assert 0.0318 < tol["median"] < 0.0527 and 0.0664 < tol["p90"] < 0.1078
    # the window's fault shows one key more, the chunk's hides a chunk's own
    import jax.numpy as jnp
    pos = jnp.arange(10)
    right = ref.visible(pos, pos, 4, False)
    try:
        ref.FAULTS = {"window_off_by_one"}
        wide = ref.visible(pos, pos, 4, False)
        ref.FAULTS, ref.FAULT_CHUNK = {"cross_misses_chunk"}, 6
        blind = ref.visible(pos, pos, 0, True)
        same = ref.visible(pos, pos, 0, False)     # not a cross layer
    finally:
        ref.FAULTS, ref.FAULT_CHUNK = set(), 2048
    assert right.sum(1).tolist() == [1, 2, 3, 4, 4, 4, 4, 4, 4, 4]
    assert wide.sum(1).tolist() == [1, 2, 3, 4, 5, 5, 5, 5, 5, 5]
    assert blind.sum(1).tolist() == [1, 1, 1, 1, 1, 1, 7, 7, 7, 7]
    assert same.sum(1).tolist() == list(range(1, 11))


# ---- mellum2-12b-a2.5b and mellum2.ide (PR 46) ----

MELLUM_METRICS = [
    "device_idle_share.mellum2", "step_ms.decode.mellum2",
    "itl_p95_ms.mellum2", "device_part_share.experts.mellum2",
    "moe_roofline_share.mellum2", "attn_prefill_mxu_share.mellum2",
    "attn_decode_hbm_share.mellum2", "attn_kv_read_share.mellum2",
    "kv_window_dead_share.mellum2", "kv_full_pool_fill_share",
    "kv_window_pool_fill_share", "prefix_hit_lost_share"]


def _mellum_conf():
    return json.loads(
        (BENCH / "configs" / "mellum2-12b-a2.5b.json").read_text())


def test_mellum_cell_and_its_files():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = bench["workloads"][-2]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "mellum2.ide", "mellum2-12b-a2.5b", "ide-sessions", 1)
    assert len(cell["why"]) <= 200
    entry = bench["configs"][-2]
    conf = _mellum_conf()
    assert entry["name"] == conf["name"] == "mellum2-12b-a2.5b"
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types"]
    assert entry["source"] == conf["source"] and len(entry["source"]) < 200
    assert len(entry["why"]) <= 200
    assert entry["file"] == "benchmarks/configs/mellum2-12b-a2.5b.json"
    assert conf["reference"] == conf["model_type"] == "mellum"
    assert (BENCH / "references" / "mellum.py").exists()
    assert conf["serve_args"] == [
        "--quantization", "int8", "--max-num-seqs", "32",
        "--max-num-batched-tokens", "2048", "--block-size", "32",
        "--kv-cache-hbm-gb", "6.0", "--precompile-step-shapes"]
    for key in ("reduced_why", "assumed", "deployment"):
        assert conf[key], key
    chk = conf["correctness"]
    assert chk["prompt_lens"] == [24, 700, 2300, 6200]
    assert chk["prompt_lens"][1] < conf["sliding_window"] < 2048 \
        < chk["prompt_lens"][2]
    assert (chk["n_gen"], chk["ks"], chk["moe_op_sizes"]) == (
        24, [0, 4, 12], [16, 256, 2048])
    reh = conf["rehearsal"]
    assert reh["sizes"]["sliding_window"] == 48 < max(
        reh["correctness"]["prompt_lens"])
    assert reh["sizes"]["sliding_window"] % 32        # no multiple of a page
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(MELLUM_METRICS[0])     # appended in order (PR 47's follows)
    assert names[at:at + len(MELLUM_METRICS)] == MELLUM_METRICS
    assert names[at + len(MELLUM_METRICS):] == [
        "prefill_ahead_share", "moe_one_pass_share", *LING_METRICS]
    for name in MELLUM_METRICS:
        assert per_layer[name]["workloads"] == ["mellum2.ide"]
        d = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
        assert d["name"] == name
        assert (BENCH / "readers" / f"{d['reader']}.py").exists()
        assert {k: d[k] for k in ("unit", "better", "source", "layer",
                                  "moves")} == {
            k: per_layer[name][k] for k in ("unit", "better", "source",
                                            "layer", "moves")}
        if "config" in d["args"]:
            assert d["args"]["config"] == "mellum2-12b-a2.5b"
    # the accepted lists are as the parent has them
    assert per_layer["kv_window_dead_share"]["workloads"] == [
        "trinity-mini.docqa"]
    assert per_layer["moe_roofline_share.docqa"]["workloads"] == [
        "trinity-mini.docqa"]
    assert len(bench["workloads"]) == 9 and all(
        w["chips"] == 1 for w in bench["workloads"])


def test_mellum_mix_is_as_the_issue_wrote_it():
    mix = json.loads((BENCH / "traffic" / "ide-sessions.json").read_text())
    assert (mix["loop"], mix["arrivals"]["kind"]) == ("open", "poisson")
    assert mix["sessions"] == {
        "asks": 10, "ask_gap_s": 5.0, "prefix_tokens": {
            "dist": "loguniform", "min": 8192, "max": 32768}}
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 64, "max": 512}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 48,
                                    "sigma": 0.6, "min": 16, "max": 128}
    assert (mix["shared_prefix_tokens"], mix["warmup_seconds"],
            mix["drain_seconds"], mix["order_seed"]) == (0, 30, 60, 23)
    assert mix["rate_note"] and mix["order_note"] and mix["why"]
    # it sorts after the sessions mix at which the stock generator stops
    assert MIXES.index("ide-sessions.json") > MIXES.index(
        "docqa-trinity.json")
    # the longest prompt and its answer fit the positions served
    conf = _mellum_conf()
    assert 32768 + 512 + 128 <= conf["max_model_len"] \
        <= conf["max_position_embeddings"]
    # a window's requests: sessions x asks, every ask of a session on its
    # document, all inside the window
    reqs = traffic.build_schedule(mix, 7, 45, "window")["requests"]
    assert len(reqs) == 10 * round(mix["rate_rps"] * 45)
    docs = {}
    for r in reqs:
        docs.setdefault(r["session"], set()).add(r["session_tokens"])
    assert all(len(v) == 1 for v in docs.values())
    live = sum(next(iter(v)) for v in docs.values())
    warm = traffic.build_schedule(mix, 7, mix["warmup_seconds"],
                                  "warmup")["requests"]
    live += sum({r["session"]: r["session_tokens"] for r in warm}.values())
    # the window's contexts and the warm-up's, which ten asks a mean 5 s
    # apart keep alive into it: more than a uniform pool of the same bytes
    # holds (6 GiB / 24,576 B), under what the full group holds
    assert 6 * 2**30 // (12 * 2048) < live < 596_896


def test_mellum_config_holds_every_published_key():
    row = _catalog_row("Mellum2-12B-A2.5B-Instruct")
    conf = _mellum_conf()
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (conf[key], value) == (12, 28)
        elif key in ("layer_types", "mlp_layer_types"):
            assert conf[key] == value[:12]       # the published layers 0-11
        else:
            assert conf[key] == value, key
    assert conf["layer_types"] == (["sliding_attention"] * 3
                                   + ["full_attention"]) * 3
    named = " ".join(conf["assumed"])
    for key in set(conf["model_config_map"].values()) - set(row["config"]):
        assert key in named, key


@pytest.mark.parametrize("rehearse", [False, True])
def test_mellum_config_maps_onto_the_program(rehearse):
    import modelcfg
    from llm_d_tpu.models import get_model
    from llm_d_tpu.models.config import ModelConfig, RopeRule
    mc = ModelConfig(**modelcfg.model_config_fields(_mellum_conf(), rehearse))
    assert get_model(mc).__name__.endswith("models.moe")
    assert mc.kv_cache_groups == ("full_attention", "sliding_attention")
    assert mc.num_layers == 12 and mc.first_dense_layers == 0
    assert mc.layer_types.count("sliding_attention") == 9
    assert mc.qk_norm and mc.moe_renormalize and not mc.num_shared_experts
    assert mc.scoring_func == "softmax" and len(mc.rope_rules) == 2
    assert mc.layer_rope_rule == (1, 1, 1, 0) * 3
    if rehearse:
        assert (mc.sliding_window, mc.max_model_len) == (48, 512)
        return
    assert (mc.hidden_size, mc.num_heads, mc.num_kv_heads, mc.head_dim_,
            mc.num_experts, mc.num_experts_per_tok, mc.moe_intermediate_size,
            mc.vocab_size, mc.sliding_window, mc.max_model_len) == (
        2304, 32, 4, 128, 64, 8, 896, 98304, 1024, 36864)
    assert mc.rope_rules == (
        RopeRule(5e5, 16.0, 8192, 32.0, 1.0, 1.2772588722239782),
        RopeRule(5e5))
    from llm_d_tpu.engine.engine import derive_group_blocks, derive_num_blocks
    from llm_d_tpu.ops.attention import pallas_ineligible_reason
    assert pallas_ineligible_reason(32, 512) is None
    layout = get_model(mc).kv_cache_layout(mc)
    full, window = derive_group_blocks(
        mc, 32, 32, 2048,
        derive_num_blocks(6 * 2**30, layout, mc.num_layers, 32))
    # the window group by the rule, the full group the rest: more than
    # twice the tokens of one pool of every layer
    assert window == 32 * 98 * 3 // 2 + 1
    assert (full - 1) * 32 > 2 * (6 * 2**30 // (12 * 2048))
    assert (3 * full + 9 * window) * 32 * 2048 <= 6 * 2**30


def test_the_accepted_window_stack_keeps_one_pool_at_its_cells_limits():
    """``trinity-mini`` may go by groups (``kv_cache_groups``), but at its
    cell's limits the rule's window group (64 slots x 130 pages, half
    again) is no smaller than its pool of 10,240 pages: one group, the
    programs, block ids and metric meaning it had."""
    import modelcfg
    from llm_d_tpu.engine.engine import (
        derive_group_blocks, derive_num_blocks, window_group_blocks)
    from llm_d_tpu.models import get_model
    from llm_d_tpu.models.config import ModelConfig
    conf = modelcfg.load_config("trinity-mini")
    mc = ModelConfig(**modelcfg.model_config_fields(conf))
    assert mc.kv_cache_groups == ("full_attention", "sliding_attention")
    args = conf["serve_args"]
    arg = lambda name: args[args.index(name) + 1]           # noqa: E731
    seqs, budget, block = (int(arg(n)) for n in (
        "--max-num-seqs", "--max-num-batched-tokens", "--block-size"))
    layout = get_model(mc).kv_cache_layout(mc)
    pool = derive_num_blocks(int(float(arg("--kv-cache-hbm-gb")) * 2**30),
                             layout, mc.num_layers, block)
    assert pool == 10240
    assert window_group_blocks(mc.sliding_window, block, seqs, budget) > pool
    assert derive_group_blocks(mc, block, seqs, budget, pool) == (pool, 0)


def test_mellum_work_functions_read_its_widths():
    import kernelwork
    import partwork
    conf = _mellum_conf()
    assert kernelwork.kv_row_bytes(conf) == 2048
    assert kernelwork.prefill_flops(conf, 10) == 4.0 * 32 * 128 * 10
    assert partwork.expert_bytes(conf) == 3 * 2304 * 896 + (
        2 * 896 + 2304) * 4
    peaks = {"hbm_bytes_per_s": 1e9, "bf16_flops": 1e12}
    # few pairs: the touched experts' bytes bound; many: the dots
    assert partwork.experts(conf, {"moe_experts_touched": 64, "moe_pairs": 8},
                            peaks) == 64 * partwork.expert_bytes(conf) / 1e9
    assert partwork.experts(
        conf, {"moe_experts_touched": 64, "moe_pairs": 2048 * 8 * 12},
        peaks) == 2048 * 8 * 12 * 6.0 * 2304 * 896 / 1e12


def test_mellum_span_metrics_on_hand_made_spans():
    from readers import counter_ratio, span_ratio

    def step(**attrs):
        return {"name": "engine.step", "ts": 0.0, "dur": 0.01,
                "attrs": attrs}

    ctx = {"spans": [
        step(kv_pages_full=50, kv_pages_full_total=100, kv_pages_window=30,
             kv_pages_window_total=40, kv_held_tokens=1000,
             kv_dead_tokens=40, kv_read_tokens=300, kv_ctx_tokens=900),
        step(kv_pages_full=100, kv_pages_full_total=100, kv_pages_window=40,
             kv_pages_window_total=40, kv_held_tokens=1000,
             kv_dead_tokens=60, kv_read_tokens=300, kv_ctx_tokens=900),
        step(kv_held_tokens=5, kv_dead_tokens=0)],      # a one-group step
        "counters": {
            "before": {"vllm:prefix_cache_queries_total": 1000.0,
                       "llmd_tpu:prefix_cache_hit_tokens_lost_total": 10.0},
            "after": {"vllm:prefix_cache_queries_total": 3000.0,
                      "llmd_tpu:prefix_cache_hit_tokens_lost_total": 110.0}}}

    def read(name):
        d = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
        reader = {"span_ratio": span_ratio,
                  "counter_ratio": counter_ratio}[d["reader"]]
        return reader.read(ctx, **d["args"])

    assert read("kv_full_pool_fill_share") == 75.0
    assert read("kv_window_pool_fill_share") == 87.5
    assert read("kv_window_dead_share.mellum2") == pytest.approx(
        100 * 100 / 2005)
    assert read("attn_kv_read_share.mellum2") == pytest.approx(100 / 3)
    assert read("prefix_hit_lost_share") == 5.0
    # a program without the counts (the parent): nothing, and no error
    ctx["spans"] = [step(kv_held_tokens=5, kv_dead_tokens=0)]
    ctx["counters"]["after"].pop(
        "llmd_tpu:prefix_cache_hit_tokens_lost_total")
    assert read("kv_full_pool_fill_share") is None
    assert read("prefix_hit_lost_share") is None


@pytest.mark.parametrize("trace", [0, 1])
def test_mellum_cell_rehearses_on_the_cpu(trace):
    """``run.py --rehearse --workload mellum2.ide``: the harness's whole
    path (server, load generator with sessions, the checks (a)-(d) against
    ``references/mellum.py``) at the tiny preset, whose window of 48 is
    shorter than its longest prompt; with ``--trace 1`` the grouped
    cache's counts feed the new metrics."""
    import os
    import subprocess
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "mellum2.ide",
         "--seed", str(2**31 + 4646), "--seconds", "4", "--trace", str(trace),
         "--rehearse"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == 12
    got = {k.removeprefix("cpu_rehearsal."): v["value"]
           for k, v in last["metrics"].items()}
    if trace:
        assert {"kv_full_pool_fill_share", "kv_window_pool_fill_share",
                "kv_window_dead_share.mellum2", "attn_kv_read_share.mellum2",
                "prefix_hit_lost_share", "prefix_hit_share",
                "step_ms.decode.mellum2", "itl_p95_ms.mellum2",
                "step_ms.mixed"} <= set(got)
        assert 0.0 < got["kv_full_pool_fill_share"] <= 100.0
        assert 0.0 < got["kv_window_pool_fill_share"] <= 100.0
        # two asks in three find their session's document in both groups
        assert 30.0 < got["prefix_hit_share"] < 67.0
        assert got["prefix_hit_lost_share"] == 0.0      # nothing is evicted
        assert got["kv_window_dead_share.mellum2"] < 40.0
        assert got["attn_kv_read_share.mellum2"] < 100.0
        # device metrics are read from a device trace only
        assert not {"moe_roofline_share.mellum2", "device_idle_share.mellum2",
                    "attn_prefill_mxu_share.mellum2",
                    "attn_decode_hbm_share.mellum2",
                    "device_part_share.experts.mellum2"} & set(got)
    else:
        assert set(got) == {"ttft_p50_ms", "ttft_p95_ms", "setup_s"}


def test_mellum_mechanism_check_names_each_fault():
    import mellum_mechanism_check as tool
    import references.mellum as ref
    from llm_d_tpu.models.config import NO_WINDOW, get_config
    faults = {f for _, f in tool.WRONG if f}
    assert faults == {
        "all_full", "no_yarn", "yarn_everywhere", "attention_factor_1",
        "int4_experts"}
    # tried and printed, not required (the tool's docstring says why)
    assert tool.MUST_REFUSE == faults - {"attention_factor_1"}
    assert tool.WRONG[0] == ("as published", None)
    c = get_config("tiny-mellum")
    full, sliding = ref.rope_rules(c)
    assert full[1] == 4.0 and sliding[1] == 0.0
    assert tool.faulty(ref, c, None) == (c, (full, sliding), False)
    assert tool.faulty(ref, c, "all_full")[0].sliding_window == NO_WINDOW
    assert tool.faulty(ref, c, "no_yarn")[1] == (sliding, sliding)
    assert tool.faulty(ref, c, "yarn_everywhere")[1] == (full, full)
    assert tool.faulty(ref, c, "attention_factor_1")[1] == (
        full[:-1] + (1.0,), sliding)
    assert tool.faulty(ref, c, "int4_experts") == (c, (full, sliding), True)
    import jax.numpy as jnp
    q = jnp.arange(-128, 128, dtype=jnp.int8)
    got = tool.int4_experts({"w_up_q": q, "w_up_s": jnp.ones(3)})
    assert got["w_up_s"].shape == (3,)
    assert sorted(set(got["w_up_q"].tolist())) == list(range(-128, 113, 16))


# ---------------------------------------------------------------------------
# prefill_ahead_share (PR 47): the scheduler's short-first rule, as data
# ---------------------------------------------------------------------------

def test_prefill_ahead_share_is_a_data_file():
    """One per-layer metric appended to BENCHMARK.json and a file for the
    reader that is there; it reads the counter the scheduler's rule brought,
    0 where nothing was passed and nothing from a program without it."""
    from readers import counter_ratio
    from llm_d_tpu.utils.metrics import (PREFILL_AHEAD_TOKENS_METRIC,
                                         STEP_PREFILL_TOKENS_METRIC)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert bench["per_layer"][-2 - len(LING_METRICS)] == {
        "name": "prefill_ahead_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler and KV manager",
        "moves": "ttft_p95_ms",
        "workloads": ["mellum2.ide", "trinity-mini.docqa", "qwen3moe.chat",
                      "kanana2.batch", "phi4flash.longdoc"]}
    entry = bench["per_layer"][-2 - len(LING_METRICS)]
    d = json.loads((BENCH / "layer_metrics"
                    / "prefill_ahead_share.json").read_text())
    assert all(d[k] == entry[k] for k in (
        "name", "unit", "better", "source", "layer", "moves"))
    assert d["reader"] == "counter_ratio" and d["args"] == {
        "numerator": PREFILL_AHEAD_TOKENS_METRIC,
        "denominator": STEP_PREFILL_TOKENS_METRIC}
    assert set(entry["workloads"]) <= {w["name"] for w in bench["workloads"]}
    ctx = {"counters": {
        "before": {STEP_PREFILL_TOKENS_METRIC: 1000.0,
                   PREFILL_AHEAD_TOKENS_METRIC: 50.0},
        "after": {STEP_PREFILL_TOKENS_METRIC: 21000.0,
                  PREFILL_AHEAD_TOKENS_METRIC: 550.0}}}
    assert counter_ratio.read(ctx, **d["args"]) == 2.5
    ctx["counters"]["after"][PREFILL_AHEAD_TOKENS_METRIC] = 50.0
    assert counter_ratio.read(ctx, **d["args"]) == 0.0      # a control
    del ctx["counters"]["after"][PREFILL_AHEAD_TOKENS_METRIC]
    assert counter_ratio.read(ctx, **d["args"]) is None     # the parent


# ---------------------------------------------------------------------------
# moe_one_pass_share (PR 48): the one-pass int8 expert kernel, as data
# ---------------------------------------------------------------------------

def test_moe_one_pass_share_is_a_data_file():
    """One per-layer metric appended to BENCHMARK.json and a file for the
    reader that is there, listed for the five cells with int8 experts (each
    reports ``ttft_p95_ms``); the counter's own test is in
    tests/test_program_parts.py."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = bench["per_layer"][-1 - len(LING_METRICS)]
    assert entry == {
        "name": "moe_one_pass_share", "unit": "%", "better": "higher",
        "source": "program_span", "layer": "MoE kernels",
        "moves": "ttft_p95_ms",
        "workloads": ["qwen3moe.chat", "kanana2.batch", "trinity-mini.docqa",
                      "sdar.batch", "mellum2.ide"]}
    d = json.loads((BENCH / "layer_metrics"
                    / "moe_one_pass_share.json").read_text())
    assert all(d[k] == entry[k] for k in (
        "name", "unit", "better", "source", "layer", "moves"))
    assert d["reader"] == "span_ratio" and d["args"] == {
        "span": "engine.step", "numerator": "moe_one_pass_pairs",
        "denominator": ["moe_pairs"]}
    configs = {c["name"]: c["file"] for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    for name in entry["workloads"]:
        conf = json.loads((REPO / configs[cells[name]["config"]]).read_text())
        assert "--quantization" in conf["serve_args"], name
    assert sorted(entry["workloads"]) == sorted(
        n for n, w in cells.items() if "--quantization" in json.loads(
            (REPO / configs[w["config"]]).read_text())["serve_args"])



# ---- ling-3.0-flash-vl / ling3.longdoc (PR 49) ------------------------------

def _ling_conf():
    return json.loads((BENCH / "configs"
                       / "ling-3.0-flash-vl.json").read_text())


def test_ling_cell_and_its_files():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = bench["workloads"][-1]                   # appended last
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "ling3.longdoc", "ling-3.0-flash-vl", "longdoc", 1)
    assert len(cell["why"]) <= 200
    entry = bench["configs"][-1]
    conf = _ling_conf()
    assert entry["name"] == conf["name"] == "ling-3.0-flash-vl"
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size",
        "first_k_dense_replace"]
    assert entry["source"] == conf["source"] and len(entry["why"]) <= 200
    assert entry["file"] == "benchmarks/configs/ling-3.0-flash-vl.json"
    assert conf["reference"] == "ling_linear"
    assert "--quantization" not in conf["serve_args"]       # bf16 as published
    # the published layers 1-7: the one leading dense layer, a whole period
    # at 5 : 1 and one layer more
    assert conf["layer_types"] == ["linear_attention"] * 4 + [
        "full_attention"] + ["linear_attention"] * 2
    assert conf["num_hidden_layers"] == 7 and conf["first_k_dense_replace"] == 1
    assert conf["layer_types"][1:].count("linear_attention") == 5 \
        == conf["layer_group_size"] - 1
    pub = conf["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"], pub["vocab_size"],
            pub["first_k_dense_replace"]) == (42, 512, 157184, 2)
    assert conf["router_experts"] == 512 == 4 * conf["num_experts"]
    assert pub["vocab_size"] == 4 * conf["vocab_size"]
    assert conf["num_experts"] >= 8 and conf["vocab_size"] * 8 >= 157184
    for key in ("reduced_why", "assumed", "deployment", "memory_account"):
        assert conf[key] and "TO WRITE" not in json.dumps(conf[key]), key
    assert {"safe_gate", "output_gate", "qk_norm", "rotary", "linear_silu",
            "router", "mla_layer_of_a_group", "max_model_len",
            "init_scales"} <= set(conf["assumed"])
    chk = conf["correctness"]
    # two prompts of each length: a generated sequence reads as one sample
    assert chk["prompt_lens"] == [300, 2300, 6200] * 2 and chk["n_gen"] == 128
    assert max(chk["ks"]) < chk["n_gen"]
    assert "TO WRITE" not in json.dumps(chk)
    mix = json.loads((BENCH / "traffic" / "longdoc.json").read_text())
    assert mix["clients"] == int(conf["serve_args"][
        conf["serve_args"].index("--max-num-seqs") + 1]) == 16
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(LING_METRICS):] == LING_METRICS       # in order, last
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name in LING_METRICS:
        assert per_layer[name]["workloads"] == ["ling3.longdoc"]
        d = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
        assert (BENCH / "readers" / f"{d['reader']}.py").exists()
        assert all(d[k] == per_layer[name][k] for k in (
            "name", "unit", "better", "source", "layer", "moves"))
        assert "workloads" not in e2e[d["moves"]]       # the cell reports it
    for name in ("lin_scan_roofline_share", "lin_decode_hbm_share",
                 "mla_prefill_mxu_share.ling3", "mla_decode_hbm_share.ling3"):
        d = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
        assert d["args"]["config"] == "ling-3.0-flash-vl" and d["unit"] == "%"
    # no accepted list is lengthened
    assert all("ling3.longdoc" not in m.get("workloads", [])
               for m in bench["per_layer"] if m["name"] not in LING_METRICS)
    assert all("ling3.longdoc" not in m.get("workloads", [])
               for m in bench["end_to_end"])


def test_ling_config_holds_every_published_key():
    row = _catalog_row("Ling-3.0-flash-VL")
    conf = _ling_conf()
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in conf["reduced"]:
            assert conf[key] == value, key
    assert set(conf["reduced"]) - {"layer_types"} <= set(row["config"])
    # the layers kept carry no SwiGLU limit
    assert not any(conf["expert_swiglu_limit_list"][1:8]
                   + conf["share_expert_swiglu_limit_list"][1:8])
    kinds = ["full_attention" if (i + 1) % conf["layer_group_size"] == 0
             else "linear_attention" for i in range(42)]
    assert conf["layer_types"] == kinds[1:8]


@pytest.mark.parametrize("rehearse", [False, True])
def test_ling_config_maps_onto_the_program(rehearse):
    import modelcfg
    from llm_d_tpu.models import get_model
    from llm_d_tpu.models.config import FULL, LINEAR, ModelConfig
    conf = _ling_conf()
    mc = ModelConfig(**modelcfg.model_config_fields(conf, rehearse))
    model = get_model(mc)
    assert model.__name__.endswith("models.moe") and mc.use_mla
    assert mc.linear_by_layer and mc.mla_layer_kinds == (FULL,)
    assert mc.layer_types == tuple(conf["layer_types"])
    assert mc.layer_types.count(LINEAR) == 6
    assert (mc.first_dense_layers, mc.first_local_expert, mc.q_lora_rank,
            mc.num_shared_experts) == (1, 0, 0, 1)
    assert (mc.scoring_func, mc.moe_renormalize, mc.routed_scaling_factor,
            mc.lin_gate_floor, mc.lin_conv_kernel) == (
        "sigmoid", True, 2.5, -5.0, 4)
    assert model.kv_cache_layers(mc) == {"kv": 1}
    if not rehearse:
        assert (mc.num_experts, mc.num_held_experts, mc.num_experts_per_tok,
                mc.n_group, mc.topk_group) == (512, 128, 8, 8, 4)
        assert mc.mla_geometry(FULL) == (32, 0, 512, 128, 64, 128, 6e6, 0, 0)
        assert (mc.lin_num_heads, mc.lin_key_dim, mc.lin_value_dim) == (
            32, 128, 128)
        assert model.kv_cache_layout(mc) == {"kv": 640}
        pool = model.state_pool_shapes(mc, 17)
        assert pool["ssm"].shape == (6, 17, 32, 128, 128)
        assert pool["conv"].shape == (6, 17, 3, 12288)
        assert (mc.vocab_size, mc.max_model_len, mc.hidden_size,
                mc.intermediate_size, mc.moe_intermediate_size) == (
            39296, 32768, 2560, 6144, 768)
        from llm_d_tpu.ops.linear_attention import pallas_ineligible_reason
        assert not pallas_ineligible_reason(
            mc.lin_num_heads, mc.lin_key_dim, mc.lin_value_dim)


def test_kda_work_counts_real_rows_and_tokens():
    import kdawork
    from readers import lin_roofline, scope_share
    conf = _ling_conf()
    assert kdawork.geometry(conf) == (6, 32, 128, 128)
    assert kdawork.state_bytes(conf) == 2 * 2**20
    # 15 decode rows: each row's state read and written once in six layers
    assert kdawork.decode_state_bytes(conf, 15) == 15 * 6 * 4 * 2**20
    # a 2,048-token chunk of one row
    assert kdawork.scan_flops(conf, 2048) == 2048 * 6 * 32 * 6 * 128 * 128
    assert kdawork.scan_bytes(conf, 1, 2048) == 6 * (
        2 * 2**20 + 2048 * 32 * 5 * 128 * 2)
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    counts = {"ssm_decode_rows": 15, "ssm_prefill_rows": 1,
              "ssm_prefill_tokens": 2048}
    assert lin_roofline.least_seconds("decode", conf, counts, peaks) == (
        15 * 6 * 4 * 2**20 / peaks["hbm_bytes_per_s"])
    assert lin_roofline.least_seconds("scan", conf, counts, peaks) == max(
        kdawork.scan_flops(conf, 2048) / peaks["bf16_flops"],
        kdawork.scan_bytes(conf, 1, 2048) / peaks["hbm_bytes_per_s"])
    with pytest.raises(ValueError, match="unknown bound"):
        lin_roofline.least_seconds("other", conf, counts, peaks)
    # without a trace a reader reads nothing (the parent, a CPU rehearsal)
    assert lin_roofline.read({"trace": None}, "delta_chunk_scan", "scan",
                             "ling-3.0-flash-vl") is None
    assert scope_share.read({"trace": None}, ["llmd.lin.state"]) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_ling_cell_rehearses_on_the_cpu(trace):
    """``run.py --rehearse --workload ling3.longdoc``: the harness's whole
    path (server, load generator, the checks (a)-(d) against
    ``references/ling_linear.py``) at the tiny preset."""
    import os
    import subprocess
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "ling3.longdoc", "--seed", str(2**31 + 4949), "--seconds", "4",
         "--trace", str(trace), "--rehearse"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=900)
    assert out.returncode == 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    got = {k.removeprefix("cpu_rehearsal."): v["value"]
           for k, v in last["metrics"].items()}
    if not trace:
        assert set(got) == {"ttft_p50_ms", "ttft_p95_ms", "setup_s"}
        return
    assert {"step_ms.mixed", "step_ms.decode.ling3", "itl_p95_ms.ling3",
            "attn_query_fill_share", "prefix_hit_share",
            "queue_wait_p95_ms"} <= set(got)
    assert got["prefix_hit_share"] == 0.0       # off for a recurrent state
    # device metrics are read from a device trace only
    assert not {"device_part_share.linear", "lin_scan_roofline_share",
                "lin_decode_hbm_share", "device_idle_share.ling3",
                "mla_prefill_mxu_share.ling3"} & set(got)


def test_kda_mechanism_check_names_each_fault():
    import references.ling_linear as ref
    import kda_mechanism_check
    # the tool reads the faults off the reference a configuration names
    assert not hasattr(kda_mechanism_check, "WRONG")
    assert {name for name, _, _ in ref.FAULT_TABLE} == {
        "no_decay", "no_delta", "beta_one", "conv_tail_zeroed",
        "stale_state", "no_out_gate", "no_group_limit", "int8_weights"}
    assert all(what and must is True for _, what, must in ref.FAULT_TABLE)
    source = open(ref.__file__).read()
    assert all(f'"{name}" in FAULTS' in source
               or f'"{name}" not in FAULTS' in source
               for name, _, _ in ref.FAULT_TABLE)
    assert ref.FAULTS == set()          # nothing wrong in a served comparison
    assert ref.CHUNK == int(_ling_conf()["serve_args"][
        _ling_conf()["serve_args"].index("--max-num-batched-tokens") + 1])
    tol = _ling_conf()["correctness"]["reference_tolerance"]
    # PR 49, on the chip, 768 positions a seed (calls E1, F1, G1; (median,
    # p90)): every served reading passes BOTH limits; the median refuses every
    # reading of the int8-rounded reference, and both limits every fault read
    served = [(0.0873, 0.2483), (0.1178, 0.3592), (0.1012, 0.2803),
              (0.0949, 0.2893), (0.0807, 0.2302), (0.0883, 0.2572),
              (0.1027, 0.2823), (0.0928, 0.2804), (0.0878, 0.2637),
              (0.1026, 0.2911), (0.1028, 0.2893), (0.1040, 0.2871),
              (0.0866, 0.2702)]
    int8 = [(0.1606, 0.4195), (0.2018, 0.5216), (0.1566, 0.4105),
            (0.1813, 0.4759), (0.1630, 0.4332), (0.1652, 0.4562),
            (0.1744, 0.4559), (0.1739, 0.4409), (0.1721, 0.4707),
            (0.1623, 0.4381), (0.1780, 0.4631), (0.1699, 0.4574)]
    faults = [(2.6003, 3.9230), (2.1988, 3.2881), (1.9355, 3.0323),
              (1.7472, 2.8449), (0.2529, 0.6506), (0.1914, 0.5640),
              (0.1609, 0.7489), (0.2099, 0.6432), (0.1795, 0.7159),
              (0.3157, 0.7261)]
    assert all(m < tol["median"] and p < tol["p90"] for m, p in served)
    assert all(m > tol["median"] for m, _ in int8)
    assert all(m > tol["median"] and p > tol["p90"] for m, p in faults)
    # room on both sides of each limit, a tenth at least
    assert 1.1 * max(m for m, _ in served) < tol["median"] \
        < min(m for m, _ in int8) / 1.1
    assert 1.1 * max(p for _, p in served) < tol["p90"] \
        < min(p for _, p in faults) / 1.1


def test_lowered_step_programs_names_a_decode_and_a_mixed_program(
        capsys, monkeypatch):
    """The tool that holds a PR's step programs against its parent's: two
    lines a configuration, the same text whenever the tree is the same."""
    import lowered_step_programs as tool
    lines = []
    for _ in range(2):
        monkeypatch.setattr(sys, "argv", ["tool", "ling-3.0-flash-vl"])
        assert tool.main() == 0
        lines.append(capsys.readouterr().out.strip().splitlines())
    assert lines[0] == lines[1] and len(lines[0]) == 2
    (decode, mixed) = (ln.split(" (")[1].split(")")[0] for ln in lines[0])
    assert decode.endswith(", 1") and not mixed.endswith(", 1")
    assert all(len(ln.split()[-2]) == 40 for ln in lines[0])      # a sha1
