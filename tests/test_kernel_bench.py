"""scripts/kernel_bench.py (interpret mode) + bench.py attribution table.

The microbench's --interpret mode is the CI contract: every member of
the int8 MoE kernel family (dense / routed / grouped / streamed) runs
through its REAL ``ops.moe`` dispatch glue on the Pallas interpreter, so
a glue regression in any kernel fails tier-1 without a TPU.  The
attribution-table builder is pure arithmetic over bench sweeps and is
pinned here directly.
"""

import importlib.util
import json
import pathlib

REPO = pathlib.Path(__file__).resolve().parent.parent


def _kernel_bench():
    spec = importlib.util.spec_from_file_location(
        "kernel_bench", REPO / "scripts" / "kernel_bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_bench_interpret_exercises_all_paths(tmp_path, capsys):
    """One interpreted sweep point per kernel: all four paths produce a
    timing (i.e. their glue traced, compiled and ran), the crossover
    block is derived, and timings are flagged invalid."""
    mod = _kernel_bench()
    out = tmp_path / "kb.json"
    rc = mod.main(["--interpret", "--t-sweep", "8,48", "--out", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc == json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["interpret"] is True and doc["timings_valid"] is False
    assert [p["T"] for p in doc["points"]] == [8, 48]
    for p in doc["points"]:
        for path in ("dense", "routed", "grouped", "streamed"):
            assert isinstance(p["ms"][path], float) and p["ms"][path] > 0, \
                (p, path)
    xo = doc["crossover"]
    assert set(xo["fastest_by_T"]) == {"8", "48"}
    for key in ("LLMD_MOE_DENSE_KERNEL_MAX_T", "LLMD_MOE_GROUPED_MIN_T",
                "LLMD_MOE_PREFILL_KERNEL"):
        assert key in xo


def test_kernel_bench_a2a_sweep_interpret(tmp_path, capsys):
    """--a2a: the tokens x collective-dtype EP exchange sweep runs all
    three wire modes (bf16 / int8 dispatch-only / int8 both ways)
    through the REAL expert_ffn_a2a glue on the 8-device CPU mesh, with
    the per-mode wire-byte accounting alongside."""
    mod = _kernel_bench()
    out = tmp_path / "a2a.json"
    rc = mod.main(["--a2a", "--interpret", "--t-sweep", "16,32",
                   "--out", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc == json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["mode"] == "ep_a2a"
    assert doc["timings_valid"] is False
    assert doc["shapes"]["ep"] == 8
    assert [p["T"] for p in doc["points"]] == [16, 32]
    for p in doc["points"]:
        for mode in ("bf16", "int8-dispatch", "int8"):
            assert isinstance(p["ms"][mode], float) and p["ms"][mode] > 0
        # The byte accounting the sweep exists to show (at this tiny
        # H=64 the per-row scale+index overhead is at its relative
        # worst; the 0.35x acceptance ratio at serving hidden sizes is
        # pinned in test_collective_quant.py).
        b = p["wire_bytes_per_token_layer"]
        assert b["int8"] < 0.5 * b["f32-combine"]
        assert b["int8-dispatch"] < b["bf16"] < b["f32-combine"]


def test_kernel_bench_spec_sweep_interpret(tmp_path, capsys):
    """--spec: the draft-depth (K) sweep runs the REAL draft-and-verify
    engine (scheduler draft allocation, fused spec program, rejection
    rollback) on CPU at a fixed seeded acceptance — one engine per K,
    accepted-tok/s + measured acceptance per point, a recommended K."""
    mod = _kernel_bench()
    out = tmp_path / "spec.json"
    rc = mod.main(["--spec", "--interpret", "--k-sweep", "1,2",
                   "--out", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc == json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["mode"] == "spec" and doc["timings_valid"] is False
    assert [p["K"] for p in doc["points"]] == [1, 2]
    for p in doc["points"]:
        assert p["accepted_tok_s"] > 0 and p["ms_per_step"] > 0
        # The seeded coin at 0.7/draft must actually accept drafts.
        assert p["acceptance_pct"] and p["acceptance_pct"] > 20
    assert doc["recommended_k"] in (1, 2)


def test_kernel_bench_eplb_sweep_interpret(tmp_path, capsys):
    """--eplb: the skew x move-budget migration sweep drives the REAL
    live-migration machinery (delta planner, double-buffered staging,
    atomic flip) on the multi-device CPU mesh: a tighter budget costs
    more ticks for the same moves, the flip cuts the measured shard
    imbalance, and the post-flip device weights match the logical
    gather exactly."""
    mod = _kernel_bench()
    out = tmp_path / "eplb.json"
    rc = mod.main(["--eplb", "--interpret", "--out", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc == json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["mode"] == "eplb" and doc["timings_valid"] is False
    by_key = {(p["skew"], p["budget"]): p for p in doc["points"]}
    assert len(by_key) == 4
    for p in doc["points"]:
        assert p["weights_consistent"] is True
        assert p["moves"] > 0 and p["staged_mb"] > 0
        # Budget-limited staging: ticks >= ceil(moves/budget), plus the
        # final flip tick.
        assert p["ticks"] >= -(-p["moves"] // p["budget"])
        assert p["imbalance_after"] <= p["imbalance_before"]
    for skew in (0.8, 1.2):
        tight, loose = by_key[(skew, 1)], by_key[(skew, 4)]
        assert tight["moves"] == loose["moves"]
        assert tight["ticks"] > loose["ticks"]


def test_kernel_bench_mixed_sweep_interpret(tmp_path, capsys):
    """--mixed: the mixed-round fusion sweep times ONE streamed program
    over the combined prefill-chunk + decode/verify population against
    the same work as two programs (streamed chunk + decode-regime
    kernel), through the REAL ops.moe kernel paths on the interpreter."""
    mod = _kernel_bench()
    out = tmp_path / "mixed.json"
    rc = mod.main(["--mixed", "--interpret", "--t-sweep", "16,32",
                   "--out", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc == json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["mode"] == "mixed" and doc["timings_valid"] is False
    assert doc["shapes"]["Qv"] == doc["shapes"]["spec_k"] + 1
    assert [p["chunk_T"] for p in doc["points"]] == [16, 32]
    for p in doc["points"]:
        # Verify rows occupy K+1 slots each in the fused stream.
        assert p["total_T"] == \
            p["chunk_T"] + p["decode_S"] * doc["shapes"]["Qv"]
        assert p["decode_path"] in ("dense", "routed", "streamed")
        for prog in ("fused", "split"):
            assert isinstance(p["ms"][prog], float) and p["ms"][prog] > 0
            assert p["tok_s"][prog] > 0


def test_kernel_bench_mixed_multistep_axis_interpret(tmp_path, capsys):
    """--mixed --multistep (round 16): the N-round axis compiles ONE
    lax.scan program chaining N mixed rounds (single dispatch + single
    host sync) and times it against N single dispatches with a sync
    each — the ops-level mirror of the engine's fused-multistep
    amortization.  Both columns must actually run on the interpreter."""
    mod = _kernel_bench()
    out = tmp_path / "mixed_ms.json"
    rc = mod.main(["--mixed", "--interpret", "--t-sweep", "16",
                   "--multistep", "1,2", "--out", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc == json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["mode"] == "mixed" and doc["timings_valid"] is False
    rows = doc["multistep"]
    assert [r["N"] for r in rows] == [1, 2]
    for r in rows:
        for prog in ("scan", "singles"):
            assert isinstance(r["ms"][prog], float) and r["ms"][prog] > 0
        # The dispatch accounting the axis exists to show: the scanned
        # program pays 1/N host syncs per round.
        assert r["syncs_per_round"]["scan"] == round(1.0 / r["N"], 3)
        assert r["syncs_per_round"]["singles"] == 1.0
    # Without the flag the document carries no multistep block.
    rc = mod.main(["--mixed", "--interpret", "--t-sweep", "16"])
    assert rc == 0
    doc2 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "multistep" not in doc2


def test_kernel_bench_respects_path_caps(tmp_path):
    """--dense-max-t / --routed-max-t null out the capped paths (the
    shapes a real chip cannot run) and the recommendation still derives
    from the remaining ones."""
    mod = _kernel_bench()
    out = tmp_path / "kb.json"
    mod.main(["--interpret", "--t-sweep", "8,48", "--dense-max-t", "8",
              "--routed-max-t", "8", "--out", str(out)])
    doc = json.loads(out.read_text())
    by_t = {p["T"]: p["ms"] for p in doc["points"]}
    assert by_t[48]["dense"] is None and by_t[48]["routed"] is None
    assert by_t[48]["grouped"] is not None
    assert by_t[48]["streamed"] is not None
    assert doc["crossover"]["LLMD_MOE_PREFILL_KERNEL"] in (
        "streamed", "grouped")


def test_regression_gate_three_metrics_band_verdict():
    """The gate covers dense-bs64 decode, moe-bs256 decode AND
    moe-bs64 prefill; a metric regresses only when its whole band sits
    below the best recorded number, and a prefill row carries its MFU."""
    import bench

    dense = {64: {"decode_tok_s": 11000.0,
                  "decode_tok_s_band": [10800.0, 11500.0]}}
    moe = {256: {"decode_tok_s": 16000.0,
                 "decode_tok_s_band": [15500.0, 15900.0],
                 "decode_hbm_roofline_pct": 40.0,
                 "decode_hbm_roofline_pct_band": [38.0, 41.5]},
           64: {"prefill_tok_s": 20000.0, "prefill_mfu_pct": 21.0,
                "prefill_tok_s_band": [19000.0, 21000.0]}}
    gate = bench._regression_gate(dense, moe)
    # dense: band max 11500 >= 11196.7 best -> not regressed.
    assert gate["dense_bs64_regressed"] is False
    # moe decode: whole band below 16060.6 -> regressed.
    assert gate["moe_bs256_regressed"] is True
    # prefill: median above best, band clears it, MFU rides along.
    assert gate["moe_prefill_tok_s_bs64_regressed"] is False
    assert gate["moe_prefill_tok_s_bs64_delta_pct"] > 0
    assert gate["moe_prefill_tok_s_bs64_mfu_pct"] == 21.0
    # Roofline YIELD at bs256 is first-class: band clears the 36.9 best
    # (not regressed) but the 55% target is not met yet.
    assert gate["moe_decode_roofline_bs256_regressed"] is False
    assert gate["moe_decode_roofline_bs256_target_pct"] == 55.0
    assert gate["moe_decode_roofline_bs256_meets_target"] is False
    # A yield collapse regresses even when raw tok/s would pass.
    gate_low = bench._regression_gate(dense, {
        256: {"decode_tok_s": 17000.0,
              "decode_tok_s_band": [16500.0, 17500.0],
              "decode_hbm_roofline_pct": 30.0,
              "decode_hbm_roofline_pct_band": [28.0, 32.0]}})
    assert gate_low["moe_bs256_regressed"] is False
    assert gate_low["moe_decode_roofline_bs256_regressed"] is True
    # No band (single sample) -> no verdict; missing roofline key (old
    # sweeps) -> metric skipped, not a crash.
    gate2 = bench._regression_gate(
        {64: {"decode_tok_s": 11000.0}},
        {256: {"decode_tok_s": 16000.0},
         64: {"prefill_tok_s": 20000.0, "prefill_mfu_pct": 21.0}})
    assert gate2["dense_bs64_regressed"] is None
    assert gate2["moe_prefill_tok_s_bs64_regressed"] is None
    assert gate2["moe_decode_roofline_bs256_delta_pct"] is None
