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
