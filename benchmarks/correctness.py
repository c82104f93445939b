"""The comparison that decides ``correct``.  Runs outside the timed window,
at the cell's widths, against the SERVED engine (no second engine would fit
beside a chip filled as a deployment fills it).

  (a) well-formed service, over HTTP: a greedy request repeated gives the
      same tokens; every answer has HTTP 200, as many tokens as asked, and
      ends in [DONE] (the window's own requests are held to the same by
      ``failed``).
  (b) the MoE kernels, op level: ``expert_ffn`` as the step programs call it
      on the engine's own int8 weights against dequantise-then-``ragged_dot``
      on the same inputs, routing given, in the three token regimes
      (dense <= 64 < routed <= 512 < streamed).  Tight: nothing is chaotic.
  (c) decode against prefill: a request generates o_1..o_n with chosen-token
      logprobs l_1..l_n through the DECODE kernels and the cache; then one
      token is asked after prompt + o_1..o_k, which the PREFILL kernels
      compute (from cached blocks where there are any).  Where it chooses
      the same token its logprob must agree with l_(k+1): two different
      kernels over the same weights.
  (d) the plain reference, THE SAME TOKEN ON BOTH SIDES: the configuration's
      ``references/<reference>.py`` (float32, published equations, MLA
      unabsorbed, experts one by one) runs over prompt + o_1..o_(n-1) and
      gives log p(o_j | before) for every generated token; the served
      chosen-token logprob l_j is held against exactly that number.  l_1
      comes from the prefill kernels (in chunks, for the prompt longer than
      a step's token budget), l_2..l_n from the decode kernels through the
      cache.  A served hidden state that has nothing to do with the
      reference's gives the chosen token the log-probability of a random
      one (4 nats lower, at a vocabulary of 150k), whatever it is.

(c) and (d) generate through the served engine's own request path
(``AsyncEngine.generate`` on the server's loop), because only there do a
token's id and its logprob come out together: HTTP carries ids in stream
frames and logprobs in whole answers, never both.

What the tolerances mean.  The weights are random, so router scores lie
close together: rounding (bf16 activations, int8 expert kernels) changes the
expert set of some token in some layer nearly everywhere (128 experts, eight
MoE layers), which moves a position by hundredths to tenths of a nat and says
nothing of a kernel.  How much is a property of the configuration (softmax or
sigmoid scores, experts per token, shared experts), so each configuration's
file carries the tolerance of (d), a few times what was measured on the chip
(PERF.md §6, PR 23), on the MEDIAN and the 90th percentile of |served -
reference| over all generated positions; the maximum is not held (one flipped
expert set in eight layers can move one position by nats).  ``sensitivity``
shows which faults that refuses and which it cannot see.
"""

from __future__ import annotations

import importlib
import json
import math
import statistics
import urllib.request
from typing import Any, Callable, Dict, List, Sequence, Tuple

import numpy as np

MOE_OP_REL_RMS_TOL = 2e-2
# (c), where prefill chose the token decode chose: measured on the chip (PR
# 23, four runs) median 0.0006-0.006, max 0.03-0.16.  The maximum keeps PR
# 21's bound (chip_smoke.py): one flipped expert set moves one position.
LOGPROB_TOL_MEDIAN = 0.05
LOGPROB_TOL_MAX = 1.5

Generate = Callable[[List[int], int], Tuple[List[int], List[float]]]


class Incorrect(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Incorrect(what)


def post(url: str, body: Dict[str, Any], timeout: float = 600):
    req = urllib.request.Request(
        url + "/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def stream_ids(url: str, model: str, prompt: List[int], n: int
               ) -> List[int]:
    """One streamed greedy request; its token ids, checked for form."""
    with post(url, {"model": model, "prompt": prompt, "max_tokens": n,
                    "stream": True, "temperature": 0.0,
                    "ignore_eos": True}) as r:
        check(r.status == 200, f"stream: HTTP {r.status}")
        ids, done = [], False
        for line in r:
            if not line.startswith(b"data: "):
                continue
            payload = line[6:].strip()
            if payload == b"[DONE]":
                done = True
                break
            ids.extend(json.loads(payload)["llmd"]["tok"])
    check(done, "stream did not end in [DONE]")
    check(len(ids) == n, f"stream gave {len(ids)} tokens, {n} asked")
    return ids


def well_formed(url: str, model: str, vocab: int, seed: int,
                block_size: int, n: int = 8) -> None:
    """(a), over HTTP."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xC0]))
    # Shorter than one KV block: the second run cannot take the prefix
    # cache's path, so program and inputs are identical.
    short = rng.integers(1, vocab, size=min(20, block_size - 1)).tolist()
    first, again = (stream_ids(url, model, short, n) for _ in range(2))
    check(first == again, "a repeated greedy request gave different tokens")
    with post(url, {"model": model, "prompt": short, "max_tokens": n,
                    "temperature": 0.0, "ignore_eos": True,
                    "logprobs": 0}) as r:
        check(r.status == 200, f"completion: HTTP {r.status}")
        out = json.loads(r.read())
    lps = out["choices"][0]["logprobs"]["token_logprobs"]
    check(out["usage"]["completion_tokens"] == n and len(lps) == n
          and all(math.isfinite(x) and x <= 1e-6 for x in lps),
          f"completion: wrong count or non-finite logprobs ({len(lps)}/{n})")


def _reference_fn(engine, name: str, k: int):
    """jit of the configuration's plain reference: (params, tokens, chosen
    ids [k]) -> per position the reference's log p of the chosen token and
    its own best log p."""
    import jax
    import jax.numpy as jnp
    mod = importlib.import_module(f"references.{name}")
    c = engine.model_config

    def fn(params, tokens, chosen):
        lp = mod.tail_logprobs(params, c, tokens, k)
        return (jnp.take_along_axis(lp, chosen[:, None], 1)[:, 0],
                lp.max(axis=-1))

    return jax.jit(fn)


def generate_cases(generate: Generate, vocab: int, seed: int,
                   prompt_lens: Sequence[int], n_gen: int
                   ) -> List[Dict[str, Any]]:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xD0]))
    cases = []
    for n in prompt_lens:
        prompt = rng.integers(1, vocab, size=n).tolist()
        ids, lps = generate(prompt, n_gen)
        check(len(ids) == n_gen and len(lps) == n_gen
              and all(math.isfinite(x) and x <= 1e-6 for x in lps),
              f"a request of {n} tokens gave {len(ids)} tokens and "
              f"{len(lps)} logprobs, {n_gen} asked, or a non-finite one")
        cases.append({"prompt": prompt, "ids": ids, "lps": lps})
    return cases


def decode_vs_prefill(generate: Generate, cases, ks: Sequence[int], log
                      ) -> Dict[str, Any]:
    """(c)."""
    diffs, flipped = [], 0
    for case in cases:
        for k in ks:
            one, lp = generate(case["prompt"] + case["ids"][:k], 1)
            if one[0] != case["ids"][k]:
                flipped += 1            # a near-tie: not the same token
                continue
            diffs.append(abs(lp[0] - case["lps"][k]))
    total = len(cases) * len(ks)
    check(flipped <= total // 2,
          f"decode-vs-prefill: prefill chose another token than decode in "
          f"{flipped} of {total} positions")
    med, worst = statistics.median(diffs), max(diffs)
    log(f"   decode vs prefill: |d logprob| median {med:.4f}, max "
        f"{worst:.4f} over {len(diffs)} positions ({flipped} chose another "
        f"token); tolerance median {LOGPROB_TOL_MEDIAN}, max "
        f"{LOGPROB_TOL_MAX}")
    check(med <= LOGPROB_TOL_MEDIAN and worst <= LOGPROB_TOL_MAX,
          f"decode and prefill kernels disagree: |d logprob| median "
          f"{med:.4f}, max {worst:.4f}")
    return {"median": med, "max": worst, "positions": len(diffs),
            "flipped": flipped}


def against_reference(fn, params, cases) -> List[Dict[str, float]]:
    """Per generated position of every case: the served logprob, the
    reference's of the same token, and the reference's best."""
    import jax.numpy as jnp
    rows = []
    for case in cases:
        ids = case["ids"]
        tokens = jnp.asarray(case["prompt"] + ids[:-1], jnp.int32)
        same, best = (np.asarray(a, np.float64) for a in fn(
            params, tokens, jnp.asarray(ids, jnp.int32)))
        check(np.isfinite(same).all() and np.isfinite(best).all(),
              "reference: non-finite logprob")
        for j, lp in enumerate(case["lps"]):
            rows.append({"prompt_tokens": len(case["prompt"]), "j": j,
                         "served": lp, "reference": float(same[j]),
                         "reference_best": float(best[j])})
    return rows


def summarise(rows) -> Dict[str, float]:
    d = [abs(r["served"] - r["reference"]) for r in rows]
    return {"positions": len(d), "median": statistics.median(d),
            "p90": float(np.quantile(d, 0.9)), "max": max(d),
            # where the served argmax is also the reference's
            "argmax_agrees": sum(r["reference_best"] - r["reference"] < 1e-6
                                 for r in rows) / len(rows)}


def refusal(s: Dict[str, float], tol: Dict[str, float]) -> str:
    """Why (d) refuses this summary, or ''."""
    return "; ".join(f"{k} {s[k]:.4f} > {tol[k]}" for k in ("median", "p90")
                     if s[k] > tol[k])


def _describe(s) -> str:
    return (f"|d logprob| median {s['median']:.4f}, p90 {s['p90']:.4f}, max "
            f"{s['max']:.4f} over {s['positions']} positions; the served "
            f"argmax is the reference's in {100 * s['argmax_agrees']:.0f} %")


def reference_rows(engine, name: str, cases) -> List[Dict[str, float]]:
    return against_reference(_reference_fn(engine, name,
                                           len(cases[0]["ids"])),
                             engine.params, cases)


def reference_check(rows, name: str, tol: Dict[str, float], log) -> None:
    """(d)."""
    s = summarise(rows)
    log(f"   served vs plain float32 reference ({name}), the same token on "
        f"both sides: " + _describe(s) + f"; tolerance median "
        f"{tol['median']}, p90 {tol['p90']}")
    why = refusal(s, tol)
    check(not why, "the served engine and the plain reference disagree: "
          + why)


# --------------------------------------------------------------------------
# What (d) refuses: the reference run again with one thing wrong.  The
# served side is the same recorded answers, so a difference of this size
# between the two sides, whichever side has it, gets this verdict.
# Never run by the driver (run.py --check-sensitivity).
# --------------------------------------------------------------------------

def _scaled(params, group: str, leaf: str, factor: float, layer=None):
    """The tree with one leaf scaled, in every layer or in ``layer``."""
    out = dict(params)
    if group in params and leaf in params[group]:
        w = params[group][leaf]
        out[group] = dict(params[group])
        if layer is None:
            out[group][leaf] = (w.astype("float32") * factor).astype(w.dtype)
        else:
            out[group][leaf] = w.at[layer].set(
                (w[layer].astype("float32") * factor).astype(w.dtype))
    return out


def sensitivity(engine, name: str, cases, tol, log) -> Dict[str, Any]:
    import jax.numpy as jnp

    import references.plain as plain
    k = len(cases[0]["ids"])
    p = engine.params
    both = ("moe_layers", "dense_layers", "layers")
    edits_of = {
        "nothing (the check itself)": [],
        "attention output x 1.05, every layer": [
            ("o_proj", 1.05, both)],
        "attention output x 1.02, every layer": [
            ("o_proj", 1.02, both)],
        "expert down-projection scale x 1.03": [
            ("w_down_s", 1.03, ("moe_layers",))],
        "expert down-projection scale x 1.01": [
            ("w_down_s", 1.01, ("moe_layers",))],
        "attention output x 1.2, every layer": [
            ("o_proj", 1.2, both)],
        "expert down-projection scale x 1.1": [
            ("w_down_s", 1.1, ("moe_layers",))],
        "attention output of the first MoE layer x 0": [
            ("o_proj", 0.0, ("moe_layers",), 0)],
        "router weights x 0 (every token to the same experts)": [
            ("router", 0.0, ("moe_layers",))],
    }
    rows = {}
    fn = _reference_fn(engine, name, k)
    for what, edits in edits_of.items():
        tree = p
        for leaf, factor, groups, *layer in edits:
            for g in groups:
                tree = _scaled(tree, g, leaf, factor, *layer)
        rows[what] = against_reference(fn, tree, cases)

    # An int8 KV cache: keys and values rounded to 8 bits per (token, head)
    # row before attention, as a quantised cache would hold them.
    exact = plain.causal_attention

    def int8_rows(x):
        scale = jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0
        return jnp.round(x / scale) * scale

    plain.causal_attention = lambda q, kk, v, s: exact(
        q, int8_rows(kk), int8_rows(v), s)
    try:
        rows["keys and values rounded to int8 rows (an int8 KV cache)"] = \
            against_reference(_reference_fn(engine, name, k), p, cases)
    finally:
        plain.causal_attention = exact
    for what, r in rows.items():
        s = summarise(r)
        log(f"   SENSITIVITY {what}: {_describe(s)} -> "
            + (f"REFUSED ({refusal(s, tol)})" if refusal(s, tol)
               else "passes"))
    return rows


def moe_op_parity(engine, seed: int, sizes: Sequence[int], log) -> float:
    """(b): copied from chip_smoke.py's single-device branch."""
    import jax
    import jax.numpy as jnp

    from llm_d_tpu.ops import moe as moe_ops
    c = engine.model_config
    ml = engine.params["moe_layers"]
    quant = {k: ml[k] for k in ("w_gate_q", "w_gate_s", "w_up_q", "w_up_s",
                                "w_down_q", "w_down_s")}
    E, k = c.num_experts, c.num_experts_per_tok

    def run(dispatch):
        return jax.jit(lambda x, w, idx, q: moe_ops.expert_ffn(
            x, w, idx, None, None, None, mesh=None,
            quant=dict(q, layer=jnp.int32(1)), dispatch=dispatch))

    worst = 0.0
    for T in sizes:
        kx, ki, kw = jax.random.split(
            jax.random.PRNGKey((seed + T) % (2**31 - 1)), 3)
        x = jax.random.normal(kx, (T, c.hidden_size), jnp.bfloat16)
        idx = jnp.argsort(jax.random.uniform(ki, (T, E)))[:, :k].astype(
            jnp.int32)                            # k distinct experts each
        w = jax.nn.softmax(jax.random.normal(kw, (T, k), jnp.float32))
        got = run("auto")(x, w, idx, quant).astype(jnp.float32)
        want = run("ragged")(x, w, idx, quant).astype(jnp.float32)
        rel = float(jnp.sqrt(jnp.mean((got - want) ** 2)
                             / jnp.mean(want ** 2)))
        check(math.isfinite(rel), f"MoE op parity T={T}: non-finite")
        log(f"   expert_ffn T={T}: served path vs dequantise-then-"
            f"ragged_dot rel RMS {rel:.5f} (tolerance "
            f"{MOE_OP_REL_RMS_TOL})")
        worst = max(worst, rel)
    check(worst <= MOE_OP_REL_RMS_TOL,
          f"expert_ffn differs from its reference by rel RMS {worst:.5f}")
    return worst
