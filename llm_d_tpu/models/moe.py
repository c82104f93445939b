"""MoE decoder-only transformer (Mixtral / DeepSeek-V3 families).

Same functional design as ``models.llama`` (stacked layers + ``lax.scan``)
with the MLP replaced by shared + routed experts.  DeepSeek-style models run
their first ``first_dense_layers`` layers dense, so the stack scans two
parameter groups: ``dense_layers`` then ``moe_layers`` (the KV cache is one
[L, slots, KVH*D] buffer split at the boundary).

An MLA stack whose layers are of two kinds (``ModelConfig.mla_layer_kinds``)
has parameter SHAPES that differ by kind, so each kind has its own pair of
groups (the SLIDING layers' are ``swa_dense_layers`` / ``swa_moe_layers``)
and its own cache buffers, stacked over the layers of the kind.  ``forward``
walks the stack as RUNS of consecutive layers of one group (``layer_runs``),
one ``lax.scan`` a run; a stack of one kind is the two runs it always was.

A stack may also name LINEAR layers (``ModelConfig.linear_by_layer``): a
linear-attention mixer in place of attention (models/linear_attention.py),
one more layer kind with its own groups (``lin_dense_layers`` /
``lin_moe_layers``) whose "cache buffers" are the engine's state pool
(``ssm``, ``conv``), two more entries of the ``kv_cache`` dict carried
through the same scans.

This is the model half of the wide-EP path (reference:
guides/wide-ep-lws/manifests/modelserver/base/decode.yaml:76-132 — EP flags,
EPLB, DeepEP backends; the engine equivalents live in ``ops.moe``).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from llm_d_tpu.models import linear_attention
from llm_d_tpu.models.config import FULL, LINEAR, SLIDING, ModelConfig
from llm_d_tpu.models.linear_attention import (  # noqa: F401  (the engine
    # builds the state pool of a stack with LINEAR layers from the model)
    STATE_KEYS, state_pool_shapes)
from llm_d_tpu.models.llama import (  # noqa: F401  (re-exports: the MoE
    # model shares the dense family's logits head and MTP drafter — the
    # drafter reads only embed/lm_head from the target params, which both
    # families carry identically)
    attention_block, compute_logits, dense_layer, draft_propose,
    embed_tokens, init_draft_params, mlp_out, sampled_hidden,
    with_layer_tables)
from llm_d_tpu.ops import layers as L
from llm_d_tpu.ops import moe as moe_ops
from llm_d_tpu.ops.attention import (
    with_block_visibility, with_query_tiles)
from llm_d_tpu.ops.parts import part
from llm_d_tpu.parallel.mesh import AXIS_EP

Params = Dict[str, Any]


def _attn_params(c: ModelConfig, n: int, key, dt) -> Params:
    dh = c.head_dim_
    k = iter(jax.random.split(key, 8))

    def stacked(shape, kk):
        return (jax.random.normal(kk, (n, *shape), jnp.float32)
                * (shape[0] ** -0.5)).astype(dt)

    p = {
        "input_norm": jnp.ones((n, c.hidden_size), dt),
        "q_proj": stacked((c.hidden_size, c.num_heads * dh), next(k)),
        "k_proj": stacked((c.hidden_size, c.num_kv_heads * dh), next(k)),
        "v_proj": stacked((c.hidden_size, c.num_kv_heads * dh), next(k)),
        "o_proj": stacked((c.num_heads * dh, c.hidden_size), next(k)),
        "post_attn_norm": jnp.ones((n, c.hidden_size), dt),
    }
    if c.attention_bias:
        p["q_bias"] = jnp.zeros((n, c.num_heads * dh), dt)
        p["k_bias"] = jnp.zeros((n, c.num_kv_heads * dh), dt)
        p["v_bias"] = jnp.zeros((n, c.num_kv_heads * dh), dt)
    if c.qk_norm:
        p["q_norm"] = jnp.ones((n, dh), dt)
        p["k_norm"] = jnp.ones((n, dh), dt)
    if c.attn_output_gate:
        p["attn_gate"] = stacked((c.hidden_size, c.num_heads * dh), next(k))
    if c.sandwich_norm:
        p["attn_out_norm"] = jnp.ones((n, c.hidden_size), dt)
        p["mlp_out_norm"] = jnp.ones((n, c.hidden_size), dt)
    return p


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    c = config
    dt = c.jax_dtype
    Ld = c.first_dense_layers
    Lm = c.num_layers - Ld
    E, Im = c.num_experts, c.moe_intermediate_size
    Ish = Im * c.num_shared_experts
    if c.mla_layer_kinds:
        return _init_params_by_kind(c, key)
    k = iter(jax.random.split(key, 16))

    def w(shape, kk):
        return (jax.random.normal(kk, shape, jnp.float32)
                * (shape[-2] ** -0.5)).astype(dt)

    def attn_params(n, kk):
        if c.use_mla:
            from llm_d_tpu.models.mla import init_mla_params
            p = init_mla_params(c, n, kk, dt)
            p["input_norm"] = jnp.ones((n, c.hidden_size), dt)
            p["post_attn_norm"] = jnp.ones((n, c.hidden_size), dt)
            return p
        return _attn_params(c, n, kk, dt)

    dense = attn_params(Ld, next(k))
    dense.update({
        "gate_proj": w((Ld, c.hidden_size, c.intermediate_size), next(k)),
        "up_proj": w((Ld, c.hidden_size, c.intermediate_size), next(k)),
        "down_proj": w((Ld, c.intermediate_size, c.hidden_size), next(k)),
    })
    moe = attn_params(Lm, next(k))
    moe.update({
        "router": w((Lm, c.hidden_size, E), next(k)).astype(jnp.float32),
        "w_gate": w((Lm, E, c.hidden_size, Im), next(k)),
        "w_up": w((Lm, E, c.hidden_size, Im), next(k)),
        "w_down": w((Lm, E, Im, c.hidden_size), next(k)),
    })
    if c.scoring_func == "sigmoid":
        moe["e_bias"] = jnp.zeros((Lm, E), jnp.float32)
    if c.num_shared_experts > 0:
        moe.update({
            "shared_gate": w((Lm, c.hidden_size, Ish), next(k)),
            "shared_up": w((Lm, c.hidden_size, Ish), next(k)),
            "shared_down": w((Lm, Ish, c.hidden_size), next(k)),
        })
    params: Params = {
        "embed": w((c.vocab_size, c.hidden_size), next(k)),
        "dense_layers": dense,
        "moe_layers": moe,
        "final_norm": jnp.ones((c.hidden_size,), dt),
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = w((c.hidden_size, c.vocab_size), next(k))
    return params


def _init_params_by_kind(c: ModelConfig, key: jax.Array) -> Params:
    """An MLA stack of two layer kinds: a dense and a MoE group a kind
    (``group_name``), each stacked over its own layers, the routed experts
    held here only (``ModelConfig.num_held_experts``) under a router of
    full width."""
    from llm_d_tpu.models.mla import init_mla_params
    dt = c.jax_dtype
    E, E_held, Im = c.num_experts, c.num_held_experts, c.moe_intermediate_size
    Ish = Im * c.num_shared_experts
    k = iter(jax.random.split(key, 40))

    def w(shape):
        return (jax.random.normal(next(k), shape, jnp.float32)
                * (shape[-2] ** -0.5)).astype(dt)

    params: Params = {"embed": w((c.vocab_size, c.hidden_size)),
                      "final_norm": jnp.ones((c.hidden_size,), dt)}
    if not c.tie_word_embeddings:
        params["lm_head"] = w((c.hidden_size, c.vocab_size))
    for kind in c.mla_layer_kinds + ((LINEAR,) if c.linear_by_layer else ()):
        for moe in (False, True):
            n = sum(run.stop - run.start for run in layer_runs(c)
                    if (run.kind, run.moe) == (kind, moe))
            if not n:
                continue
            if kind == LINEAR:
                p = linear_attention.init_params(c, n, next(k), dt)
            else:
                p = init_mla_params(c, n, next(k), dt, kind)
            p["input_norm"] = jnp.ones((n, c.hidden_size), dt)
            p["post_attn_norm"] = jnp.ones((n, c.hidden_size), dt)
            if moe:
                p.update({
                    "router": w((n, c.hidden_size, E)).astype(jnp.float32),
                    "w_gate": w((n, E_held, c.hidden_size, Im)),
                    "w_up": w((n, E_held, c.hidden_size, Im)),
                    "w_down": w((n, E_held, Im, c.hidden_size)),
                })
                if c.scoring_func == "sigmoid":
                    p["e_bias"] = jnp.zeros((n, E), jnp.float32)
                if c.num_shared_experts > 0:
                    p.update({
                        "shared_gate": w((n, c.hidden_size, Ish)),
                        "shared_up": w((n, c.hidden_size, Ish)),
                        "shared_down": w((n, Ish, c.hidden_size)),
                    })
            else:
                p.update({
                    "gate_proj": w((n, c.hidden_size, c.intermediate_size)),
                    "up_proj": w((n, c.hidden_size, c.intermediate_size)),
                    "down_proj": w((n, c.intermediate_size, c.hidden_size)),
                })
            params[group_name(kind, moe)] = p
    return params


def group_name(kind: str, moe: bool) -> str:
    """The parameter group of the layers of ``kind`` with (``moe``) or
    without routed experts."""
    return ({SLIDING: "swa_", LINEAR: "lin_"}.get(kind, "")
            + ("moe_layers" if moe else "dense_layers"))


class LayerRun(NamedTuple):
    """Consecutive layers of one parameter group."""
    kind: str        # FULL | SLIDING | LINEAR (FULL where the stack has one
                     # geometry)
    moe: bool
    start: int       # [start, stop) within the group's stack
    stop: int
    layer0: int      # the run's first layer, in the stack
    plane0: int      # and in the cache buffers of its kind


def layer_runs(c: ModelConfig) -> Tuple[LayerRun, ...]:
    """The stack as runs of consecutive layers of one group.  A stack of
    one geometry (``mla_layer_kinds`` empty: GQA with or without
    ``layer_types``, plain MLA) is its dense layers, then its MoE layers."""
    Ld = c.first_dense_layers
    if not c.mla_layer_kinds:
        return (LayerRun(FULL, False, 0, Ld, 0, 0),
                LayerRun(FULL, True, 0, c.num_layers - Ld, Ld, Ld))
    runs, seen, planes = [], {}, {}
    for li, kind in enumerate(c.layer_types):
        group = (kind, li >= Ld)
        at = seen.get(group, 0)
        if runs and (runs[-1].kind, runs[-1].moe) == group:
            runs[-1] = runs[-1]._replace(stop=at + 1)
        else:
            runs.append(LayerRun(*group, at, at + 1, li, planes.get(kind, 0)))
        seen[group] = at + 1
        planes[kind] = planes.get(kind, 0) + 1
    return tuple(runs)


def forward(
    params: Params,
    kv_cache: Dict[str, jax.Array],   # {"k","v": [L, slots, KVH*dh]}
    batch: Dict[str, jax.Array],
    config: ModelConfig,
    block_size: int,
    attn_backend: str = "auto",
    mesh: Optional[Mesh] = None,
    collect_routed: bool = False,   # also return [Lm, T, k] routed ids (EPLB)
    moe_opts: Optional[Dict] = None,   # {"dbo_{decode,prefill}_min_tokens"}
    collect_moe_trace: bool = False,   # also return per-MoE-layer dispatch
                                       # inputs (the collective accuracy
                                       # harness's real-trace capture)
    count_touched: bool = False,    # also return, last, the distinct routed
                                    # experts the step's real token rows
                                    # select, summed over the MoE layers
):
    c = config
    Ld = c.first_dense_layers
    stacked = batch["token_ids"].ndim == 2
    x = embed_tokens(params, batch["token_ids"], c)   # [T, D] / [dp, T_l, D]
    # (a stack with LINEAR layers carries the state pool beside its pages)
    cache_keys = tuple(kv_cache_layout(c)) + (
        STATE_KEYS if c.linear_by_layer else ())
    kinds = c.mla_layer_kinds or (FULL,)
    # Once a step program, outside the layer scans: a block-diffusion
    # model's visibility limits, and the query tile list the Pallas prefill
    # kernels walk in every layer (one a kind that they serve: its heads
    # and row width set the tile).
    with part("tiles"):
        batch = with_block_visibility(batch, c.diffusion_block_length)
        if c.use_mla:
            from llm_d_tpu.ops import sparse_mla
            batches = {}
            for kind in kinds:
                g = c.mla_geometry(kind)
                if g.index_topk or g.window:
                    # Served over tiles of its own (ops/sparse_mla.py).
                    batches[kind] = sparse_mla.with_tiles(
                        batch, sparse_mla.SELECT_Q_TILE if g.index_topk
                        else sparse_mla.window_q_tile(
                            g, sparse_mla.kernel_serves(
                                g, attn_backend, block_size,
                                batch["block_tables"].shape[-1] * block_size)))
                else:
                    batches[kind] = with_query_tiles(
                        batch, g.num_heads, g.row_width, attn_backend, mesh,
                        mla=True)
            if c.linear_by_layer:
                batches[LINEAR] = batch
        else:
            batches = {FULL: with_query_tiles(
                batch, c.num_heads, kv_cache[cache_keys[0]].shape[-1],
                attn_backend, mesh)}
        real = None
        if count_touched:
            # The token bucket's real rows lie first; a padded query slot
            # names row T.
            T = batch["token_ids"].shape[-1]
            real = jnp.arange(T) < jnp.sum(batch["qtok_idx"] < T)
    if not c.use_mla:
        batches[FULL] = with_layer_tables(batches[FULL], c)
    # DBO threshold by phase: the program's query width is static under jit,
    # and Q == 1 holds exactly for pure-decode programs (single-step or
    # fused).  None (no opts) lets the op consult its standalone env vars;
    # -1 disables DBO outright.
    is_decode = batch["qtok_idx"].shape[-1] == 1
    dbo_min_tokens = (moe_opts or {}).get(
        "dbo_decode_min_tokens" if is_decode else "dbo_prefill_min_tokens")
    # The share of the routed experts held here (None: all of them).
    held = ((c.first_local_expert, c.num_local_experts)
            if c.num_local_experts else None)

    def attend_local(lp, hn, caches, ab, li, kind=FULL):
        """Attention dispatch: MLA (the kind's latent buffers), a LINEAR
        layer's mixer (the state pool) or classic GQA."""
        if kind == LINEAR:
            return linear_attention.mixer_block(
                lp, c, hn, ab, caches, li, attn_backend)
        if c.use_mla:
            from llm_d_tpu.models.mla import mla_attention_block
            return mla_attention_block(
                lp, c, hn, ab, caches, block_size, attn_backend,
                layer=li, mesh=mesh, kind=kind)
        return attention_block(
            lp, c, hn, ab, caches, block_size, attn_backend, layer=li,
            mesh=mesh)

    def attend(lp, hn, caches, li, run):
        """The run's attention over the buffers of its kind, ``li`` the
        layer in the stack.  Stacked mode: per-dp-shard attention (manual
        dp, auto tp) — the dp half of the wide-EP regime; see
        parallel.dp_attention."""
        if stacked:
            from llm_d_tpu.parallel.dp_attention import dp_attend
            return dp_attend(attend_local, mesh, lp, hn, caches, batch, li)
        if not c.mla_layer_kinds:
            return attend_local(lp, hn, caches, batches[FULL], li)
        own = [cache_keys.index(name)
               for name in kind_buffers(c, run.kind)]
        off = run.layer0 - run.plane0
        a, new = attend_local(
            lp, hn, tuple(caches[i] for i in own), batches[run.kind],
            li - off if off else li, run.kind)
        caches = list(caches)
        for i, buf in zip(own, new):
            caches[i] = buf
        return a, tuple(caches)

    def moe_tokens(hn):
        """[dp, T_l, D] -> [dp*T_l, D] for EP dispatch: the merged token
        dim stays dp-sharded (row-major reshape is shard-local), so the
        a2a's in_specs re-slice only within each dp group."""
        return hn.reshape(-1, hn.shape[-1]) if stacked else hn

    # Full stacked KV cache rides both scans' carries; each layer updates its
    # plane in place (see models.llama.forward) — no split/concat copies.
    def dense_body(run, carry, lp):
        h, caches, li = carry
        h, caches = dense_layer(
            lp, c, h, lambda hn: attend(lp, hn, caches, li, run))
        return (h, caches, li + 1), None

    def moe_body(run, quant_stacked, held_stacked, carry, lp):
        h, caches, li = carry
        with part("attn.proj"):
            hn = L.rms_norm(h, lp["input_norm"], c.rms_norm_eps)
        a, caches = attend(lp, hn, caches, li, run)
        with part("router"):
            h = h + a
            hn = L.rms_norm(h, lp["post_attn_norm"], c.rms_norm_eps)
            ht = moe_tokens(hn)                   # [T, D] (dp-sharded rows)
            weights, idx = moe_ops.route(
                jnp.dot(ht.astype(jnp.float32), lp["router"]), c,
                e_bias=lp.get("e_bias"))
            # Of the experts HELD: ids elsewhere match none of them.
            touched = (moe_ops.experts_touched(
                idx - c.first_local_expert if c.first_local_expert else idx,
                real, c.num_held_experts)
                if real is not None else None)
            phys_idx = idx
            if "replica_table" in lp:
                # EPLB: route to a physical replica of the logical expert
                # (round-robin over its replicas; parallel.eplb plans the
                # table per layer — the layer index phases the walk so every
                # layer doesn't start on replica 0).
                phys_idx = moe_ops.to_physical_experts(
                    idx, lp["replica_table"], lp["num_replicas"],
                    phase=li - Ld)
        if quant_stacked is not None:
            # int8 payloads travel to the op STACKED (closure, not scan
            # xs — a scan slice feeding pallas_call would materialize a
            # per-layer copy) with the MoE-layer plane index; on TPU
            # they reach the Pallas int8 kernel family without a
            # materialized dequant — dense streaming / fused-routing
            # routed / one-pass by the step's token count on one device
            # (ops/pallas/moe_int8.py, moe_routed.py, moe_one_pass.py),
            # and the chunk-streamed kernel (moe_routed_stream.py) per
            # dispatch chunk on the a2a EP mesh path.
            quant = dict(quant_stacked, layer=li - Ld)
            w_gate = w_up = w_down = held_plane = None
        elif held_stacked is not None:
            # A share's bf16 experts travel STACKED too, with the layer's
            # plane in its group: the grouped product's operands are
            # buffers, and a scan slice handed to it is a copy of the
            # layer's experts (ops.moe._held_expert_ffn).
            quant = None
            w_gate, w_up, w_down = held_stacked
            held_plane = li - (run.layer0 - run.start)
        else:
            quant = held_plane = None
            w_gate, w_up, w_down = lp["w_gate"], lp["w_up"], lp["w_down"]
        with part("experts"):
            m = moe_ops.expert_ffn(
                ht, weights, phys_idx, w_gate, w_up, w_down, mesh=mesh,
                dbo_min_tokens=dbo_min_tokens, quant=quant, held=held,
                held_plane=held_plane)
            if stacked:
                m = m.reshape(hn.shape)
        if "shared_gate" in lp:
            with part("shared"):
                m = m + L.swiglu_mlp(hn, lp["shared_gate"], lp["shared_up"],
                                     lp["shared_down"])
        with part("mlp"):
            h = h + mlp_out(lp, c, m)
        if collect_moe_trace:
            # The EXACT operands the EP dispatch ships: the rms-normed
            # hidden rows plus the routing the combine applies — what the
            # collective accuracy harness measures quantization against
            # (ops/collective_accuracy.py).
            return (h, caches, li + 1), ({
                "x": ht, "weights": weights, "idx": phys_idx}, touched)
        return (h, caches, li + 1), (idx, touched)

    quant_keys = ("w_gate_q", "w_gate_s", "w_up_q", "w_up_s",
                  "w_down_q", "w_down_s")
    held_keys = ("w_gate", "w_up", "w_down")
    carry = (x, tuple(kv_cache[k] for k in cache_keys), jnp.int32(0))
    per_moe_layer = []
    with part("scan"):
        for run in layer_runs(c):
            gp = params[group_name(run.kind, run.moe)]
            if not run.moe:
                body = functools.partial(dense_body, run)
            else:
                quant_stacked = ({k: gp[k] for k in quant_keys}
                                 if "w_gate_q" in gp else None)
                if quant_stacked is not None:
                    gp = {k: v for k, v in gp.items() if k not in quant_keys}
                held_stacked = None
                if held is not None and quant_stacked is None:
                    held_stacked = tuple(gp[k] for k in held_keys)
                    gp = {k: v for k, v in gp.items() if k not in held_keys}
                body = functools.partial(moe_body, run, quant_stacked,
                                         held_stacked)
            n = jax.tree.leaves(gp)[0].shape[0]
            if (run.start, run.stop) == (0, n):
                carry, ys = jax.lax.scan(body, carry, gp)
            else:
                # Some layers of the group: the body takes its layer's
                # slices from the whole stacks, which stay where they are.
                carry, ys = jax.lax.scan(
                    lambda cr, i, body=body, gp=gp: body(
                        cr, jax.tree.map(lambda a: a[i], gp)),
                    carry, jnp.arange(run.start, run.stop))
            if run.moe:
                per_moe_layer.append(ys)
    x, caches, _ = carry
    routed, touched = (
        per_moe_layer[0] if len(per_moe_layer) == 1 else jax.tree.map(
            lambda *a: jnp.concatenate(a), *per_moe_layer))

    out = (sampled_hidden(params, x, batch, c, stacked),
           dict(zip(cache_keys, caches)))
    if collect_moe_trace or collect_routed:
        # The harness's real routed trace {"x": [Lm, T, H], "weights":
        # [Lm, T, k], "idx": [Lm, T, k]} (see moe_body), or the [Lm, T, k]
        # logical ids for the engine's EPLB LoadTracker.
        out += (routed,)
    if count_touched:
        with part("router"):
            out += (jnp.sum(touched),)
    return out


def sharding_rules(config: ModelConfig):
    """TP for attention/shared experts (Megatron layout), EP over the
    flattened (dp, sp, tp) axes for routed experts — the wide-EP regime
    ("TPxDP in attention, EP in MoE layers"; reference decode.yaml:76,87)."""
    rules = [
        (r"embed", P(None, "tp")),
        (r"layers/((q|k|v)_proj|attn_gate)", P(None, None, "tp")),
        (r"layers/(q|k|v)_bias", P(None, "tp")),
        (r"layers/o_proj", P(None, "tp", None)),
        (r"dense_layers/(gate|up)_proj", P(None, None, "tp")),
        (r"dense_layers/down_proj", P(None, "tp", None)),
        (r"moe_layers/router", P()),
        (r"moe_layers/w_(gate|up|down)", P(None, AXIS_EP)),
        (r"moe_layers/shared_(gate|up)", P(None, None, "tp")),
        (r"moe_layers/shared_down", P(None, "tp", None)),
        (r"lm_head", P(None, "tp")),
    ]
    if config.use_mla:
        from llm_d_tpu.models.mla import mla_sharding_rules
        rules = mla_sharding_rules() + rules
    return rules


def kind_buffers(config: ModelConfig, kind: str = FULL) -> Tuple[str, ...]:
    """The cache buffers the layers of ``kind`` read and write, the latent
    rows first."""
    if not config.use_mla:
        return ("k", "v")
    if kind == LINEAR:
        return STATE_KEYS
    if kind == SLIDING and config.mla_layer_kinds:
        return ("kv_swa",)
    return ("kv", "idx") if config.index_topk else ("kv",)


def kv_cache_layout(config: ModelConfig) -> Dict[str, int]:
    """Per-buffer cache row widths.  MLA caches ONE latent row per token
    (kv_lora_rank + rope, lane-padded) — for V3 that is 640 values vs
    32768 for materialized heads, the memory profile wide-EP decode
    relies on.

    The MLA row ALWAYS lane-pads to a multiple of 128 (V3: 576 -> 640,
    +11%): the Pallas decode kernel's page DMAs need the alignment, zero
    columns are score-neutral (models/mla.py), and deriving the width
    from config alone keeps the PD KV-transfer wire format identical
    across backends (a CPU prefiller can feed a TPU decoder).

    An MLA stack of two kinds has a latent buffer a kind, each as wide as
    the kind's own row, and layers that select keys an index key buffer
    beside theirs; ``kv_cache_layers`` says how many layers each holds."""
    if config.use_mla:
        layout = {}
        for kind in config.mla_layer_kinds or (FULL,):
            g = config.mla_geometry(kind)
            names = kind_buffers(config, kind)
            layout[names[0]] = g.row_width
            if len(names) > 1:
                layout[names[1]] = config.index_head_dim
        return layout
    return {"k": config.num_kv_heads * config.head_dim_,
            "v": config.num_kv_heads * config.head_dim_}


def kv_cache_layers(config: ModelConfig) -> Dict[str, int]:
    """Layers each cache buffer holds rows of: all of them, or where the
    buffers go by layer kind the layers of the buffer's kind."""
    kinds = config.mla_layer_kinds
    if not kinds:
        return dict.fromkeys(kv_cache_layout(config), config.num_layers)
    return {name: config.layer_types.count(kind) for kind in kinds
            for name in kind_buffers(config, kind)}


def kv_cache_spec(config: Optional[ModelConfig] = None) -> Dict[str, P]:
    if config is not None and config.use_mla:
        # The latent row is shared by all (tp-sharded) heads: replicate.
        return dict.fromkeys(kv_cache_layout(config), P())
    return {"k": P(None, None, "tp"), "v": P(None, None, "tp")}
