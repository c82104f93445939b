"""Phase clock of the engine loop: where one iteration's host time goes.

``EngineCore.step`` reads the clock at its phase boundaries (plain
``time.monotonic()`` reads, the tracing module's hot-path idiom) and the
times ride the ``engine.step`` span as attributes, in ms:

  between_ms   return of the previous ``step()`` -> entry of this one, when
               the previous step left work for the scheduler (hand-over of
               the outputs, inbox drain, ``has_work``, GIL waits)
  starved_ms   the same gap when it left none: the loop slept for lack of
               work
  schedule_ms  entry -> ``scheduler.schedule()`` and the queue-wait
               bookkeeping done
  build_ms     -> the step's batch assembled and copied to the device
  dispatch_ms  -> the step program enqueued (RNG split, key unpack, call)
  fetch_ms     the one blocking ``jax.device_get``: waiting for the device
  post_ms      fetch done -> ``step()`` returns
  host_cpu_ms  ``time.thread_time()`` of the stepping thread over all of
               the above; wall less this less ``fetch_ms`` is time the
               thread held no CPU (GIL, preemption)

and, counted by the classic step path where it does them (``count``):

  h2d_copies   arrays handed to ``jax.device_put`` during ``build``
  launches     programs enqueued from the span's start to the fetch

and, described by ``EngineCore._kv_counts`` (all four step paths; the
classic path also hands them to its ``llmd.dispatch`` annotation):

  kv_ctx_tokens   sum over the step's rows and attention layers of the keys
                  a full-attention layer reads (a chunk of n queries ending
                  at context L reads n L - n (n - 1) / 2)
  kv_read_tokens  the same with each layer's window applied (equal to
                  ``kv_ctx_tokens`` for a model without a window)
  kv_held_tokens  sum over rows and layers of the tokens the cache holds
                  NOW: every token in every layer of a one-group cache; of
                  a cache in groups by layer kind (kv_cache.py) the window
                  layers count from the first page the row still holds
  kv_dead_tokens  sum over rows and window layers of those no later query
                  of the row can see: what a pool per layer kind frees (in
                  a grouped cache what is left of it: the chunk being
                  computed and the page the window begins in)

and, for a cache in groups by layer kind (the classic path; the page counts
also on its ``llmd.dispatch`` annotation, as the step is composed):

  kv_pages_full, kv_pages_window    pages of the group that a running
                  sequence references or that are kept for a later hit
  kv_pages_full_total, kv_pages_window_total    the group's pages (its
                  trash page apart)
  kv_window_pages_released    window pages the step's rows gave back once
                  it was launched (the span only)

and, for a stack whose full layers select their keys
(``ModelConfig.index_topk``; ``kv_read_tokens`` then counts what is
attended to, not what is visible):

  index_pairs         sum over rows and full layers of the visible keys a
                      query: every (query, key) pair the indexer scores
  kv_selected_tokens  of those, the pairs the full layers attend to:
                      min(visible, index_topk) a query

and, for the classic path's steps with prefill tokens
(``EngineCore._attn_q_counts``):

  attn_q_real     query tokens the step computes
  attn_q_slots    query slots the prefill attention grid holds for them: the
                  Pallas kernels' query tiles (tiles x slots a tile), the
                  padded [S, Q] rectangle where another path serves

and, where the Pallas prefill kernels serve them
(``EngineCore._attn_k_counts``), summed over the step's query tiles and
attention layers:

  attn_k_real     keys from the first one the tile's first query sees (under
                  the layer's window) to its last query's own
  attn_k_slots    keys the kernel's inner loop covers for the tile: the key
                  blocks it walks x the keys a block (several pages,
                  ``ops.attention.prefill_key_block``); the rest lie before
                  the window, past the causal diagonal or past the context

and, for the classic path's pure-decode steps of a stack the MLA decode
kernel serves (``EngineCore._attn_dk_counts``), summed over the step's rows
and attention layers:

  attn_dk_real    keys the rows can see: their contexts, the new token's
                  own key included
  attn_dk_slots   keys the kernel's inner loop covers for them: the key
                  blocks a grid program walks (to its longest row's last;
                  rows in the order of their lengths, pad rows of the
                  sequence bucket first) x its rows x the keys a block
                  (``ops.attention.mla_decode_walk``)

and, for a stack whose MLA layers with a window the masked flash kernel
serves (``EngineCore._attn_wk_counts``; every step, pure decode too), summed
over those layers:

  attn_wk_real    keys the window shows the step's queries: min(window,
                  position + 1) a query
  attn_wk_slots   keys the kernel's inner loop covers for them: a query
                  tile's key blocks, from the one that holds the first key
                  its first query sees to its last query's own, x the keys
                  a block x the tile's slots (``ops.sparse_mla.
                  window_q_tile``, ``ops.pallas.mla_masked.KEY_BLOCK``)

and, for a stack whose full layers select their keys through the indexer's
kernel (``EngineCore._idx_k_counts``; every step, pure decode too), summed
over those layers:

  idx_k_real      (query, visible key) pairs the indexer must score: the
                  step's ``index_pairs``
  idx_k_slots     pairs the kernel's walk covers for them: a query tile's
                  index blocks, from key 0 to its last query's own, x the
                  keys a block (``ops.pallas.dsa_index.index_block``) x the
                  eight slots the kernel's tile holds (a decode row fills
                  one)

and, for a stack with recurrent state beside its pages
(``EngineCore._state_counts``; the classic path, also on its
``llmd.dispatch`` annotation):

  ssm_decode_rows     rows of one token: the one-token update of the state
                      pool, in place
  ssm_prefill_rows    rows of more, and
  ssm_prefill_tokens  their tokens: the chunked scan
  ssm_resets          rows whose chunk starts at position 0: the program
                      zeroes their state (new, or preempted and recomputed)

and, for a decoder-hybrid-decoder stack (``ModelConfig.mixer_by_layer``;
``EngineCore._cross_decoder_counts``; the ``kv_*`` counts above then go by
the layers that attend and the planes that are held: the self-decoder's
attention layers read, ``cross_kv_layer``'s plane is held beside theirs):

  xdec_rows          rows the step samples from: the only ones that go
                     through ``cross_kv_layer``'s attention and the layers
                     after it (beside ``prefill_tokens + decode_tokens``)
  xattn_read_tokens  sum over those rows of their context x the layers that
                     read the shared plane (``cross_kv_layer`` itself and
                     the CROSS layers), a query a row; part of
                     ``kv_ctx_tokens`` and ``kv_read_tokens`` too

and, counted by the step program itself and fetched with the step's ids
(the classic path of an MoE stack on one device; ``EngineCore._moe_counts``;
also on the ``llmd.post`` annotation of the iteration that retired the step,
so the count lies on the trace's clock beside the kernels):

  moe_experts_touched  sum over the MoE layers of the DISTINCT routed
                       experts the step's real token rows select (padded
                       rows of the token bucket masked out; logical ids)
  moe_experts_held     MoE layers x routed experts: what a stream of every
                       expert reads
  moe_pairs            real token rows x experts a token x MoE layers
  moe_one_pass_pairs   ``moe_pairs`` where the step's program has more than
                       ``ops.moe.ROUTED_INT8_MAX_T`` token rows and the int8
                       kernels serve it (the one-pass kernel: every expert's
                       matrices read once a layer), else 0; known on the
                       host from the step's token bucket, nothing fetched

and, on the classic path's ``llmd.dispatch`` annotation only, beside
``prefill_tokens``:

  sample_rows     rows x slots the output head and the sampler run on (the
                  sequence bucket, padded rows included)

The DEVICE's side of a step is named by part, not by phase: every operation
of a step program lies in one ``llmd.<part>`` scope (ops/parts.py has the
vocabulary), which the profiler's trace carries beside these annotations.

Phases are contiguous, so they add up to the iteration.  An iteration that
fetched nothing (an empty schedule, the first dispatch of a pipelined
block) writes no span; its times stay in the accumulator and ride the next
span, so consecutive spans still account for all of the loop's time.

Each phase is also a ``jax.profiler.TraceAnnotation("llmd.<phase>")``: an
atomic load when no profiler session is on, an event on the ``/host:CPU``
plane beside the device plane when one is, which puts the loop's phases on
the device trace's clock.  Host-side only: no device value is touched here.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Tuple

from jax.profiler import TraceAnnotation


class StepClock:
    def __init__(self) -> None:
        self._acc: Dict[str, float] = {}     # phase -> s since last flush
        self._counts: Dict[str, int] = {}    # event -> n since last flush
        self._phase: Optional[str] = None
        self._since = 0.0
        self._note: Optional[TraceAnnotation] = None
        self._left: Optional[Tuple[float, bool]] = None
        self._cpu: Optional[Tuple[int, float]] = None   # (thread, CPU s)

    def _switch(self, phase: Optional[str], now: float, **args) -> None:
        if self._phase is not None:
            self._acc[self._phase] = (
                self._acc.get(self._phase, 0.0) + now - self._since)
            self._note.__exit__(None, None, None)
        self._phase, self._since, self._note = phase, now, None
        if phase is not None:
            self._note = TraceAnnotation("llmd." + phase, **args)
            self._note.__enter__()

    def enter(self) -> None:
        """``step()`` entered: the gap since the last return is booked."""
        now = time.monotonic()
        if self._left is not None:
            left_at, left_work = self._left
            gap = "between" if left_work else "starved"
            self._acc[gap] = self._acc.get(gap, 0.0) + now - left_at
        self._switch("schedule", now)

    def mark(self, phase: str, **args) -> float:
        """The running phase ends and ``phase`` begins; returns the read.
        ``args`` ride the phase's annotation (read only while a profiler
        session is on): what the step under way asks of the device, on
        the clock of the kernels that do it."""
        now = time.monotonic()
        self._switch(phase, now, **args)
        return now

    def count(self, what: str, n: int = 1) -> None:
        """``n`` more of ``what`` happened in this iteration."""
        self._counts[what] = self._counts.get(what, 0) + n

    def leave(self, left_work: bool) -> None:
        """``step()`` returns; ``left_work``: the scheduler still has some."""
        now = time.monotonic()
        self._switch(None, now)
        self._left = (now, left_work)

    def flush(self) -> Dict[str, float]:
        """``<phase>_ms`` and the counts accumulated since the last flush,
        and ``host_cpu_ms`` where the same thread flushed last time."""
        out: Dict[str, float] = {
            f"{k}_ms": round(v * 1e3, 4) for k, v in self._acc.items()}
        out.update(self._counts)
        self._acc.clear()
        self._counts.clear()
        thread, cpu = threading.get_ident(), time.thread_time()
        if self._cpu is not None and self._cpu[0] == thread:
            out["host_cpu_ms"] = round((cpu - self._cpu[1]) * 1e3, 4)
        self._cpu = (thread, cpu)
        return out
