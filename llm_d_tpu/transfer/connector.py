"""TPU KV connector: P->D disaggregation's engine-side halves.

Mirrors the reference's vLLM KV-connector contract
(``--kv-transfer-config '{"kv_connector":"TPUConnector","kv_role":...}'``,
ms-pd/values_tpu.yaml:44,131; response params README.tpu.md:182-189):

  producer ("kv_producer"/"kv_both"): after a ``do_remote_decode`` prefill
    the engine pins the request's blocks; the connector gathers their KV
    (one jitted device gather + a single device_get) and registers the host
    slab with the native transfer server under the request uuid.  The
    response's ``kv_transfer_params`` advertises {remote_block_ids,
    remote_host, remote_port, uuid}.

  consumer ("kv_consumer"/"kv_both"): a request arriving with
    ``kv_transfer_params`` is diverted before scheduling; a worker thread
    fetches the slab, then the engine thread allocates local blocks,
    scatters the KV in (one jitted update), marks all but the last prompt
    token computed, and enqueues the request — only the final prompt token
    is recomputed locally to produce sampling logits.

``kv_load_failure_policy`` follows decode.yaml:96: "fail" aborts the request
loudly; "recompute" falls back to a full local prefill.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import queue
import struct
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from llm_d_tpu.engine.request import Request, RequestOutput, RequestState
from llm_d_tpu.transfer import transport
from llm_d_tpu.utils import tracing
from llm_d_tpu.utils.config import env_float, env_int
from llm_d_tpu.utils.faultinject import FaultInjected, get_injector

logger = logging.getLogger(__name__)

_MAGIC = 0x4B565442  # "KVTB"
# Wire version 2: every buffer segment carries a dtype code so a consumer
# REJECTS a producer whose cache dtype differs (a bf16 decoder must never
# silently reinterpret the int8+scales slab of a peer from an older build
# — wrong page bytes would decode as garbage attention, not an error).
_WIRE_VERSION = 2
# magic, version, num_layers, block_size, num_buffers, nb
_HEADER = struct.Struct("<IIIIII")
_BUF_HEADER = struct.Struct("<IB")   # (row width, dtype code) per segment


def _next_pow2(n: int, lo: int = 1) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass
class KVConnectorConfig:
    kv_role: str = "kv_both"            # kv_producer | kv_consumer | kv_both
    host: str = "127.0.0.1"             # address advertised to consumers
    port: int = 0                        # 0 = ephemeral
    kv_load_failure_policy: str = "fail"  # fail | recompute
    timeout_ms: int = 30000
    # Producer-side safety valve: pinned blocks whose consumer never pulled
    # are released after this long (the reference leans on request timeouts;
    # an engine must not leak cache to a dead peer).
    pin_timeout_s: float = 120.0
    # Consumer-side retry budget BEFORE kv_load_failure_policy applies: a
    # transient drop (P/D-Serve reports failed P->D transfers dominate
    # per-request failures at scale) costs one short backoff instead of an
    # abort or a full local recompute.
    pull_retries: int = dataclasses.field(
        default_factory=lambda: env_int("LLMD_KV_PULL_RETRIES", 2))
    pull_backoff_s: float = dataclasses.field(
        default_factory=lambda: env_float("LLMD_KV_PULL_BACKOFF_S", 0.05))


class TpuConnector:
    """Both halves of the P->D transfer, bound to one EngineCore."""

    def __init__(self, config: KVConnectorConfig) -> None:
        self.config = config
        self.host = config.host
        self.server = None
        self.port = 0
        if config.kv_role in ("kv_producer", "kv_both"):
            self.server = transport.make_server("0.0.0.0", config.port)
            self.port = self.server.port
        # consumer side: fetches finished by worker threads, drained by the
        # engine thread in poll().
        self._loaded: "queue.Queue[Tuple[Request, Optional[bytes], Optional[str], float]]" = (
            queue.Queue())
        self._inflight = 0
        self._inflight_mu = threading.Lock()
        self._retry: List[Tuple[Request, bytes]] = []
        self._pin_times: Dict[str, float] = {}
        # Requests aborted while their KV pull was in flight: dropped at
        # poll() instead of being admitted for a disconnected client.
        # Only ids with a live pull are tracked (bounded by _pending_ids;
        # most aborts target already-admitted requests and must not leak
        # a set entry forever).
        self._aborted: set = set()
        self._pending_ids: set = set()
        # request_id -> (host, port, uuid) for pulls that may still hold a
        # PRODUCER pin: cancellation (abort / deadline expiry) sends the
        # release so the producer's blocks free immediately instead of
        # waiting out its pin timeout.
        self._pending_params: Dict[str, Tuple[str, int, str]] = {}

    # ------------------------------------------------------------------
    # producer side
    # ------------------------------------------------------------------

    def register_transfer(self, engine, req: Request) -> None:
        """Gather the pinned blocks' KV to host and serve them under the uuid."""
        assert self.server is not None, \
            "register_transfer on a consumer-only connector"
        blob = _pack_blocks(engine, req.block_ids)
        self.server.register(req.request_id, blob)
        self._pin_times[req.request_id] = time.monotonic()
        # Producer-side stage mark: how many bytes this prefill pinned
        # for the consumer's pull (the other end of kv.transfer).
        tracing.trace_event("engine", "kv.stage", parent=req.trace_ctx,
                            request_id=req.request_id, bytes=len(blob),
                            blocks=len(req.block_ids))

    def _poll_producer(self, engine) -> None:
        if self.server is None:
            return
        for uuid in self.server.drain_released():
            self._pin_times.pop(uuid, None)
            engine.release_pinned(uuid)
        if self._pin_times:
            now = time.monotonic()
            expired = [u for u, t in self._pin_times.items()
                       if now - t > self.config.pin_timeout_s]
            for uuid in expired:
                logger.warning("pinned transfer %s expired; releasing", uuid)
                self._pin_times.pop(uuid, None)
                self.server.unregister(uuid)
                engine.release_pinned(uuid)

    # ------------------------------------------------------------------
    # consumer side
    # ------------------------------------------------------------------

    def start_load_kv(self, engine, req: Request) -> None:
        """Begin the remote pull; the request joins the scheduler via poll()."""
        params = req.kv_transfer_params or {}
        with self._inflight_mu:
            self._inflight += 1
            self._pending_ids.add(req.request_id)
            try:
                self._pending_params[req.request_id] = (
                    str(params["remote_host"]), int(params["remote_port"]),
                    str(params.get("uuid", req.request_id)))
            except (KeyError, TypeError, ValueError):
                pass    # malformed params fail in the fetch worker anyway
        threading.Thread(
            target=self._fetch_worker, args=(req, params),
            name=f"kv-pull-{req.request_id[:8]}", daemon=True).start()

    def _fetch_worker(self, req: Request, params: Dict[str, Any]) -> None:
        t0 = time.perf_counter()
        wall0 = time.time()
        blob: Optional[bytes] = None
        error: Optional[str] = None
        retries = max(0, self.config.pull_retries)
        try:
            # Malformed params are PERMANENT: fail straight to policy, no
            # retry/backoff (only transport-level failures are transient).
            host = params["remote_host"]
            port = int(params["remote_port"])
            uuid = params.get("uuid", req.request_id)
        except (KeyError, TypeError, ValueError) as e:
            self._loaded.put((req, None, f"{type(e).__name__}: {e}",
                              time.perf_counter() - t0))
            return
        for attempt in range(retries + 1):
            error = None
            try:
                get_injector().check("kv.pull", key=f"{host}:{port}")
                blob = transport.fetch(host, port, uuid,
                                       timeout_ms=self.config.timeout_ms)
            except (transport.TransferNotFound, KeyError) as e:
                # Slab absent on a REACHABLE producer: the pin expired or
                # the uuid is stale — permanent, retrying can only burn
                # backoff before the policy decision (producers register
                # the slab BEFORE answering kv_transfer_params).
                error = f"{type(e).__name__}: {e}"
                break
            except (transport.TransferError, OSError, ValueError,
                    FaultInjected) as e:
                error = f"{type(e).__name__}: {e}"
                if attempt < retries:
                    logger.warning(
                        "kv pull for %s failed (%s); retry %d/%d",
                        req.request_id, error, attempt + 1, retries)
                    tracing.trace_event(
                        "engine", "kv.pull_retry", parent=req.trace_ctx,
                        request_id=req.request_id, attempt=attempt + 1,
                        error=error)
                    time.sleep(self.config.pull_backoff_s * (2 ** attempt))
                continue
            try:
                # The slab is on this host now; free the producer
                # immediately (its pinned prefill blocks return to the
                # pool).  A failed release must NOT fail the load — the
                # producer's pin timeout reclaims the blocks.
                transport.release(host, port, uuid,
                                  timeout_ms=self.config.timeout_ms)
            except (transport.TransferError, OSError, ValueError) as e:
                logger.warning("kv release for %s failed (%s); producer "
                               "pin timeout will reclaim", req.request_id, e)
            break
        # P->D wire span (phase "transfer"): the KV-transfer leg of the
        # PD TTFT decomposition, with the byte count the NetKV-style
        # transfer-cost scorer will want per link.
        tracing.get_tracer("engine").record_span(
            "kv.transfer", wall0, time.time(), parent=req.trace_ctx,
            request_id=req.request_id, phase="transfer",
            bytes=len(blob) if blob else 0,
            source=f"{host}:{port}", error=error)
        self._loaded.put((req, blob, error, time.perf_counter() - t0))

    def abort(self, request_id: str) -> None:
        """Mark an in-flight pull's request aborted (dropped at poll) and
        release the PRODUCER's pinned blocks eagerly — a cancelled or
        deadline-expired consumer must propagate P->D, or the producer's
        cache shrinks until its pin timeout fires."""
        with self._inflight_mu:
            if request_id not in self._pending_ids:
                return
            self._aborted.add(request_id)
            remote = self._pending_params.get(request_id)
        if remote is not None:
            self._release_remote(request_id, remote)

    def _release_remote(self, request_id: str,
                        remote: Tuple[str, int, str]) -> None:
        """Best-effort producer release off the engine thread (the
        producer's pin timeout is the backstop when this fails)."""
        host, port, uuid = remote

        def _release():
            try:
                transport.release(host, port, uuid,
                                  timeout_ms=self.config.timeout_ms)
            except (transport.TransferError, OSError, ValueError) as e:
                logger.warning(
                    "cancel-release for %s failed (%s); producer pin "
                    "timeout will reclaim", request_id, e)
        threading.Thread(target=_release,
                         name=f"kv-cancel-{request_id[:8]}",
                         daemon=True).start()

    def has_pending(self) -> bool:
        with self._inflight_mu:
            if self._inflight > 0:
                return True
        return bool(self._retry) or bool(self._pin_times)

    @property
    def num_pending_loads(self) -> int:
        """In-flight + retry-parked KV pulls: load the scheduler can't see
        yet (the DP dispatcher counts these, or every PD request would pile
        onto rank 0 while its pulls are still in flight)."""
        with self._inflight_mu:
            return self._inflight + len(self._retry)

    def poll(self, engine) -> List[RequestOutput]:
        """Engine-thread pump: finish loads, admit requests, drain releases."""
        self._poll_producer(engine)
        outputs: List[RequestOutput] = []

        ready: List[Tuple[Request, bytes]] = list(self._retry)
        self._retry.clear()
        while True:
            try:
                req, blob, error, dt = self._loaded.get_nowait()
            except queue.Empty:
                break
            with self._inflight_mu:
                self._inflight -= 1
                self._pending_ids.discard(req.request_id)
                self._pending_params.pop(req.request_id, None)
            if req.request_id in self._aborted:
                self._aborted.discard(req.request_id)
                req.state = RequestState.FINISHED_ABORTED
                continue
            if error is not None or blob is None:
                outputs.extend(self._load_failed(engine, req, error or "empty"))
                continue
            engine.metrics.kv_transfer_time.observe(dt)
            engine.metrics.observe_phase("transfer", req.criticality, dt)
            ready.append((req, blob))
        if self._aborted:
            dropped = [r for r, _ in ready if r.request_id in self._aborted]
            for r in dropped:
                r.state = RequestState.FINISHED_ABORTED
                self._aborted.discard(r.request_id)
                with self._inflight_mu:
                    self._pending_ids.discard(r.request_id)
            ready = [(r, b) for r, b in ready
                     if r.state is not RequestState.FINISHED_ABORTED]

        for req, blob in ready:
            with self._inflight_mu:
                self._pending_ids.discard(req.request_id)
            if req.deadline_expired():
                # Budget blew while the KV slab was in flight / parked:
                # drop before allocating a single local block.  The
                # producer's pin was already released post-fetch.
                req.state = RequestState.FINISHED_DEADLINE
                engine.metrics.inc_deadline_exceeded(req.criticality)
                outputs.append(RequestOutput(
                    req.request_id, [], True,
                    finish_reason=RequestState.FINISHED_DEADLINE.value))
                continue
            out = self._admit(engine, req, blob)   # re-adds if retried
            if out is not None:
                outputs.append(out)
        return outputs

    def _admit(self, engine, req: Request, blob: bytes) -> Optional[RequestOutput]:
        """Scatter the fetched KV into local blocks and make req schedulable."""
        P = req.num_prompt_tokens
        bs = engine.config.block_size
        nb = -(-P // bs)
        # Gate against the request's OWN region (SPMD dp pins requests to a
        # KV shard): a pool-wide can_allocate would pass while the pinned
        # region stays full — gate and allocation must agree.  On failure
        # the pin is dropped so the next poll may re-route by capacity.
        km = engine.kv_manager
        region = km.assign_region(req)
        if not km.can_allocate(nb, region):
            # Cache pressure: hold the slab and retry next poll (the blocks
            # will free as running requests finish). Still abortable.
            km.unpin(req)
            self._retry.append((req, blob))
            with self._inflight_mu:
                self._pending_ids.add(req.request_id)
            return None
        attached = km.allocate(req, P)
        if attached is None:
            km.unpin(req)
            self._retry.append((req, blob))
            with self._inflight_mu:
                self._pending_ids.add(req.request_id)
            return None
        try:
            _scatter_blocks(engine, req.block_ids, blob)
        except ValueError as e:
            engine.kv_manager.free(req)
            return_list = self._load_failed(engine, req, f"bad slab: {e}")
            return return_list[0] if return_list else None
        req.num_computed_tokens = P - 1   # last prompt token recomputed locally
        req.kv_transfer_params = None
        engine.scheduler.add_request(req)
        return None

    def _load_failed(self, engine, req: Request, error: str
                     ) -> List[RequestOutput]:
        if self.config.kv_load_failure_policy == "recompute":
            logger.warning("kv load failed for %s (%s); recomputing locally",
                           req.request_id, error)
            req.do_remote_prefill = False
            req.kv_transfer_params = None
            engine.scheduler.add_request(req)
            return []
        logger.error("kv load failed for %s: %s", req.request_id, error)
        req.state = RequestState.FINISHED_ABORTED
        return [RequestOutput(req.request_id, [], True,
                              finish_reason=RequestState.FINISHED_ABORTED.value)]

    def close(self) -> None:
        if self.server is not None:
            self.server.close()


# ---------------------------------------------------------------------------
# Device <-> host slab marshalling.  One jitted program per (padded) block
# count: gather/scatter the [L, slots, F] stacked cache at whole-block
# granularity, staged through a single contiguous [2, L, nb*bs, F] buffer.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _gather_fn(num_blocks: int, block_size: int):
    @jax.jit
    def gather(buf, block_ids):
        # block_ids: [nb] int32 (padded entries point at the null block 0).
        slots = (block_ids[:, None] * block_size
                 + jnp.arange(block_size, dtype=jnp.int32)[None, :]).reshape(-1)
        return buf[:, slots, :]                   # [L, nb*bs, W]
    return gather


@functools.lru_cache(maxsize=32)
def _scatter_fn(num_blocks: int, block_size: int):
    @functools.partial(jax.jit, donate_argnums=(0,))
    def scatter(buf, block_ids, slab):
        slots = (block_ids[:, None] * block_size
                 + jnp.arange(block_size, dtype=jnp.int32)[None, :]).reshape(-1)
        return buf.at[:, slots, :].set(slab)
    return scatter


@functools.lru_cache(maxsize=32)
def _gather_fn_stacked(num_blocks: int, block_size: int, shard: int):
    """Stacked (SPMD dp) cache: gather blocks from one shard's plane.

    The shard index is baked into the jitted program so XLA fuses the
    plane slice into the gather — slicing ``buf[shard]`` OUTSIDE jit would
    materialize the whole multi-GB plane to move a handful of blocks."""
    @jax.jit
    def gather(buf, block_ids):
        slots = (block_ids[:, None] * block_size
                 + jnp.arange(block_size, dtype=jnp.int32)[None, :]).reshape(-1)
        return buf[shard][:, slots, :]            # [L, nb*bs, W]
    return gather


@functools.lru_cache(maxsize=32)
def _scatter_fn_stacked(num_blocks: int, block_size: int, shard: int):
    """Stacked (SPMD dp) cache: write one shard's plane in place.

    NOTE: ``buf.at[shard, :, slots, :]`` would MIX the scalar and array
    advanced indices across the basic slice, moving the slots dim to the
    front (numpy advanced-indexing rule) — update the plane with a single
    advanced index instead."""
    @functools.partial(jax.jit, donate_argnums=(0,))
    def scatter(buf, block_ids, slab):
        slots = (block_ids[:, None] * block_size
                 + jnp.arange(block_size, dtype=jnp.int32)[None, :]).reshape(-1)
        plane = buf[shard].at[:, slots, :].set(slab)
        return buf.at[shard].set(plane)
    return scatter


def _cache_items(engine):
    """Deterministically ordered cache buffers ({k, v} dense, {kv} MLA)."""
    return sorted(engine.kv_cache.items())


def _resolve_blocks(engine, block_ids: List[int]):
    """Global block ids -> (shard plane or None, shard-local ids).

    Stacked caches (SPMD dp) hold [dp, L, slots_l, W]; a request's blocks
    all live in ONE region by construction (engine.kv_cache regions), so a
    transfer addresses a single plane.  The wire format stays identical
    across dp configurations — only device addressing changes."""
    dp = getattr(engine, "dp", 1)
    if dp == 1:
        return None, np.asarray(block_ids, np.int32)
    B_l = engine.kv_manager.blocks_per_region
    shards = {b // B_l for b in block_ids} or {0}
    assert len(shards) == 1, f"transfer blocks span dp shards: {shards}"
    r = shards.pop()
    return r, np.asarray([b % B_l for b in block_ids], np.int32)


def _pack_blocks(engine, block_ids: List[int]) -> bytes:
    bs = engine.config.block_size
    nb = len(block_ids)
    shard, local_ids = _resolve_blocks(engine, block_ids)
    nb_pad = _next_pow2(max(nb, 1))
    ids = np.zeros(nb_pad, np.int32)   # pad gathers the null block; trimmed
    ids[:nb] = local_ids
    ids_dev = jnp.asarray(ids)
    items = _cache_items(engine)
    L = items[0][1].shape[0] if shard is None else items[0][1].shape[1]
    parts = [_HEADER.pack(_MAGIC, _WIRE_VERSION, L, bs, len(items), nb)]
    for _, buf in items:
        if shard is None:
            slab = _gather_fn(nb_pad, bs)(buf, ids_dev)
            width = buf.shape[2]
        else:
            slab = _gather_fn_stacked(nb_pad, bs, shard)(buf, ids_dev)
            width = buf.shape[3]
        host = np.asarray(jax.device_get(slab))[:, :nb * bs, :]
        parts.append(_BUF_HEADER.pack(
            width, transport.wire_dtype_code(host.dtype)))
        parts.append(host.tobytes())
    return b"".join(parts)


def _scatter_blocks(engine, block_ids: List[int], blob: bytes) -> None:
    bs = engine.config.block_size
    magic, ver, bL, bbs, n_bufs, bnb = _HEADER.unpack_from(blob, 0)
    if magic != _MAGIC:
        raise ValueError("bad magic")
    if ver != _WIRE_VERSION:
        raise ValueError(
            f"KV wire version {ver} != {_WIRE_VERSION} (peer running an "
            "incompatible build; refusing to reinterpret the slab)")
    items = _cache_items(engine)
    shard, local_ids = _resolve_blocks(engine, block_ids)
    L = items[0][1].shape[0] if shard is None else items[0][1].shape[1]
    if (bL, bbs, n_bufs) != (L, bs, len(items)):
        raise ValueError(
            f"slab layout {(bL, bbs, n_bufs)} != cache layout "
            f"{(L, bs, len(items))} (a producer of another cache dtype "
            "ships another buffer set)")
    nb = len(block_ids)
    if bnb < nb:
        raise ValueError(f"slab has {bnb} blocks, need {nb}")
    nb_pad = _next_pow2(max(nb, 1))
    if nb_pad != nb:
        # Padded scatter targets must be real, distinct slots: route the
        # pad writes into the null block (local block 0 is the trash block).
        ids = np.zeros(nb_pad, np.int32)
        ids[:nb] = local_ids
    else:
        ids = local_ids
    ids_dev = jnp.asarray(ids)
    off = _HEADER.size
    for name, buf in items:
        width_have = buf.shape[2] if shard is None else buf.shape[3]
        width, code = _BUF_HEADER.unpack_from(blob, off)
        off += _BUF_HEADER.size
        if width != width_have:
            raise ValueError(
                f"buffer {name!r}: slab width {width} != cache {width_have}")
        try:
            dtype = transport.wire_dtype(code)
        except transport.TransferError as e:
            raise ValueError(str(e)) from e
        if dtype != np.dtype(buf.dtype):
            # Explicit dtype-mismatch rejection: a bf16 decoder never
            # silently reinterprets an int8 producer's blocks — the cache
            # dtype must match across the P->D pair.
            raise ValueError(
                f"buffer {name!r}: producer shipped {dtype} but the local "
                f"cache is {np.dtype(buf.dtype)} — cache dtype "
                "mismatch, refusing to reinterpret")
        count = L * bnb * bs * width
        payload = np.frombuffer(blob, dtype=dtype, offset=off, count=count)
        off += count * dtype.itemsize
        slab = payload.reshape(L, bnb * bs, width)[:, :nb * bs, :]
        if nb_pad != nb:
            pad = np.zeros((L, nb_pad * bs, width), dtype)
            pad[:, :nb * bs, :] = slab
            slab = pad
        fn = (_scatter_fn(nb_pad, bs) if shard is None
              else _scatter_fn_stacked(nb_pad, bs, shard))
        engine.kv_cache[name] = fn(buf, ids_dev, jnp.asarray(slab))
