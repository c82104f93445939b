"""Host-buffer transport under the KV connector (the NIXL/UCX role).

The data plane is native C++ (``native/kv_transfer.cpp``), compiled on
first use from the committed source and driven via ctypes: a
registered-slab server whose accept loop runs off the GIL, plus blocking
fetch/release clients.  The binary is never committed: it is built into
``native/libkvtransfer-<sha256 of the source>.so`` — a path fixed by the
source's CONTENT, so a copy of the tree with arbitrary mtimes neither
rebuilds needlessly nor loads a stale library — and an image build may
prebuild it (docker/Dockerfile.tpu) so the runtime needs no toolchain.

The facade (``make_server`` / ``fetch`` / ``release``) IS the native
plane: a producer or consumer that cannot have it fails loudly with the
compiler's message instead of degrading to Python.  The pure-Python
transport below speaks the identical wire protocol; it serves the
offload tier's shared-cache server (engine/offload.py asks for it by
name) and cross-checks the protocol in tests.

Reference roles mirrored here: NIXL point-to-point KV transfer without a
metadata side channel (docs/proposals/llm-d.md:60-68); the vLLM TPUConnector
contract's remote_host/remote_port/uuid addressing (README.tpu.md:182-189).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import socket
import struct
import subprocess
import threading
from typing import Deque, Dict, List, Optional, Tuple

logger = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "native")
_SRC = os.path.join(_NATIVE_DIR, "kv_transfer.cpp")
_build_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


class TransferError(Exception):
    pass


class TransferNotFound(TransferError):
    pass


def _lib_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_NATIVE_DIR, f"libkvtransfer-{digest}.so")


def _load_native() -> ctypes.CDLL:
    """Build (if this source has no library yet) and load the native
    transport.  Raises ``TransferError`` carrying the toolchain's message
    when it cannot: there is no silent Python fallback."""
    global _lib
    if _lib is not None:
        return _lib
    with _build_lock:
        if _lib is not None:
            return _lib
        try:
            path = _lib_path()
            if not os.path.exists(path):
                tmp = f"{path}.{os.getpid()}.tmp"   # atomic publish only
                subprocess.run(
                    ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                     "-pthread", "-o", tmp, _SRC],
                    check=True, capture_output=True, text=True)
                os.replace(tmp, path)
            lib = ctypes.CDLL(path)
        except subprocess.CalledProcessError as e:
            raise TransferError(
                f"native kv-transfer build failed: {e.stderr[-2000:]}") from e
        except OSError as e:
            raise TransferError(
                f"native kv-transfer unavailable: {e}") from e
        lib.kvts_create.restype = ctypes.c_void_p
        lib.kvts_create.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.kvts_port.restype = ctypes.c_int
        lib.kvts_port.argtypes = [ctypes.c_void_p]
        lib.kvts_register.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_uint64]
        lib.kvts_unregister.restype = ctypes.c_int
        lib.kvts_unregister.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.kvts_next_released.restype = ctypes.c_int
        lib.kvts_next_released.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
        lib.kvts_destroy.argtypes = [ctypes.c_void_p]
        lib.kvts_fetch.restype = ctypes.c_int64
        lib.kvts_fetch.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_char))]
        lib.kvts_free.argtypes = [ctypes.POINTER(ctypes.c_char)]
        lib.kvts_release.restype = ctypes.c_int
        lib.kvts_release.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
        _lib = lib
    return _lib


# ---------------------------------------------------------------------------
# Wire-level dtype tags, shared by every KV payload format riding this
# transport (the PD slab wire in transfer/connector.py and the offload
# tier's packed-block format in engine/offload.py).  A one-byte code per
# buffer segment lets a receiver REJECT a dtype-mismatched producer —
# a peer of an older build may still send int8 rows + f32 scale planes,
# which must never be silently reinterpreted as bf16 rows (the byte count
# alone would already misparse, but the code makes the failure a named
# error).
# ---------------------------------------------------------------------------

WIRE_DTYPE_BF16 = 0
WIRE_DTYPE_INT8 = 1
WIRE_DTYPE_F32 = 2


def wire_dtype_code(dtype) -> int:
    """numpy/jax dtype -> wire code; raises on an unshippable dtype."""
    import ml_dtypes
    import numpy as np
    dt = np.dtype(dtype)
    if dt == np.dtype(ml_dtypes.bfloat16):
        return WIRE_DTYPE_BF16
    if dt == np.dtype(np.int8):
        return WIRE_DTYPE_INT8
    if dt == np.dtype(np.float32):
        return WIRE_DTYPE_F32
    raise TransferError(f"dtype {dt} has no KV wire code")


def wire_dtype(code: int):
    """Wire code -> numpy dtype; raises TransferError on unknown codes
    (a newer producer's format must fail loudly, not misparse)."""
    import ml_dtypes
    import numpy as np
    table = {WIRE_DTYPE_BF16: np.dtype(ml_dtypes.bfloat16),
             WIRE_DTYPE_INT8: np.dtype(np.int8),
             WIRE_DTYPE_F32: np.dtype(np.float32)}
    if code not in table:
        raise TransferError(f"unknown KV wire dtype code {code}")
    return table[code]


def _resolve(host: str) -> str:
    """The native client only speaks dotted quads; resolve names here."""
    try:
        socket.inet_aton(host)
        return host
    except OSError:
        return socket.gethostbyname(host)


class NativeTransferServer:
    """Slab registry + TCP server backed by the C++ accept loop."""

    def __init__(self, host: str = "0.0.0.0", port: int = 0) -> None:
        lib = self._lib = _load_native()
        self._handle = lib.kvts_create(_resolve(host).encode()
                                       if host != "0.0.0.0" else b"0.0.0.0",
                                       port)
        if not self._handle:
            raise TransferError(f"kvts_create failed on {host}:{port}")
        self.port = lib.kvts_port(self._handle)

    def register(self, uuid: str, blob: bytes) -> None:
        self._lib.kvts_register(self._handle, uuid.encode(), blob, len(blob))

    def unregister(self, uuid: str) -> bool:
        return bool(self._lib.kvts_unregister(self._handle, uuid.encode()))

    def drain_released(self) -> List[str]:
        out: List[str] = []
        buf = ctypes.create_string_buffer(4096)
        while True:
            n = self._lib.kvts_next_released(self._handle, buf, 4096)
            if n <= 0:
                break
            out.append(buf.raw[:n].decode())
        return out

    def close(self) -> None:
        if self._handle:
            self._lib.kvts_destroy(self._handle)
            self._handle = None


def native_fetch(host: str, port: int, uuid: str,
                 timeout_ms: int = 30000) -> bytes:
    lib = _load_native()
    out = ctypes.POINTER(ctypes.c_char)()
    n = lib.kvts_fetch(_resolve(host).encode(), port, uuid.encode(),
                       timeout_ms, ctypes.byref(out))
    if n == -2:
        raise TransferNotFound(f"uuid {uuid!r} not registered on "
                               f"{host}:{port}")
    if n < 0:
        raise TransferError(f"fetch {uuid!r} from {host}:{port} failed")
    try:
        return ctypes.string_at(out, n)
    finally:
        lib.kvts_free(out)


def native_release(host: str, port: int, uuid: str,
                   timeout_ms: int = 10000) -> bool:
    lib = _load_native()
    return bool(lib.kvts_release(_resolve(host).encode(), port,
                                 uuid.encode(), timeout_ms))


# ---------------------------------------------------------------------------
# Pure-Python transport: identical wire protocol; asked for by name (the
# offload tier's shared-cache server, the protocol cross-check in tests).
# ---------------------------------------------------------------------------

_NOT_FOUND = 0xFFFFFFFFFFFFFFFF


def _recv_full(sock: socket.socket, n: int) -> bytes:
    chunks = []
    while n > 0:
        b = sock.recv(min(n, 1 << 20))
        if not b:
            raise TransferError("connection closed mid-frame")
        chunks.append(b)
        n -= len(b)
    return b"".join(chunks)


class PyTransferServer:
    """threading-based server with the same interface as the native one."""

    def __init__(self, host: str = "0.0.0.0", port: int = 0) -> None:
        self._blobs: Dict[str, bytes] = {}
        self._released: Deque[str] = __import__("collections").deque()
        self._mu = threading.Lock()
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(64)
        self.port = self._sock.getsockname()[1]
        self._stop = False
        self._thread = threading.Thread(
            target=self._accept_loop, name="kv-transfer", daemon=True)
        self._thread.start()

    def _accept_loop(self) -> None:
        while not self._stop:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                break
            threading.Thread(
                target=self._handle, args=(conn,), daemon=True).start()

    def _handle(self, conn: socket.socket) -> None:
        try:
            op = _recv_full(conn, 1)[0]
            (uuid_len,) = struct.unpack("<I", _recv_full(conn, 4))
            uuid = _recv_full(conn, uuid_len).decode()
            if op == 1:
                with self._mu:
                    blob = self._blobs.get(uuid)
                if blob is None:
                    conn.sendall(struct.pack("<Q", _NOT_FOUND))
                else:
                    conn.sendall(struct.pack("<Q", len(blob)))
                    conn.sendall(blob)
            elif op == 2:
                with self._mu:
                    self._blobs.pop(uuid, None)
                    self._released.append(uuid)
                conn.sendall(b"\x01")
        except (TransferError, OSError):
            pass
        finally:
            conn.close()

    def register(self, uuid: str, blob: bytes) -> None:
        with self._mu:
            self._blobs[uuid] = blob

    def unregister(self, uuid: str) -> bool:
        with self._mu:
            return self._blobs.pop(uuid, None) is not None

    def drain_released(self) -> List[str]:
        out: List[str] = []
        with self._mu:
            while self._released:
                out.append(self._released.popleft())
        return out

    def close(self) -> None:
        self._stop = True
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._sock.close()


def py_fetch(host: str, port: int, uuid: str, timeout_ms: int = 30000) -> bytes:
    with socket.create_connection((host, port), timeout=timeout_ms / 1000) as s:
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        u = uuid.encode()
        s.sendall(b"\x01" + struct.pack("<I", len(u)) + u)
        (size,) = struct.unpack("<Q", _recv_full(s, 8))
        if size == _NOT_FOUND:
            raise TransferNotFound(
                f"uuid {uuid!r} not registered on {host}:{port}")
        return _recv_full(s, size)


def py_release(host: str, port: int, uuid: str, timeout_ms: int = 10000) -> bool:
    try:
        with socket.create_connection(
                (host, port), timeout=timeout_ms / 1000) as s:
            u = uuid.encode()
            s.sendall(b"\x02" + struct.pack("<I", len(u)) + u)
            return _recv_full(s, 1) == b"\x01"
    except (OSError, TransferError):
        return False


# ---------------------------------------------------------------------------
# Facade: the native plane (see module docstring — no Python fallback).
# ---------------------------------------------------------------------------

make_server = NativeTransferServer
fetch = native_fetch
release = native_release
