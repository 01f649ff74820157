"""PS service: TCP transport over the native table store.

Capability parity with the reference RPC PS runtime
(reference: paddle/fluid/operators/distributed/ — RPCServer + request
handlers SendVar/GetVar/PrefetchVar in request_handler_impl.cc,
grpc/brpc transports; listen_and_serv_op.cc server loop; HeartBeatMonitor
heart_beat_monitor.h:54; BarrierMonitor :106).  Storage + server-side
optimize live in C++ (native/ps_table.cpp); the wire protocol is a
length-prefixed JSON header + raw ndarray payload over TCP sockets.
"""
from __future__ import annotations

import json
import os
import random as random_mod
import socket
import socketserver
import struct
import threading
import time
import uuid
from typing import Dict, Optional

import numpy as np

from ..profiler import RecordEvent
from ..utils import telemetry as tm
from ..utils import tracing
from .table import DenseTable, SparseTable


class BarrierMonitor:
    """Worker-liveness barrier (reference: operators/distributed/
    barrier_monitor.h:106).

    Trainers announce themselves on every barrier entry; a monitor thread
    watches partially-filled barriers and, when the oldest waiter has been
    stuck longer than ``timeout``, releases everyone with the list of
    missing trainer ids — the failure-detection signal the reference's
    monitor thread swamp_in/valid loop produces.  ``decrease``/``increase``
    adjust the expected worker count for elastic membership.
    """

    def __init__(self, n_trainers: int, timeout: float = 120.0):
        self.n = max(int(n_trainers), 1)
        self.timeout = timeout
        self._cv = threading.Condition()
        self._arrived: Dict[int, float] = {}
        self._generation = 0
        self._released_gen = -1
        self._failed: list = []
        self._valid = True
        self._stop = False
        self._monitor = threading.Thread(target=self._watch, daemon=True)
        self._monitor.start()

    # ------------------------------------------------------------------
    def wait(self, trainer_id: int, timeout: Optional[float] = None):
        """Block until all n trainers arrive.  Returns [] on success or
        the sorted list of missing trainer ids when the monitor released
        a broken round."""
        timeout = timeout or self.timeout
        with self._cv:
            gen = self._generation
            self._arrived[trainer_id] = time.time()
            if len(self._arrived) >= self.n:
                # last arrival completes the round
                self._generation += 1
                self._released_gen = gen
                self._failed = []
                self._arrived.clear()
                self._cv.notify_all()
                return []
            deadline = time.time() + timeout
            while self._released_gen < gen:
                remaining = deadline - time.time()
                if remaining <= 0 or not self._cv.wait(timeout=min(remaining, 1.0)):
                    if self._released_gen >= gen:
                        break
                    if time.time() >= deadline:
                        # caller-side timeout: release the WHOLE
                        # generation, exactly like the monitor thread —
                        # removing only our own arrival would leave the
                        # other waiters blocked on a round that can no
                        # longer complete, and they would later observe a
                        # different missing-trainer list
                        missing = self._missing_locked()
                        self._failed = missing
                        self._valid = False
                        self._released_gen = self._generation
                        self._generation += 1
                        self._arrived.clear()
                        self._cv.notify_all()
                        return missing
            return list(self._failed)

    def _missing_locked(self):
        present = set(self._arrived)
        return sorted(set(range(self.n)) - present)

    def _watch(self):
        while not self._stop:
            time.sleep(min(self.timeout / 4, 1.0))
            with self._cv:
                if not self._arrived or len(self._arrived) >= self.n:
                    continue
                oldest = min(self._arrived.values())
                if time.time() - oldest > self.timeout:
                    # release the round as FAILED with the missing ids
                    self._failed = self._missing_locked()
                    self._valid = False
                    self._released_gen = self._generation
                    self._generation += 1
                    self._arrived.clear()
                    self._cv.notify_all()

    # ------------------------------------------------------------------
    def valid(self) -> bool:
        with self._cv:
            return self._valid

    def reset_valid(self):
        with self._cv:
            self._valid = True
            self._failed = []

    def increase(self, k: int = 1):
        with self._cv:
            self.n += k

    def decrease(self, k: int = 1):
        with self._cv:
            self.n = max(self.n - k, 1)
            if len(self._arrived) >= self.n:
                # stale failure info from a previous broken round must not
                # leak into this successfully-completed one
                self._failed = []
                self._released_gen = self._generation
                self._generation += 1
                self._arrived.clear()
                self._cv.notify_all()

    def stop(self):
        self._stop = True


# --------------------------------------------------------------------------
# wire format: [u32 header_len][header json][payload bytes]
# header: {"op": str, "name": str, "meta": {...}, "arrays": [[dtype, shape,
#          nbytes], ...]}
# --------------------------------------------------------------------------
#: state-changing control-plane ops: the client stamps these with an
#: idempotence key (meta["req_id"]) and the server's RequestDeduper
#: short-circuits replays, so the retry layer can resend after a lost
#: reply without double-applying (reference: brpc's built-in retry is
#: safe only because its server dedupes log_ids the same way)
_MUTATING_OPS = frozenset({
    "push_dense", "push_sparse", "push_delta", "init_dense",
    "record_sparse_update", "blob_put",
})
#: ops the retry layer must NOT re-enter:
#: * barrier — a timed-out wait was already counted by the
#:   BarrierMonitor; resending would join the NEXT round;
#: * barrier_membership — applies a +/-delta; a lost-reply retry would
#:   double-apply it (and the dedup ack carries no n_trainers payload);
#: * pull_updated_rows / blob_take — DESTRUCTIVE reads (server-side
#:   get_and_clear / pop): after a lost reply the data is gone, and a
#:   retry would "succeed" with an empty answer, silently losing the
#:   rows/blobs — surface the transport error to the caller instead;
#: * stop — fire-and-forget shutdown.
_NO_RETRY_OPS = frozenset({"barrier", "barrier_membership",
                           "pull_updated_rows", "blob_take", "stop"})
def _send_msg(sock, op: str, name: str = "", meta: dict = None, arrays=()):
    arrays = [np.ascontiguousarray(a) for a in arrays]
    header = json.dumps({
        "op": op, "name": name, "meta": meta or {},
        "arrays": [[str(a.dtype), list(a.shape), a.nbytes] for a in arrays],
    }).encode()
    payload = b"".join(a.tobytes() for a in arrays)
    sock.sendall(struct.pack("<I", len(header)) + header + payload)


def _recv_exact(sock, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed")
        buf.extend(chunk)
    return bytes(buf)


def _recv_msg(sock):
    (hlen,) = struct.unpack("<I", _recv_exact(sock, 4))
    header = json.loads(_recv_exact(sock, hlen).decode())
    arrays = []
    for dtype, shape, nbytes in header["arrays"]:
        raw = _recv_exact(sock, nbytes)
        arrays.append(np.frombuffer(raw, dtype=dtype).reshape(shape).copy())
    return header["op"], header["name"], header["meta"], arrays


class PSServer:
    """One PS shard: owns a set of named dense/sparse tables."""

    def __init__(self, endpoint: str, n_trainers: int = 1):
        host, port = endpoint.rsplit(":", 1)
        self.host, self.port = host, int(port)
        self.n_trainers = n_trainers
        self.dense: Dict[str, DenseTable] = {}
        self.sparse: Dict[str, SparseTable] = {}
        self._barrier = threading.Barrier(max(n_trainers, 1))
        self._barrier_monitor = BarrierMonitor(n_trainers)
        from .update_recorder import (AsyncSparseParamUpdateRecorder,
                                      RequestDeduper)

        # async/geo mode: per-trainer updated-rows tracking (reference:
        # async_sparse_param_update_recorder.h — only instantiated when
        # sync_mode=false there; here recording is off until an
        # async-family mode enables it, so sync servers never accumulate
        # per-trainer row sets)
        self.update_recorder = AsyncSparseParamUpdateRecorder(n_trainers)
        self.record_sparse_updates = False
        # idempotent-retry guard: req_id-stamped mutating ops replayed
        # by a client's retry loop (lost reply) are acked, not re-applied
        self.dedup = RequestDeduper()
        self._blobs: Dict[str, list] = {}
        self._heartbeats: Dict[int, float] = {}
        self._lock = threading.Lock()
        self._server: Optional[socketserver.ThreadingTCPServer] = None
        self._thread: Optional[threading.Thread] = None
        # native binary-framed data plane (grpc_server.cc analog): the
        # pull/push hot path served from C++ (native/ps_table.cpp
        # ps_serve_start) with no Python/GIL involvement; the JSON
        # control plane here keeps barriers/heartbeats/blobs/checkpoints
        self.data_port = 0

    # ------------------------------------------------------------------
    def _handle(self, op, name, meta, arrays, sock):
        try:
            self._handle_inner(op, name, meta, arrays, sock)
        except (ConnectionError, OSError):
            raise
        except Exception as e:  # reply instead of killing the connection
            _send_msg(sock, "error",
                      meta={"what": f"{type(e).__name__}: {e}", "op": op,
                            "table": name})

    def _handle_inner(self, op, name, meta, arrays, sock):
        # server-side span (r17): a request carrying trace_ctx gets its
        # handling recorded against the SAME trace id (parented on the
        # client's span), so one trace shows the RPC end-to-end
        ctx = (meta or {}).get("trace_ctx")
        s_tr = s_span = None
        if ctx and tracing.enabled():
            s_tr, s_span = tracing.server_span(
                f"ps_server:{op}", ctx, attrs={"op": op})
        try:
            self._handle_deduped(op, name, meta, arrays, sock,
                                 s_tr, s_span)
        finally:
            if s_span is not None:
                s_tr.end(s_span)  # no-op when a branch already ended it

    def _handle_deduped(self, op, name, meta, arrays, sock, s_tr, s_span):
        trace_id = ((meta or {}).get("trace_ctx") or {}).get("trace_id")
        req_id = (meta or {}).get("req_id")
        if not (req_id and op in _MUTATING_OPS):
            self._dispatch_traced(op, name, meta, arrays, sock,
                                  s_tr, s_span)
            return
        # begin() BLOCKS while the same id is mid-apply on another
        # thread (a fast retry can land on a new connection before the
        # original apply finishes), then answers duplicate-or-claimed
        if self.dedup.begin(req_id):
            # first attempt fully applied, its reply was lost: ack
            # without touching state — the span is tagged as a dedup
            # replay carrying the ORIGINAL apply's trace id
            tm.counter("ps_dedup_replays_total",
                       "mutating RPCs acked from the server deduper "
                       "(lost-reply retries short-circuited)").inc()
            if s_span is not None:
                s_tr.end(s_span, attrs={
                    "dedup_replay": True,
                    "origin_trace": self.dedup.origin(req_id) or ""})
            _send_msg(sock, "ok", meta={"duplicate": True})
            return
        try:
            self._dispatch_traced(op, name, meta, arrays, sock,
                                  s_tr, s_span)
        except (ConnectionError, OSError):
            # mutating branches touch no sockets while applying — a
            # transport error out of one means the APPLY completed and
            # only the ok-reply failed to send (the exact lost-reply
            # case): commit, so the incoming retry is acked not
            # re-applied.
            self.dedup.commit(req_id, trace_id=trace_id)
            if s_span is not None:
                s_tr.end(s_span, attrs={"reply_lost": True})
            raise
        except BaseException:
            # apply failed (an "error" reply goes out via _handle):
            # release the claim — the client does not retry app errors,
            # but a manual resend may legitimately re-apply
            self.dedup.abort(req_id)
            raise
        self.dedup.commit(req_id, trace_id=trace_id)

    def _dispatch_traced(self, op, name, meta, arrays, sock, s_tr, s_span):
        if s_span is None:
            self._dispatch(op, name, meta, arrays, sock)
        else:
            with tracing.use_span(s_tr, s_span):
                self._dispatch(op, name, meta, arrays, sock)

    def _dispatch(self, op, name, meta, arrays, sock):
        if op == "create_dense":
            with self._lock:
                if name not in self.dense:
                    t = DenseTable(
                        meta["size"], meta.get("optimizer", "sgd"),
                        meta.get("lr", 0.01), meta.get("mu", 0.9),
                        meta.get("beta1", 0.9), meta.get("beta2", 0.999),
                        meta.get("eps", 1e-8))
                    self.dense[name] = t
                    if self.data_port > 0:
                        from .table import bind_name

                        bind_name(name, 0, t.tid)
            _send_msg(sock, "ok")
        elif op == "create_sparse":
            with self._lock:
                if name not in self.sparse:
                    t = SparseTable(
                        meta["dim"], meta.get("init_range", 0.01),
                        meta.get("optimizer", "sgd"), meta.get("lr", 0.01),
                        meta.get("eps", 1e-8), meta.get("seed", 2026))
                    self.sparse[name] = t
                    if self.data_port > 0:
                        from .table import bind_name

                        bind_name(name, 1, t.tid)
            _send_msg(sock, "ok")
        elif op == "data_port":
            _send_msg(sock, "ok", meta={"port": self.data_port,
                                        "host": self.host})
        elif op == "init_dense":
            self.dense[name].init(arrays[0])
            _send_msg(sock, "ok")
        elif op == "pull_dense":
            _send_msg(sock, "ok", arrays=[self.dense[name].pull()])
        elif op == "push_dense":
            self.dense[name].push_grad(arrays[0])
            _send_msg(sock, "ok")
        elif op == "push_delta":
            # GEO-SGD delta apply: param += delta, no server optimizer
            # (reference: GeoSgdCommunicator's SendUpdateDenseVars)
            t = self.dense[name]
            with self._lock:
                t.init(t.pull() + arrays[0])
            _send_msg(sock, "ok")
        elif op == "pull_sparse":
            _send_msg(sock, "ok", arrays=[self.sparse[name].pull(arrays[0])])
        elif op == "push_sparse":
            self.sparse[name].push_grad(arrays[0], arrays[1])
            if self.record_sparse_updates:
                self.update_recorder.update(name, arrays[0].tolist())
            _send_msg(sock, "ok")
        elif op == "record_sparse_update":
            # native-data-plane pushes notify the recorder via this
            # control-plane message (also enables recording: only
            # async-family clients send it)
            self.record_sparse_updates = True
            self.update_recorder.update(name, arrays[0].tolist())
            _send_msg(sock, "ok")
        elif op == "enable_update_recording":
            self.record_sparse_updates = bool(meta.get("enable", True))
            _send_msg(sock, "ok")
        elif op == "pull_updated_rows":
            rows = self.update_recorder.get_and_clear(
                name, int(meta.get("trainer_id", 0)))
            _send_msg(sock, "ok",
                      arrays=[np.asarray(rows, np.int64)])
        elif op == "barrier":
            # reference: send_barrier/fetch_barrier ops + BarrierMonitor
            trainer_id = meta.get("trainer_id", -1)
            if trainer_id >= 0:
                # monitored path: failure detection with missing-ids report
                missing = self._barrier_monitor.wait(
                    trainer_id, meta.get("timeout"))
                if missing:
                    _send_msg(sock, "error",
                              meta={"what": "barrier broken",
                                    "missing_trainers": missing})
                    return
                _send_msg(sock, "ok")
                return
            try:
                self._barrier.wait(timeout=meta.get("timeout", 120.0))
            except threading.BrokenBarrierError:
                # recover for subsequent rounds; exactly one waiter resets
                # (a second reset() would break waiters of the next round)
                with self._lock:
                    if self._barrier.broken:
                        self._barrier.reset()
                _send_msg(sock, "error", meta={"what": "barrier broken"})
                return
            _send_msg(sock, "ok")
        elif op == "barrier_status":
            _send_msg(sock, "ok", meta={
                "valid": self._barrier_monitor.valid(),
                "missing": list(self._barrier_monitor._failed),
                "n_trainers": self._barrier_monitor.n,
            })
        elif op == "barrier_reset":
            self._barrier_monitor.reset_valid()
            _send_msg(sock, "ok")
        elif op == "barrier_membership":
            delta = int(meta.get("delta", 0))
            if delta > 0:
                self._barrier_monitor.increase(delta)
            elif delta < 0:
                self._barrier_monitor.decrease(-delta)
            _send_msg(sock, "ok", meta={"n_trainers": self._barrier_monitor.n})
        elif op == "heartbeat":
            # reference: HeartBeatMonitor worker liveness
            with self._lock:
                self._heartbeats[meta["trainer_id"]] = time.time()
            _send_msg(sock, "ok")
        elif op == "worker_status":
            now = time.time()
            with self._lock:
                status = {str(t): now - ts for t, ts in self._heartbeats.items()}
            _send_msg(sock, "ok", meta={"ages": status})
        elif op == "blob_put":
            # generic byte channel: dataset global-shuffle shards, size
            # allreduces (reference analog: FleetWrapper RPC instance
            # exchange in data_set.cc GlobalShuffle)
            with self._lock:
                self._blobs.setdefault(name, []).append(arrays[0].tobytes())
            _send_msg(sock, "ok")
        elif op == "blob_peek":
            with self._lock:
                blobs = list(self._blobs.get(name, []))
            _send_msg(sock, "ok",
                      arrays=[np.frombuffer(b, np.uint8) for b in blobs])
        elif op == "blob_take":
            with self._lock:
                blobs = self._blobs.pop(name, [])
            _send_msg(sock, "ok",
                      arrays=[np.frombuffer(b, np.uint8) for b in blobs])
        elif op == "save":
            self._save(meta["path"])
            _send_msg(sock, "ok")
        elif op == "load":
            self._load(meta["path"])
            _send_msg(sock, "ok")
        elif op == "shrink":
            dropped = {n: t.shrink(meta.get("days", 0))
                       for n, t in self.sparse.items()}
            _send_msg(sock, "ok", meta={"dropped": dropped})
        elif op == "stop":
            _send_msg(sock, "ok")
            threading.Thread(target=self.stop, daemon=True).start()
        else:
            _send_msg(sock, "error", meta={"what": f"unknown op {op}"})

    def _save(self, path: str):
        """Checkpoint tables (reference: CheckpointNotify handler).
        Atomic per file (tmp + fsync + os.replace): a pserver killed
        mid-save leaves the previous snapshot readable, never a torn
        .npz that _load would crash on."""
        import os

        from ..utils.atomic_io import atomic_savez

        os.makedirs(path, exist_ok=True)
        dense = {n: t.pull() for n, t in self.dense.items()}
        atomic_savez(os.path.join(path, "dense.npz"), **dense)
        for n, t in self.sparse.items():
            ids, ws = t.export_rows()
            atomic_savez(os.path.join(path, f"sparse_{n}.npz"),
                         ids=ids, ws=ws)

    def _load(self, path: str):
        import os

        dpath = os.path.join(path, "dense.npz")
        if os.path.exists(dpath):
            with np.load(dpath) as z:
                for n in z.files:
                    if n in self.dense:
                        self.dense[n].init(z[n])
        for n, t in self.sparse.items():
            spath = os.path.join(path, f"sparse_{n}.npz")
            if os.path.exists(spath):
                with np.load(spath) as z:
                    t.import_rows(z["ids"], z["ws"])

    # ------------------------------------------------------------------
    def start(self, block: bool = False):
        handle = self._handle

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                try:
                    while True:
                        op, name, meta, arrays = _recv_msg(self.request)
                        handle(op, name, meta, arrays, self.request)
                        if op == "stop":
                            return
                except (ConnectionError, OSError):
                    return

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            # don't join handler threads on close: a handler blocked on a
            # still-open client socket would deadlock server.stop()
            daemon_threads = True
            block_on_close = False

        self._server = Server((self.host, self.port), Handler)
        if self.port == 0:
            self.port = self._server.server_address[1]
        try:
            from .table import serve_start

            self.data_port = serve_start(
                "0.0.0.0" if self.host in ("", "0.0.0.0") else self.host, 0)
            if self.data_port < 0:
                self.data_port = 0
        except Exception:
            self.data_port = 0  # no native lib: JSON path serves data too
        if block:
            self._server.serve_forever()
        else:
            self._thread = threading.Thread(
                target=self._server.serve_forever, daemon=True)
            self._thread.start()
        return self

    def stop(self):
        self._barrier_monitor.stop()
        if self.data_port > 0:
            try:
                from .table import serve_stop

                serve_stop(self.data_port)
            except Exception:
                pass
            self.data_port = 0
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None

    @property
    def endpoint(self):
        return f"{self.host}:{self.port}"


def _retry_policy():
    """(retries, deadline_s, backoff_s) from the FLAGS_rpc_* knobs."""
    from ..utils.flags import flag

    return (int(flag("rpc_retry_times") or 0),
            float(flag("rpc_deadline") or 0) / 1e3,
            float(flag("rpc_retry_backoff_ms") or 0) / 1e3)


def _backoff_sleep(attempt: int, backoff_s: float, deadline_left: float,
                   rng: random_mod.Random):
    """Bounded exponential backoff with +/-50% jitter, capped at 2 s
    and at the remaining deadline."""
    if backoff_s <= 0:
        return
    delay = min(backoff_s * (2 ** attempt), 2.0)
    delay *= 0.5 + rng.random()  # jitter in [0.5, 1.5)x
    delay = min(delay, max(deadline_left, 0.0))
    if delay > 0:
        time.sleep(delay)


class _BinaryDataClient:
    """Client for the native binary data plane (native/ps_table.cpp
    ps_serve_*; reference: grpc_client.cc).  One socket per THREAD per
    endpoint, so concurrent trainer threads do not serialize on a shared
    connection the way the JSON control path does."""

    #: binary ops safe to blind-retry: pure reads (1=pull_dense,
    #: 3=pull_sparse).  The C++ wire protocol has no idempotence-key
    #: field, so mutating ops (2/4/5/6) must NOT auto-retry — after an
    #: ambiguous failure the server may already have applied the push.
    _RETRYABLE = frozenset({1, 3})

    def __init__(self):
        self._tls = threading.local()
        self.n_rpc = 0  # completed round trips (RTT accounting)
        self.n_retries = 0
        self._n_rpc_lock = threading.Lock()
        self._rng = random_mod.Random()

    def _sock(self, host, port):
        socks = getattr(self._tls, "socks", None)
        if socks is None:
            socks = self._tls.socks = {}
        key = (host, port)
        s = socks.get(key)
        if s is None:
            s = socket.create_connection((host, port), timeout=120)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            socks[key] = s
        return s

    def _drop_sock(self, host, port, s):
        """A failed transaction leaves the stream desynced (possibly
        mid-message): the cached per-thread socket must be rebuilt, or
        every later call on this thread inherits the poison."""
        socks = getattr(self._tls, "socks", None)
        if socks is not None and socks.get((host, port)) is s:
            socks.pop((host, port), None)
        try:
            s.close()
        except OSError:
            pass

    def call(self, host, port, op, name, arr1=None, arr2=None):
        from ..utils import chaos

        retries, deadline_s, backoff_s = _retry_policy()
        if op not in self._RETRYABLE:
            retries = 0
        start = time.time()
        attempt = 0
        while True:
            try:
                return self._call_once(host, port, op, name, arr1, arr2,
                                       chaos)
            except (ConnectionError, OSError):
                left = (deadline_s - (time.time() - start)
                        if deadline_s else float("inf"))
                if attempt >= retries or left <= 0:
                    if left <= 0:
                        tm.counter(
                            "ps_rpc_deadline_exceeded_total",
                            "RPCs abandoned because FLAGS_rpc_deadline "
                            "expired").inc()
                    raise
                with self._n_rpc_lock:
                    self.n_retries += 1
                tm.counter("ps_rpc_retries_total",
                           "transport-level RPC retries",
                           labels=("plane",)).labels(plane="binary").inc()
                _backoff_sleep(attempt, backoff_s, left, self._rng)
                attempt += 1

    def _call_once(self, host, port, op, name, arr1, arr2, chaos):
        t0 = time.perf_counter()
        s = self._sock(host, port)
        nm = name.encode()
        msg = [struct.pack("<BH", op, len(nm)), nm]
        a1 = (np.ascontiguousarray(arr1) if arr1 is not None
              else np.zeros(0, np.float32))
        msg.append(struct.pack("<Q", a1.size))
        msg.append(a1.tobytes())
        if op == 4:
            a2 = np.ascontiguousarray(arr2)
            msg.append(struct.pack("<Q", a2.size))
            msg.append(a2.tobytes())
        try:
            with RecordEvent(f"rpc:bin:{op}", cat="rpc"):
                chaos.on_rpc("send", f"bin:{op}")
                s.sendall(b"".join(msg))
                chaos.on_rpc("recv", f"bin:{op}")
                status = _recv_exact(s, 1)[0]
                (n,) = struct.unpack("<Q", _recv_exact(s, 8))
                payload = _recv_exact(s, n * 4) if n else b""
        except BaseException:
            # evict on ANY mid-transaction failure, not just OSError —
            # a struct/decode error means the stream is desynced too
            self._drop_sock(host, port, s)
            raise
        if status != 0:
            raise RuntimeError(
                f"native PS error from {host}:{port} (op {op}, {name!r})")
        with self._n_rpc_lock:
            self.n_rpc += 1
        opname = f"bin:{op}"
        tm.counter("ps_rpc_total", "completed client RPC round trips",
                   labels=("op",)).labels(op=opname).inc()
        tm.histogram("ps_rpc_latency_s",
                     "client-observed RPC round-trip seconds",
                     labels=("op",)).labels(op=opname).observe(
                         time.perf_counter() - t0)
        return np.frombuffer(payload, np.float32).copy()


class PSClient:
    """Trainer-side client (reference: GrpcClient / parameter_send/recv)."""

    def __init__(self, endpoints):
        if isinstance(endpoints, str):
            endpoints = endpoints.split(",")
        self.endpoints = list(endpoints)
        self._socks: Dict[str, socket.socket] = {}
        self._lock = threading.Lock()
        self._data = _BinaryDataClient()
        self._data_ports: Dict[str, tuple] = {}
        self.n_rpc = 0  # completed JSON-path round trips
        self.n_retries = 0  # transport failures that were retried
        self._rng = random_mod.Random()
        # idempotence-key prefix: unique per client per process, so a
        # restarted trainer can never collide with its dead self's ids
        self._req_prefix = f"{uuid.uuid4().hex[:12]}.{os.getpid()}"
        self._req_n = 0

    def rpc_count(self) -> int:
        """Total completed client round trips (JSON control path +
        native data plane) — the RTT-per-step accounting of the
        wide_deep path (BASELINE metric #5).  A call that
        succeeds after N transport retries counts ONE completed round
        trip (plus N in ``retry_count()``): the metric is end-to-end
        RPCs, not wire attempts."""
        return self.n_rpc + self._data.n_rpc

    def retry_count(self) -> int:
        """Transport-level retries performed across both wire paths."""
        return self.n_retries + self._data.n_retries

    def _next_req_id(self) -> str:
        with self._lock:
            self._req_n += 1
            return f"{self._req_prefix}.{self._req_n}"

    def _data_ep(self, ep: str):
        """(host, port) of the native data plane, or None (fallback to
        the JSON path when the server has no native lib)."""
        if ep not in self._data_ports:
            try:
                meta, _ = self._call(ep, "data_port")
                port = int(meta.get("port", 0))
            except Exception:
                port = 0
            host = ep.rsplit(":", 1)[0]
            self._data_ports[ep] = (host, port) if port > 0 else None
        return self._data_ports[ep]

    def _sock(self, ep: str) -> socket.socket:
        with self._lock:
            s = self._socks.get(ep)
            if s is None:
                host, port = ep.rsplit(":", 1)
                s = socket.create_connection((host, int(port)), timeout=120)
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                self._socks[ep] = s
            return s

    def _call(self, ep, op, name="", meta=None, arrays=()):
        """One logical RPC with deadline + bounded-backoff retry
        (FLAGS_rpc_deadline / FLAGS_rpc_retry_times /
        FLAGS_rpc_retry_backoff_ms).  Only TRANSPORT failures retry —
        an "error" reply is an application answer and raises
        immediately.  Mutating ops carry a per-call idempotence key so
        a retry after a lost reply is acked by the server's deduper
        instead of double-applied; barrier ops never retry (re-entering
        a barrier would corrupt the round)."""
        meta = dict(meta or {})
        retries, deadline_s, backoff_s = _retry_policy()
        if op in _NO_RETRY_OPS:
            retries = 0
        if op in _MUTATING_OPS and "req_id" not in meta:
            meta["req_id"] = self._next_req_id()
        # trace-context propagation (r17): when the caller runs inside
        # a request trace, this logical RPC gets ONE client span (all
        # wire attempts inside it — chaos/retry annotations attach to
        # it) and the wire header carries {trace_id, span_id} next to
        # the idempotence key, so the server's span joins the same
        # trace and a dedup-acked replay can be tagged with its origin.
        tr = span = None
        cur = tracing.current() if tracing.enabled() else None
        if cur is not None:
            tr, parent = cur
            span = tr.start(f"ps:{op}", parent=parent,
                            attrs={"op": op, "ep": ep})
            meta["trace_ctx"] = {"trace_id": tr.trace_id,
                                 "span_id": span.span_id}
        start = time.time()
        attempt = 0
        while True:
            try:
                if span is not None:
                    with tracing.use_span(tr, span):
                        out = self._transact(ep, op, name, meta, arrays)
                else:
                    out = self._transact(ep, op, name, meta, arrays)
                if span is not None:
                    tr.end(span, attrs={"attempts": attempt + 1})
                return out
            except (ConnectionError, OSError):
                left = (deadline_s - (time.time() - start)
                        if deadline_s else float("inf"))
                if attempt >= retries or left <= 0:
                    if left <= 0:
                        tm.counter(
                            "ps_rpc_deadline_exceeded_total",
                            "RPCs abandoned because FLAGS_rpc_deadline "
                            "expired").inc()
                    if span is not None:
                        tr.end(span, attrs={"attempts": attempt + 1,
                                            "error": "transport"})
                    raise
                with self._lock:
                    self.n_retries += 1
                tm.counter("ps_rpc_retries_total",
                           "transport-level RPC retries",
                           labels=("plane",)).labels(plane="json").inc()
                _backoff_sleep(attempt, backoff_s, left, self._rng)
                attempt += 1
            except BaseException as e:
                if span is not None:
                    tr.end(span, attrs={"attempts": attempt + 1,
                                        "error": type(e).__name__})
                raise

    def _transact(self, ep, op, name, meta, arrays):
        """Single wire attempt.  ANY failure mid-transaction (transport
        error, garbled frame, injected chaos) evicts the cached socket:
        a stream abandoned mid-message is desynced, and keeping it
        would poison every later call on this client."""
        from ..utils import chaos

        t0 = time.perf_counter()
        s = self._sock(ep)
        try:
            with self._lock, RecordEvent(f"rpc:{op}", cat="rpc"):
                chaos.on_rpc("send", op)
                _send_msg(s, op, name, meta, arrays)
                chaos.on_rpc("recv", op)
                rop, _, rmeta, rarrays = _recv_msg(s)
        except BaseException:
            with self._lock:
                if self._socks.get(ep) is s:
                    del self._socks[ep]
            try:
                s.close()
            except OSError:
                pass
            raise
        if rop == "error":
            raise RuntimeError(f"PS error from {ep}: {rmeta}")
        with self._lock:
            self.n_rpc += 1
        tm.counter("ps_rpc_total", "completed client RPC round trips",
                   labels=("op",)).labels(op=op).inc()
        tm.histogram("ps_rpc_latency_s",
                     "client-observed RPC round-trip seconds",
                     labels=("op",)).labels(op=op).observe(
                         time.perf_counter() - t0)
        return rmeta, rarrays

    def _ep_for(self, name: str) -> str:
        # deterministic across processes (built-in hash() is salted per
        # process, which would route the same table to different servers
        # on different trainers)
        import zlib

        return self.endpoints[zlib.crc32(name.encode()) % len(self.endpoints)]

    # ------------------------------------------------------------------
    def create_dense(self, name, size, **cfg):
        self._call(self._ep_for(name), "create_dense", name,
                   {"size": int(size), **cfg})

    def create_sparse(self, name, dim, **cfg):
        self._call(self._ep_for(name), "create_sparse", name,
                   {"dim": int(dim), **cfg})

    def init_dense(self, name, values):
        ep = self._ep_for(name)
        d = self._data_ep(ep)
        v = np.asarray(values, np.float32).ravel()
        if d is not None:
            self._data.call(d[0], d[1], 5, name, v)
            return
        self._call(ep, "init_dense", name, arrays=[v])

    def pull_dense(self, name):
        ep = self._ep_for(name)
        d = self._data_ep(ep)
        if d is not None:
            return self._data.call(d[0], d[1], 1, name)
        _, arrays = self._call(ep, "pull_dense", name)
        return arrays[0]

    def record_sparse_update(self, name, ids):
        """Notify the shard's AsyncSparseParamUpdateRecorder of rows a
        native-data-plane push touched."""
        self._call(self._ep_for(name), "record_sparse_update", name,
                   arrays=[np.asarray(ids, np.int64)])

    def pull_updated_rows(self, name, trainer_id=0):
        """Drain this trainer's pending updated-row set for a sparse
        param (async_sparse_param_update_recorder.h GetAndClear)."""
        _, arrays = self._call(self._ep_for(name), "pull_updated_rows",
                               name, {"trainer_id": int(trainer_id)})
        return arrays[0]

    def push_dense(self, name, grad, sync=True):
        ep = self._ep_for(name)
        d = self._data_ep(ep)
        g = np.asarray(grad, np.float32).ravel()
        if d is not None:
            self._data.call(d[0], d[1], 2, name, g)
            return
        self._call(ep, "push_dense", name, {"sync": sync}, [g])

    def push_delta(self, name, delta):
        ep = self._ep_for(name)
        d = self._data_ep(ep)
        v = np.asarray(delta, np.float32).ravel()
        if d is not None:
            self._data.call(d[0], d[1], 6, name, v)
            return
        self._call(ep, "push_delta", name, arrays=[v])

    def pull_sparse(self, name, ids):
        ep = self._ep_for(name)
        d = self._data_ep(ep)
        ids = np.asarray(ids, np.int64).ravel()
        if d is not None and ids.size:
            # empty pulls go through the JSON path: the binary reply has
            # no dim info, and (0, 0) vs (0, dim) is a real shape
            # divergence for downstream concat/matmul
            flat = self._data.call(d[0], d[1], 3, name, ids)
            return flat.reshape(ids.size, -1)
        _, arrays = self._call(ep, "pull_sparse", name, arrays=[ids])
        return arrays[0]

    def push_sparse(self, name, ids, grads, record=False):
        """``record=True`` also notifies the shard's async sparse
        update recorder (needed on the native data plane, which
        bypasses the JSON handler that records automatically)."""
        ep = self._ep_for(name)
        d = self._data_ep(ep)
        ids = np.asarray(ids, np.int64).ravel()
        grads = np.asarray(grads, np.float32)
        if d is not None:
            self._data.call(d[0], d[1], 4, name, ids, grads)
            if record:
                self.record_sparse_update(name, ids)
            return
        self._call(ep, "push_sparse", name, arrays=[ids, grads])

    def blob_put(self, name: str, blob: bytes):
        self._call(self._ep_for(name), "blob_put", name,
                   arrays=[np.frombuffer(blob, np.uint8)])

    def blob_peek(self, name: str):
        _, arrays = self._call(self._ep_for(name), "blob_peek", name)
        return [a.tobytes() for a in arrays]

    def blob_take(self, name: str):
        _, arrays = self._call(self._ep_for(name), "blob_take", name)
        return [a.tobytes() for a in arrays]

    def barrier(self, timeout=120.0, trainer_id=-1):
        """Anonymous barrier (trainer_id=-1) keeps the legacy behavior;
        a real trainer_id routes through the BarrierMonitor and raises
        with the missing-trainer list on failure detection."""
        for ep in self.endpoints:
            self._call(ep, "barrier",
                       meta={"timeout": timeout, "trainer_id": trainer_id})

    def barrier_status(self):
        meta, _ = self._call(self.endpoints[0], "barrier_status")
        return meta

    def barrier_reset(self):
        for ep in self.endpoints:
            self._call(ep, "barrier_reset")

    def barrier_membership(self, delta):
        metas = [self._call(ep, "barrier_membership", meta={"delta": delta})[0]
                 for ep in self.endpoints]
        return metas[0]["n_trainers"]

    def heartbeat(self, trainer_id):
        for ep in self.endpoints:
            self._call(ep, "heartbeat", meta={"trainer_id": trainer_id})

    def worker_status(self):
        meta, _ = self._call(self.endpoints[0], "worker_status")
        return meta["ages"]

    def save(self, path):
        """Snapshot every pserver's tables.  Attempts ALL endpoints and
        raises one aggregate error naming each shard that failed — a
        partial checkpoint (some shards new, some old) must be loudly
        visible, never silently treated as complete."""
        errs = []
        for ep in self.endpoints:
            try:
                self._call(ep, "save", meta={"path": path})
            except Exception as e:
                errs.append((ep, e))
        if errs:
            detail = "; ".join(f"{ep}: {type(e).__name__}: {e}"
                               for ep, e in errs)
            raise RuntimeError(
                f"PS checkpoint save to {path!r} failed on "
                f"{len(errs)}/{len(self.endpoints)} shard(s) — {detail}")

    def load(self, path):
        for ep in self.endpoints:
            self._call(ep, "load", meta={"path": path})

    def shrink(self, days=0):
        for ep in self.endpoints:
            self._call(ep, "shrink", meta={"days": days})

    def stop_server(self):
        for ep in self.endpoints:
            try:
                self._call(ep, "stop")
            except Exception:
                pass

    def close(self):
        for s in self._socks.values():
            try:
                s.close()
            except OSError:
                pass
        self._socks.clear()
