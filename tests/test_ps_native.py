"""Native binary-framed PS transport (native/ps_table.cpp ps_serve_* —
the grpc_server.cc analog): data-plane routing, exactness under
4-trainer concurrency, JSON-fallback parity, and (r11) RPC
retry/backoff with idempotent replay under injected faults.
"""
import struct
import threading
import time

import numpy as np
import pytest

from paddle_tpu.distributed_ps import runtime
from paddle_tpu.distributed_ps.service import PSClient, PSServer
from paddle_tpu.utils import chaos
from paddle_tpu.utils import flags as _flags


@pytest.fixture(autouse=True)
def _chaos_off():
    saved = dict(_flags._flags)
    chaos.reset()
    yield
    _flags._flags.clear()
    _flags._flags.update(saved)
    chaos.reset()


def _arm(spec):
    _flags.set_flags({"chaos": spec, "rpc_retry_backoff_ms": 1})
    chaos.reset()


@pytest.fixture
def server():
    s = PSServer("127.0.0.1:0", n_trainers=1).start()
    yield s
    s.stop()
    runtime.clear()
    from paddle_tpu.distributed_ps.table import reset_all_tables

    reset_all_tables()


def test_native_data_plane_active(server):
    assert server.data_port > 0, "native data plane did not start"
    c = PSClient([server.endpoint])
    c.create_dense("w", 8, optimizer="sgd", lr=0.5)
    assert c._data_ep(server.endpoint) is not None
    c.init_dense("w", np.arange(8, dtype=np.float32))
    np.testing.assert_allclose(c.pull_dense("w"),
                               np.arange(8, dtype=np.float32))
    c.push_dense("w", np.ones(8, np.float32))
    np.testing.assert_allclose(c.pull_dense("w"),
                               np.arange(8, dtype=np.float32) - 0.5)
    c.close()


def test_native_sparse_roundtrip(server):
    c = PSClient([server.endpoint])
    c.create_sparse("emb", 4, optimizer="sgd", lr=1.0)
    ids = np.array([5, 9, 5], np.int64)
    rows = c.pull_sparse("emb", ids)
    assert rows.shape == (3, 4)
    np.testing.assert_allclose(rows[0], rows[2])  # same id, same row
    g = np.ones((3, 4), np.float32)
    c.push_sparse("emb", ids, g)
    rows2 = c.pull_sparse("emb", ids)
    # id 5 appears twice in the push -> two SGD steps of lr*1
    np.testing.assert_allclose(rows2[0], rows[0] - 2.0, atol=1e-6)
    np.testing.assert_allclose(rows2[1], rows[1] - 1.0, atol=1e-6)
    c.close()


def test_four_trainer_concurrent_stress(server):
    """4 trainer threads hammer the same dense + sparse tables through
    the native transport; per-push atomicity (table mutex in C++) makes
    the final dense value exact."""
    n_trainers, pushes = 4, 50
    setup = PSClient([server.endpoint])
    setup.create_dense("w", 64, optimizer="sgd", lr=0.01)
    setup.init_dense("w", np.zeros(64, np.float32))
    setup.create_sparse("emb", 8, optimizer="sgd", lr=0.01)
    errs = []

    def trainer(tid):
        try:
            c = PSClient([server.endpoint])
            rng = np.random.RandomState(tid)
            for i in range(pushes):
                c.pull_dense("w")
                c.push_dense("w", np.ones(64, np.float32))
                ids = rng.randint(0, 1000, 16).astype(np.int64)
                rows = c.pull_sparse("emb", ids)
                assert rows.shape == (16, 8)
                c.push_sparse("emb", ids, np.ones((16, 8), np.float32))
            c.close()
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=trainer, args=(t,))
               for t in range(n_trainers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs, errs
    final = setup.pull_dense("w")
    np.testing.assert_allclose(
        final, -0.01 * n_trainers * pushes * np.ones(64), atol=1e-4)
    setup.close()


def test_json_fallback_parity(server):
    """Forcing the JSON control path must produce the same numbers as
    the binary path (the wire is an implementation detail)."""
    c = PSClient([server.endpoint])
    c.create_dense("w", 6, optimizer="sgd", lr=0.1)
    c.init_dense("w", np.arange(6, dtype=np.float32))
    c.push_dense("w", np.ones(6, np.float32))
    via_native = c.pull_dense("w")
    cj = PSClient([server.endpoint])
    cj._data_ports[server.endpoint] = None  # force JSON path
    via_json = cj.pull_dense("w")
    np.testing.assert_allclose(via_native, via_json)
    c.close()
    cj.close()


def test_rpc_round_trip_counter(server):
    """rpc_count() tracks completed client round trips on BOTH wire
    paths — the RTT-per-step accounting of the wide_deep path
    (BASELINE metric #5)."""
    c = PSClient([server.endpoint])
    n0 = c.rpc_count()
    c.create_dense("w", 8, optimizer="sgd", lr=0.5)
    c.init_dense("w", np.arange(8, dtype=np.float32))
    after_setup = c.rpc_count()
    assert after_setup > n0
    c.pull_dense("w")
    c.push_dense("w", np.ones(8, np.float32))
    assert c.rpc_count() >= after_setup + 2  # one RTT per pull/push min
    # the JSON fallback path counts too
    cj = PSClient([server.endpoint])
    cj._data_ports[server.endpoint] = None
    m0 = cj.rpc_count()
    cj.pull_dense("w")
    assert cj.rpc_count() > m0
    c.close()
    cj.close()


# --------------------------------------------------------------------------
# r11: RPC retry/backoff + idempotent replay under injected faults
# --------------------------------------------------------------------------
def _json_client(server):
    c = PSClient([server.endpoint])
    c._data_ports[server.endpoint] = None  # force the JSON control path
    return c


def test_retry_idempotent_push_on_lost_reply(server):
    """The double-apply trap: the server applies a push but the REPLY
    is lost.  The retry resends with the same req_id; the server's
    RequestDeduper acks it without re-applying — the table moves by
    exactly ONE update, and rpc_count counts ONE completed call."""
    c = _json_client(server)
    c.create_dense("w", 8, optimizer="sgd", lr=1.0)
    c.init_dense("w", np.zeros(8, np.float32))
    n0, r0 = c.rpc_count(), c.retry_count()
    _arm("rpc_drop=recv@1")  # next RPC: sent, applied, reply dropped
    c.push_dense("w", np.ones(8, np.float32))
    _flags.set_flags({"chaos": ""})
    chaos.reset()
    assert c.retry_count() == r0 + 1
    assert c.rpc_count() == n0 + 1  # one logical RPC despite two attempts
    np.testing.assert_allclose(c.pull_dense("w"), -np.ones(8))
    assert len(server.dedup) >= 1
    c.close()


def test_retry_after_dropped_send_applies_once(server):
    """A request dropped BEFORE it reaches the wire never touched the
    server: the retry applies it exactly once."""
    c = _json_client(server)
    c.create_dense("w", 4, optimizer="sgd", lr=1.0)
    c.init_dense("w", np.zeros(4, np.float32))
    _arm("rpc_drop=send@1")
    c.push_dense("w", np.ones(4, np.float32))
    _flags.set_flags({"chaos": ""})
    chaos.reset()
    assert c.retry_count() == 1
    np.testing.assert_allclose(c.pull_dense("w"), -np.ones(4))
    c.close()


def test_rpc_deadline_bounds_retries(server):
    """With every attempt dropped, the call fails within the deadline
    instead of retrying forever."""
    c = _json_client(server)
    c.create_dense("w", 4, optimizer="sgd", lr=1.0)
    _flags.set_flags({"chaos": "rpc_drop=send:1.0", "rpc_deadline": 300,
                      "rpc_retry_times": 50, "rpc_retry_backoff_ms": 20})
    chaos.reset()
    t0 = time.time()
    with pytest.raises(ConnectionError):
        c.pull_dense("w")
    assert time.time() - t0 < 5.0
    c.close()


def test_barrier_never_retries(server):
    """Re-entering a barrier after a transport failure would join the
    NEXT round and corrupt membership accounting — barrier calls must
    surface the failure instead of retrying."""
    c = _json_client(server)
    _arm("rpc_drop=send@1")
    r0 = c.retry_count()
    with pytest.raises(ConnectionError):
        c.barrier(timeout=5.0)
    assert c.retry_count() == r0
    c.close()


def test_binary_plane_retry_policy(server):
    """Native data plane: pure reads (pull) retry through transport
    faults; mutating pushes have no idempotence key on the C++ wire, so
    they surface the error instead of blind-retrying — and the failed
    thread's cached socket is dropped, not left poisoned."""
    c = PSClient([server.endpoint])
    c.create_dense("w", 4, optimizer="sgd", lr=0.5)
    c.init_dense("w", np.arange(4, dtype=np.float32))
    assert c._data_ep(server.endpoint) is not None
    _arm("rpc_drop=send@1")
    np.testing.assert_allclose(c.pull_dense("w"),
                               np.arange(4, dtype=np.float32))
    assert c._data.n_retries == 1
    _arm("rpc_drop=send@1")
    with pytest.raises(ConnectionError):
        c.push_dense("w", np.ones(4, np.float32))
    socks = getattr(c._data._tls, "socks", {}) or {}
    assert not socks, "failed binary socket must be evicted"
    _flags.set_flags({"chaos": ""})
    chaos.reset()
    # the next push reconnects cleanly and applies once
    c.push_dense("w", np.ones(4, np.float32))
    np.testing.assert_allclose(c.pull_dense("w"),
                               np.arange(4, dtype=np.float32) - 0.5)
    c.close()


def test_desynced_json_socket_rebuilt(server, monkeypatch):
    """A reply that fails to PARSE (stream desync) is not an OSError —
    the old client kept that socket cached and every later call on it
    inherited the poison.  Now any mid-transaction failure evicts, and
    the next call reconnects and works."""
    import paddle_tpu.distributed_ps.service as svc

    c = _json_client(server)
    c.create_dense("w", 4, optimizer="sgd", lr=0.5)
    c.init_dense("w", np.zeros(4, np.float32))
    ep = server.endpoint
    s0 = c._socks[ep]

    real = svc._recv_msg
    state = {"fired": False}
    me = threading.current_thread()

    def garbled(sock):
        if threading.current_thread() is me and not state["fired"]:
            state["fired"] = True
            raise struct.error("garbled reply frame")
        return real(sock)

    monkeypatch.setattr(svc, "_recv_msg", garbled)
    with pytest.raises(struct.error):
        c.pull_dense("w")  # parse failure: not retryable, but evicts
    assert c._socks.get(ep) is not s0
    np.testing.assert_allclose(c.pull_dense("w"), np.zeros(4))
    c.close()


def test_dedup_replay_carries_original_trace(server):
    """r17 trace propagation, proven via the lost-reply dedup path: the
    client injects trace_ctx next to the idempotence key, the retry
    resends the SAME context, and the server's dedup-acked replay span
    is tagged with the originating trace id — one connected trace
    shows apply + replay end-to-end."""
    from paddle_tpu.utils import tracing

    _flags.set_flags({"trace_requests": 1})
    tracing.reset()
    try:
        c = _json_client(server)
        c.create_dense("w", 8, optimizer="sgd", lr=1.0)
        c.init_dense("w", np.zeros(8, np.float32))
        with tracing.start_request_trace("train_push", "push-0") as tr:
            _arm("rpc_drop=recv@1")  # sent, applied, reply dropped
            c.push_dense("w", np.ones(8, np.float32))
            _flags.set_flags({"chaos": ""})
            chaos.reset()
        # applied exactly once despite the retry
        np.testing.assert_allclose(c.pull_dense("w"), -np.ones(8))
        spans = tracing.store().get(tr.trace_id).spans
        client = [s for s in spans if s.name == "ps:push_dense"]
        srv = [s for s in spans if s.name == "ps_server:push_dense"]
        assert len(client) == 1            # ONE logical RPC span
        assert client[0].attrs["attempts"] == 2
        assert [e[0] for e in client[0].events] == ["chaos:rpc_drop"]
        assert len(srv) == 2               # original apply + replay ack
        assert all(s.parent_id == client[0].span_id for s in srv)
        replays = [s for s in srv if s.attrs.get("dedup_replay")]
        assert len(replays) == 1
        assert replays[0].attrs["origin_trace"] == tr.trace_id
        # the deduper remembers the committing trace per req_id
        assert tr.trace_id in server.dedup._origin.values()
        c.close()
    finally:
        tracing.reset()
