"""The r4 long-tail op corpus (ops/long_tail_ops.py + recv_save +
split_byref) against hand-written NumPy oracles.

Reference semantics: tree_conv_op.cc/math/tree2col.cc,
rank_attention.cu.h, batch_fc_op.cu, attention_lstm_op.cc,
fused/fused_embedding_fc_lstm_op.cc, fused/fusion_seqconv_eltadd_relu_op.cc,
fused/fusion_seqexpand_concat_fc_op.cc, pyramid_hash_op.cc,
distributed_ops/{recv_save_op.cc, split_byref_op.cc}.
"""
import numpy as np
import pytest

from paddle_tpu.ops.registry import eager_call

RNG = np.random.RandomState(7)


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


# ---------------------------------------------------------------- batch_fc
def test_batch_fc_matches_numpy():
    x = RNG.randn(3, 5, 4).astype(np.float32)
    w = RNG.randn(3, 4, 6).astype(np.float32)
    b = RNG.randn(3, 6).astype(np.float32)
    out = eager_call("batch_fc", {"Input": [x], "W": [w], "Bias": [b]},
                     {}, {"Out": 1})["Out"][0]
    ref = np.maximum(np.einsum("sbi,sio->sbo", x, w) + b[:, None, :], 0)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)


# ----------------------------------------------------------- rank_attention
def test_rank_attention_matches_kernel_semantics():
    ins, x_dim, max_rank, para_col = 4, 3, 2, 5
    x = RNG.randn(ins, x_dim).astype(np.float32)
    param = RNG.randn(max_rank * max_rank * x_dim, para_col).astype(
        np.float32)
    # rank_offset rows: [rank, r0, idx0, r1, idx1] (1-based ranks; 0 = absent)
    rank_offset = np.array([
        [1, 1, 0, 2, 1],
        [2, 1, 2, 0, 0],
        [0, 1, 3, 2, 0],   # lower < 0 -> all zero
        [2, 0, 0, 2, 3],
    ], np.int32)
    out = eager_call("rank_attention",
                     {"X": [x], "RankOffset": [rank_offset],
                      "RankParam": [param]},
                     {"MaxRank": max_rank},
                     {"Out": 1, "InputHelp": 1, "InsRank": 1})["Out"][0]
    ref = np.zeros((ins, para_col), np.float32)
    pblocks = param.reshape(max_rank * max_rank, x_dim, para_col)
    for i in range(ins):
        lower = rank_offset[i, 0] - 1
        for k in range(max_rank):
            faster = rank_offset[i, 2 * k + 1] - 1
            if lower < 0 or faster < 0:
                continue
            idx = rank_offset[i, 2 * k + 2]
            ref[i] += x[idx] @ pblocks[lower * max_rank + faster]
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)


# ------------------------------------------------------------- tree_conv
def test_tree_conv_matches_tbcnn_oracle():
    fs, out_sz, nf, max_depth = 3, 2, 2, 2
    # tree: 1 -> (2, 3); sentinel row ends the edge list
    edges = np.array([[1, 2], [1, 3], [0, 0]], np.int32)
    nodes = RNG.randn(4, fs).astype(np.float32)   # node ids are 1-based
    filt = RNG.randn(fs, 3, out_sz, nf).astype(np.float32)
    out = eager_call("tree_conv",
                     {"NodesVector": [nodes], "EdgeSet": [edges],
                      "Filter": [filt]},
                     {"max_depth": max_depth}, {"Out": 1})["Out"][0]
    out = np.asarray(out)

    def eta(idx, pclen, depth):
        et = (max_depth - depth) / max_depth
        frac = 0.5 if pclen == 1 else (idx - 1.0) / (pclen - 1.0)
        el = (1.0 - et) * frac
        er = (1.0 - et) * (1.0 - frac)
        return el, er, et

    w = filt.reshape(fs * 3, out_sz * nf)

    def conv(patch):
        pm = np.zeros(fs * 3, np.float32)
        for nid, idx, pclen, depth in patch:
            el, er, et = eta(idx, pclen, depth)
            f = nodes[nid - 1]
            pm[0::3] += el * f
            pm[1::3] += er * f
            pm[2::3] += et * f
        return (pm @ w).reshape(out_sz, nf)

    # max_depth=2: each patch holds root + its children at depth 1
    ref1 = conv([(1, 1, 1, 0), (2, 1, 2, 1), (3, 2, 2, 1)])
    ref2 = conv([(2, 1, 1, 0)])
    ref3 = conv([(3, 1, 1, 0)])
    np.testing.assert_allclose(out[0], ref1, atol=1e-5)
    np.testing.assert_allclose(out[1], ref2, atol=1e-5)
    np.testing.assert_allclose(out[2], ref3, atol=1e-5)


# ------------------------------------------------------------ var_conv_2d
def test_var_conv_2d_valid_region():
    N, C, H, W = 2, 1, 6, 6
    out_ch, kh, kw = 2, 3, 3
    x = RNG.randn(N, C, H, W).astype(np.float32)
    w = RNG.randn(out_ch, C * kh * kw).astype(np.float32)
    rows = np.array([6, 4], np.int64)
    cols = np.array([6, 3], np.int64)
    out = eager_call("var_conv_2d",
                     {"X": [x], "W": [w], "ROW": [rows], "COLUMN": [cols]},
                     {"InputChannel": C, "OutputChannel": out_ch,
                      "KernelH": kh, "KernelW": kw,
                      "StrideH": 1, "StrideW": 1},
                     {"Out": 1, "Col": 1})["Out"][0]
    out = np.asarray(out)
    assert out.shape == (N, out_ch, H, W)
    # sample 1: valid region 4x3; outside must be exactly zero
    assert np.all(out[1, :, 4:, :] == 0) and np.all(out[1, :, :, 3:] == 0)
    # sample 0 full-size: matches a plain SAME conv
    import jax.numpy as jnp
    from jax import lax

    dn = lax.conv_dimension_numbers((1, C, H, W), (out_ch, C, kh, kw),
                                    ("NCHW", "OIHW", "NCHW"))
    ref = np.asarray(lax.conv_general_dilated(
        jnp.asarray(x[:1]), jnp.asarray(w.reshape(out_ch, C, kh, kw)),
        (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn))[0]
    np.testing.assert_allclose(out[0], ref, atol=1e-4)


# ---------------------------------------------------------- attention_lstm
def test_attention_lstm_matches_numpy_loop():
    N, T, M, D = 2, 4, 3, 2
    x = RNG.randn(N, T, M).astype(np.float32)
    length = np.array([4, 2], np.int64)
    c0 = RNG.randn(N, D).astype(np.float32)
    h0 = RNG.randn(N, D).astype(np.float32)
    aw = RNG.randn(M + D, 1).astype(np.float32)
    ab = RNG.randn(1).astype(np.float32)
    lw = RNG.randn(D + M, 4 * D).astype(np.float32)
    lb = RNG.randn(1, 4 * D).astype(np.float32)
    outs = eager_call(
        "attention_lstm",
        {"X": [x], "Length": [length], "C0": [c0], "H0": [h0],
         "AttentionWeight": [aw], "AttentionBias": [ab],
         "LSTMWeight": [lw], "LSTMBias": [lb]},
        {}, {"Hidden": 1, "Cell": 1, "AttentionedX": 1,
             "AttentionFCOut": 1, "LSTMX": 1, "LSTMOUT": 1})
    hidden = np.asarray(outs["Hidden"][0])

    for b in range(N):
        h, c = h0[b], c0[b]
        for t in range(int(length[b])):
            L = int(length[b])
            fc = x[b, :L] @ aw[:M, 0] + ab[0] + c @ aw[M:, 0]
            fc = np.maximum(fc, 0)
            e = np.exp(fc - fc.max())
            probs = e / e.sum()
            lstm_x = probs @ x[b, :L]
            g = lstm_x @ lw[D:] + h @ lw[:D] + lb[0]
            f = _sigmoid(g[:D])
            i = _sigmoid(g[D:2 * D])
            o = _sigmoid(g[2 * D:3 * D])
            cand = np.tanh(g[3 * D:])
            c = f * c + i * cand
            h = o * np.tanh(c)
            np.testing.assert_allclose(hidden[b, t], h, atol=1e-4,
                                       err_msg=f"b={b} t={t}")


# --------------------------------------------------- fused_embedding_fc_lstm
@pytest.mark.parametrize("peephole", [False, True])
def test_fused_embedding_fc_lstm(peephole):
    N, T, D, vocab = 2, 3, 2, 11
    ids = RNG.randint(0, vocab, (N, T)).astype(np.int64)
    length = np.array([3, 2], np.int64)
    emb = RNG.randn(vocab, 4 * D).astype(np.float32)
    wh = RNG.randn(D, 4 * D).astype(np.float32)
    bias = RNG.randn(1, 4 * D + (3 * D if peephole else 0)).astype(
        np.float32)
    outs = eager_call(
        "fused_embedding_fc_lstm",
        {"Ids": [ids], "Length": [length], "Embeddings": [emb],
         "WeightH": [wh], "Bias": [bias]},
        {"use_peepholes": peephole},
        {"Hidden": 1, "Cell": 1, "XX": 1})
    hidden = np.asarray(outs["Hidden"][0])
    b4 = bias[0, :4 * D]
    wc = bias[0, 4 * D:] if peephole else None
    for b in range(N):
        h = np.zeros(D, np.float32)
        c = np.zeros(D, np.float32)
        for t in range(int(length[b])):
            g = emb[ids[b, t]] + b4 + h @ wh
            gc, gi, gf, go = g[:D], g[D:2 * D], g[2 * D:3 * D], g[3 * D:]
            if peephole:
                gi = gi + wc[:D] * c
                gf = gf + wc[D:2 * D] * c
            c = _sigmoid(gf) * c + _sigmoid(gi) * np.tanh(gc)
            if peephole:
                go = go + wc[2 * D:] * c
            h = _sigmoid(go) * np.tanh(c)
            np.testing.assert_allclose(hidden[b, t], h, atol=1e-4,
                                       err_msg=f"b={b} t={t}")


# ------------------------------------------------- fusion_seqconv_eltadd_relu
def test_fusion_seqconv_eltadd_relu():
    N, T, M, ctx_len, out_dim = 2, 5, 3, 3, 4
    ctx_start = -1
    x = RNG.randn(N, T, M).astype(np.float32)
    length = np.array([5, 3], np.int64)
    w = RNG.randn(ctx_len * M, out_dim).astype(np.float32)
    b = RNG.randn(out_dim).astype(np.float32)
    out = eager_call("fusion_seqconv_eltadd_relu",
                     {"X": [x], "Length": [length], "Filter": [w],
                      "Bias": [b]},
                     {"contextLength": ctx_len, "contextStart": ctx_start},
                     {"Out": 1, "ColMat": 1})["Out"][0]
    out = np.asarray(out)
    for bi in range(N):
        L = int(length[bi])
        for t in range(L):
            col = np.zeros(ctx_len * M, np.float32)
            for j in range(ctx_len):
                src = t + ctx_start + j
                if 0 <= src < L:
                    col[j * M:(j + 1) * M] = x[bi, src]
            ref = np.maximum(col @ w + b, 0)
            np.testing.assert_allclose(out[bi, t], ref, atol=1e-4,
                                       err_msg=f"b={bi} t={t}")
        assert np.all(out[bi, L:] == 0)


# ----------------------------------------------- fusion_seqexpand_concat_fc
def test_fusion_seqexpand_concat_fc():
    N, T, D0, D1, out_dim = 2, 4, 3, 2, 5
    ref_seq = RNG.randn(N, T, D0).astype(np.float32)
    length = np.array([4, 2], np.int64)
    other = RNG.randn(N, D1).astype(np.float32)
    w = RNG.randn(D0 + D1, out_dim).astype(np.float32)
    b = RNG.randn(out_dim).astype(np.float32)
    out = eager_call(
        "fusion_seqexpand_concat_fc",
        {"X": [ref_seq, other],
         "Length": [length], "FCWeight": [w], "FCBias": [b]},
        {"fc_activation": "relu"}, {"Out": 1})["Out"][0]
    out = np.asarray(out)
    for bi in range(N):
        L = int(length[bi])
        for t in range(L):
            cat = np.concatenate([ref_seq[bi, t], other[bi]])
            np.testing.assert_allclose(out[bi, t],
                                       np.maximum(cat @ w + b, 0),
                                       atol=1e-4)
        assert np.all(out[bi, L:] == 0)


# -------------------------------------------------------------- pyramid_hash
def test_pyramid_hash_shapes_and_determinism():
    N, T, space, emb_dim, rand_len = 2, 5, 97, 8, 2
    x = RNG.randint(1, 1000, (N, T)).astype(np.int32)
    length = np.array([5, 3], np.int64)
    w = RNG.randn(space, rand_len).astype(np.float32)
    attrs = {"num_emb": emb_dim, "rand_len": rand_len,
             "max_pyramid_layer": 3}
    o1 = eager_call("pyramid_hash",
                    {"X": [x], "Length": [length], "W": [w]}, attrs,
                    {"Out": 1, "OutLength": 1, "X_Temp_Out": 1,
                     "DropPos": 1})
    o2 = eager_call("pyramid_hash",
                    {"X": [x], "Length": [length], "W": [w]}, attrs,
                    {"Out": 1, "OutLength": 1, "X_Temp_Out": 1,
                     "DropPos": 1})
    out1, len1 = np.asarray(o1["Out"][0]), np.asarray(o1["OutLength"][0])
    np.testing.assert_array_equal(out1, np.asarray(o2["Out"][0]))
    # pyramid of window sizes 2..3: sample0 (len 5) has 4+3 windows,
    # sample1 (len 3) has 2+1
    assert list(len1) == [7, 3]
    assert out1.shape == (N, T * 2, emb_dim)
    assert np.all(out1[0, 7:] == 0) and np.all(out1[1, 3:] == 0)
    # every emitted embedding row is built from W rows
    assert np.all(np.isfinite(out1))


# ----------------------------------------------------- split_byref / recv_save
def test_split_byref_sections():
    x = RNG.randn(10, 4).astype(np.float32)
    outs = eager_call("split_byref", {"X": [x]}, {"sections": [3, 3, 4]},
                      {"Out": 3})["Out"]
    np.testing.assert_array_equal(np.asarray(outs[0]), x[:3])
    np.testing.assert_array_equal(np.asarray(outs[1]), x[3:6])
    np.testing.assert_array_equal(np.asarray(outs[2]), x[6:])


def test_recv_save_pulls_and_writes(tmp_path):
    from paddle_tpu.distributed_ps import runtime
    from paddle_tpu.distributed_ps.service import PSClient, PSServer

    server = PSServer("127.0.0.1:0", n_trainers=1).start()
    try:
        client = PSClient([server.endpoint])
        w = RNG.randn(6, 4).astype(np.float32)
        client.create_dense("w_part0", w[:3].size, optimizer="sgd", lr=0.1)
        client.create_dense("w_part1", w[3:].size, optimizer="sgd", lr=0.1)
        client.init_dense("w_part0", w[:3])
        client.init_dense("w_part1", w[3:])
        runtime.set_client(client)
        path = str(tmp_path / "w_saved")
        eager_call("recv_save", {}, {
            "file_path": path, "shape": [6, 4],
            "slice_varnames": ["w_part0", "w_part1"],
            "remote_varnames": ["w_part0", "w_part1"],
            "is_sparse": False}, {})
        got = np.load(path + ".npy")
        np.testing.assert_allclose(got, w, atol=1e-6)
    finally:
        server.stop()
        runtime.clear()


# ------------------------------------------- async sparse update recorder
def test_async_sparse_update_recorder():
    """reference: async_sparse_param_update_recorder.h — pushes record
    rows for every trainer; each trainer drains its own set once."""
    import numpy as np

    from paddle_tpu.distributed_ps.service import PSClient, PSServer

    server = PSServer("127.0.0.1:0", n_trainers=2).start()
    try:
        client = PSClient([server.endpoint])
        client.create_sparse("emb", 4, optimizer="sgd", lr=0.5)
        client.push_sparse("emb", np.array([3, 7], np.int64),
                           np.ones((2, 4), np.float32), record=True)
        client.push_sparse("emb", np.array([7, 9], np.int64),
                           np.ones((2, 4), np.float32), record=True)
        r0 = client.pull_updated_rows("emb", trainer_id=0)
        assert sorted(r0.tolist()) == [3, 7, 9]
        # drained: second pull is empty
        assert client.pull_updated_rows("emb", trainer_id=0).size == 0
        # trainer 1 still has its own pending copy
        r1 = client.pull_updated_rows("emb", trainer_id=1)
        assert sorted(r1.tolist()) == [3, 7, 9]
    finally:
        server.stop()


# ----------------------------------------------------------------- cpu_info
def test_cpu_info_helpers():
    from paddle_tpu.utils import cpu_info

    assert cpu_info.cpu_count() >= 1
    total = cpu_info.cpu_total_physical_memory()
    assert total > (1 << 28)
    assert 0 < cpu_info.cpu_max_alloc_size() <= total
    assert cpu_info.cpu_min_chunk_size() == 4096
    assert 0 < cpu_info.cpu_max_chunk_size() <= cpu_info.cpu_max_alloc_size()
    assert cpu_info.device_count() >= 1
    info = cpu_info.device_info()
    assert info and {"id", "kind", "platform"} <= set(info[0])


# ----------------------------------------------------------------- launch_ps
def test_launch_ps_spawns_role_env(tmp_path):
    """launch_ps wires the PADDLE_* PS env protocol into server and
    trainer process sets (reference: distributed/launch_ps.py)."""
    import json
    import sys

    from paddle_tpu.distributed.launch_ps import _parse_args, start_procs

    script = tmp_path / "probe.py"
    # start_procs reaps the servers when the last trainer exits: a trainer
    # stays until both servers have written their line (a loaded host
    # starts them late, and a reaped server leaves an empty log)
    script.write_text(
        "import json, os, sys, time\n"
        "print(json.dumps({k: os.environ.get(k) for k in ("
        "'TRAINING_ROLE', 'PADDLE_TRAINER_ID', 'PADDLE_PORT',"
        "'PADDLE_PSERVERS_IP_PORT_LIST', 'PADDLE_TRAINERS_NUM')}))\n"
        "logs = os.path.join(os.path.dirname(sys.argv[0]), 'logs')\n"
        "end = time.time() + 60\n"
        "while os.environ['TRAINING_ROLE'] == 'TRAINER' and time.time() < end"
        " and not all(os.path.exists(p) and os.path.getsize(p) for p in ("
        "os.path.join(logs, f'serverlog.{i}') for i in range(2))):\n"
        "    time.sleep(0.05)\n")
    args = _parse_args([
        "--server_num", "2", "--worker_num", "2",
        "--start_port", "16170",
        "--log_dir", str(tmp_path / "logs"), str(script)])
    rc = start_procs(args, wait=True)
    assert rc == 0
    logs = sorted((tmp_path / "logs").iterdir())
    assert {p.name for p in logs} == {
        "serverlog.0", "serverlog.1", "workerlog.0", "workerlog.1"}
    srv = json.loads((tmp_path / "logs" / "serverlog.1").read_text())
    assert srv["TRAINING_ROLE"] == "PSERVER"
    assert srv["PADDLE_PORT"] == "16171"
    assert srv["PADDLE_TRAINERS_NUM"] == "2"
    wrk = json.loads((tmp_path / "logs" / "workerlog.1").read_text())
    assert wrk["TRAINING_ROLE"] == "TRAINER"
    assert wrk["PADDLE_TRAINER_ID"] == "1"
    assert wrk["PADDLE_PSERVERS_IP_PORT_LIST"] == \
        "127.0.0.1:16170,127.0.0.1:16171"
