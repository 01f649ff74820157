"""Attention-probs dropout inside the Pallas flash kernel (the
reference's fused-attention dropout capability — multihead_matmul +
probs dropout — without storing the mask: backward regenerates it from
the saved per-step seed).

CPU runs exercise the reference fallback + the op/grad plumbing; the
kernel-level checks (determinism, mask coordination, grad parity) need a
real TPU and are skipped elsewhere — tools/validate_flash_dropout.py is
the on-device harness.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas_kernels import attention_reference, flash_attention

ON_TPU = jax.default_backend() == "tpu"


def _qkv(s=256, b=2, h=2, d=32, scale=0.5):
    rng = np.random.RandomState(0)
    return [jnp.asarray(rng.randn(b, h, s, d).astype(np.float32) * scale)
            for _ in range(3)]


def test_reference_dropout_statistics():
    q, k, v = _qkv()
    base = attention_reference(q, k, v, scale=1.0)
    outs = [attention_reference(q, k, v, scale=1.0, dropout_rate=0.2,
                                dropout_seed=jnp.asarray([float(i)]))
            for i in range(32)]
    mean = jnp.mean(jnp.stack(outs), 0)
    rel = float(jnp.linalg.norm(mean - base) / jnp.linalg.norm(base))
    assert rel < 0.15, rel
    # different seeds genuinely differ
    assert float(jnp.max(jnp.abs(outs[0] - outs[1]))) > 0


def test_reference_dropout_grads_flow():
    q, k, v = _qkv(s=64)
    seed = jnp.asarray([3.0])

    def loss(q_, k_, v_):
        o = attention_reference(q_, k_, v_, scale=1.0, dropout_rate=0.2,
                                dropout_seed=seed)
        return jnp.sum(o * o)

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in (gq, gk, gv):
        assert float(jnp.sum(jnp.abs(g))) > 0


def test_fused_op_dropout_trains_dygraph():
    """End to end: BERT-tiny with attention dropout ON takes the fused
    path and trains (on CPU this is the reference fallback; on TPU the
    Pallas kernel)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.dygraph import guard, jit_train_step
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    cfg = BertConfig(vocab_size=200, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=2, intermediate_size=64,
                     max_position_embeddings=32,
                     attention_probs_dropout_prob=0.1)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 200, (2, 16)).astype(np.int64)
    labels = rng.randint(0, 200, (2, 16)).astype(np.int64)
    with guard():
        model = BertForPretraining(cfg)
        opt = fluid.optimizer.AdamOptimizer(
            2e-3, parameter_list=model.parameters())
        step = jit_train_step(model, opt, lambda m, i, l: m(i, l))
        losses = [float(np.asarray(step(ids, labels).value()))
                  for _ in range(5)]
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


def test_static_graph_fused_dropout_seed_saved():
    """The Seed output is produced and wired into the grad op (static
    path), so backward sees the same masks as forward."""
    import paddle_tpu as pt
    import paddle_tpu.layers as L
    from paddle_tpu.framework.core import Program, program_guard
    from paddle_tpu.framework.scope import Scope, scope_guard

    main, startup = Program(), Program()
    main.random_seed = 5
    with program_guard(main, startup):
        q = L.data("q", [2, 32, 16])
        k = L.data("k", [2, 32, 16])
        vp = L.create_parameter([2, 2, 32, 16], "float32", name="v_param")
        out = L.fused_multihead_attention(q, k, vp, dropout_rate=0.2)
        loss = L.reduce_mean(out)
        from paddle_tpu.backward import append_backward

        append_backward(loss)
    ops = {o.type: o for o in main.global_block().ops}
    fwd = ops["fused_multihead_attention"]
    gop = ops["fused_multihead_attention_grad"]
    assert fwd.outputs.get("Seed"), "Seed output missing"
    assert gop.inputs.get("Seed") == fwd.outputs["Seed"]
    # executes + produces grads
    rng = np.random.RandomState(1)
    feed = {n: rng.randn(2, 2, 32, 16).astype(np.float32)
            for n in ("q", "k")}
    exe = pt.Executor(pt.CPUPlace())
    with scope_guard(Scope()):
        exe.run(startup)
        outs = exe.run(main, feed=feed,
                       fetch_list=[loss.name, "v_param@GRAD"])
    assert np.isfinite(np.asarray(outs[0])).all()
    assert float(np.abs(np.asarray(outs[1])).sum()) > 0


@pytest.mark.skipif(not ON_TPU, reason="Pallas kernel needs a TPU")
def test_kernel_dropout_determinism_and_stats():
    q, k, v = _qkv(s=512, d=64)
    seed = jnp.asarray([7.0], jnp.float32)
    f = jax.jit(lambda sd: flash_attention(q, k, v, dropout_rate=0.1,
                                           dropout_seed=sd))
    o1, o2 = f(seed), f(seed)
    assert float(jnp.max(jnp.abs(o1 - o2))) == 0.0
    o3 = f(jnp.asarray([8.0], jnp.float32))
    assert float(jnp.max(jnp.abs(o1 - o3))) > 0


def test_fused_vs_split_backward_same_grads(monkeypatch):
    """The fused single-block backward and the split dq/dkv kernels must
    regenerate the SAME dropout masks and produce identical grads (r4:
    the fused path is auto-engaged at nq == nk == 1)."""
    q, k, v = _qkv(s=256, d=32)
    seed = jnp.asarray([11.0], jnp.float32)

    def grads():
        def loss(q, k, v):
            o = flash_attention(q, k, v, dropout_rate=0.1,
                                dropout_seed=seed)
            return jnp.sum(o.astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    monkeypatch.setenv("PT_FLASH_FUSED_BWD", "1")
    g_fused = grads()
    monkeypatch.setenv("PT_FLASH_FUSED_BWD", "0")
    g_split = grads()
    for name, a, b in zip("qkv", g_fused, g_split):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
