"""The main path's Pallas kernels, compiled for a described TPU v5e.

No chip is attached under tier-1, but the chip's compiler is installed:
``jax.experimental.topologies`` describes a ``v5e:2x2`` slice and
``jit(...).lower(shapes).compile()`` raises what the real chip would
raise (block shapes Mosaic refuses, primitives it cannot lower, VMEM /
SMEM overruns).  Interpret mode sees none of that, so these compiles
guard every kernel ``chip_smoke.py`` reaches, at the smoke's widths.
Nothing runs — numerics stay with the interpret-mode tests.
"""
import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.utils.cost_model import FUSABLE_ACTS


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip's sharding; the persistent compile cache
    is off around the module (an entry compiled for a described device
    cannot be read back without one — the next run would only warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.framework.place import host_tpu_chips

    if host_tpu_chips():
        # describing a topology loads libtpu into THIS process for good,
        # and libtpu serves one process: every later child that needs
        # the chip (the native-runtime tests) would be locked out
        pytest.skip("a TPU host: ask the chip itself (chip_smoke.py)")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / no topology support here
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    """Lower+compile ``fn`` for the described chip; returns the
    compiled program's text (the kernel shows as ``tpu_custom_call``)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# --- the smoke's widths ----------------------------------------------------
BERT = (38, 12, 512, 64)          # ERNIE/BERT-base b38 s512
DEC_SEQS, DEC_HEADS, DEC_D = 8, 12, 64     # GPT-2-small decode batch
PAGE, POOL_PAGES, TABLE_W = 16, 4096, 64   # 64 pages = max_seq_len 1024
FFN = (19456, 768, 3072)          # 38*512 rows through the BERT FFN
# NHWC activations the layout pass hands the epilogue kernels at b128
RESNET_STAGES = [(128, 56, 56, 64), (128, 7, 7, 2048)]


def _flash_case(causal, shape, dropout, multi_block):
    b, h, s, d = shape
    blk = 512
    assert (s // blk > 1) == multi_block

    def f(q, k, v, bias, seed, do):
        o, lse = pk._flash_fwd(q, k, v, bias, d ** -0.5, causal, blk, blk,
                               dropout, seed)
        return pk._flash_bwd(q, k, v, bias, o, lse, do, d ** -0.5, causal,
                             blk, blk, dropout, seed)

    bf = jnp.bfloat16
    return f, [(shape, bf)] * 3 + [((b, s), jnp.float32),
                                   ((1,), jnp.float32), (shape, bf)]


def _stored(head_dim, page_size=PAGE, kv_dtype="float32"):
    """The shape the engine stores a pool of that geometry in."""
    from paddle_tpu.inference.kv_cache import KVCacheConfig

    return KVCacheConfig(POOL_PAGES, page_size, DEC_HEADS, head_dim,
                         dtype=jnp.dtype(kv_dtype).name).pool_shape()


def _paged_case(kv_dtype):
    quant = kv_dtype == jnp.int8

    def f(q, kp, vp, bt, cl, *scales):
        ks, vs = scales if quant else (None, None)
        return pk._paged_decode_call(q, kp, vp, bt, cl, DEC_D ** -0.5,
                                     k_scale=ks, v_scale=vs)

    pool = (_stored(DEC_D, kv_dtype=kv_dtype), kv_dtype)
    shapes = [((DEC_SEQS, DEC_HEADS, DEC_D), jnp.float32), pool, pool,
              ((DEC_SEQS, TABLE_W), jnp.int32), ((DEC_SEQS,), jnp.int32)]
    if quant:
        shapes += [((DEC_HEADS, POOL_PAGES), jnp.float32)] * 2
    return f, shapes


def _append_case(kv_dtype, tokens, head_dim=DEC_D, page_size=PAGE):
    """K and V of one layer in one call, at a decode batch's and at a
    prefill bucket's slot count, on the pool as the engine stores it:
    head_dim 64 lane-full (two tokens a row), 128 as it is, and head_dim
    64 with pages of 8 (half a tile: not stored lane-full) in the kernel's
    page-minor view."""
    def f(kp, vp, k, v, slots):
        return pk.kv_append((kp, vp), (k, v), slots)

    pool = (_stored(head_dim, page_size, kv_dtype), kv_dtype)
    rows = ((tokens, DEC_HEADS, head_dim), kv_dtype)
    return f, [pool, pool, rows, rows, ((tokens,), jnp.int32)]


def _bn_fwd_case(shape, residual):
    c = shape[-1]
    bf = jnp.bfloat16

    def f(x, a, b, *z):
        out = pk.bn_act_apply(x, a, b, z[0] if z else None, act="relu",
                              c_axis=3)
        assert out is not None, "bn_act_apply gave way to the jnp path"
        return out

    return f, [(shape, bf), ((c,), bf), ((c,), bf)] + \
        ([(shape, bf)] if residual else [])


def _bn_bwd_case(shape, want_g):
    c = shape[-1]
    bf = jnp.bfloat16

    def f(y, dy, x, cg, mean, cx, c0):
        out = pk.bn_act_bwd_apply(y, dy, x, cg, mean, cx, c0, act="relu",
                                  c_axis=3, want_g=want_g)
        assert out is not None, "bn_act_bwd_apply gave way to the jnp path"
        return out[0] if not want_g else out

    return f, [(shape, bf)] * 3 + [((c,), bf), ((c,), bf), ((c,), bf),
                                   ((c,), jnp.float32)]


def _matmul_case(act):
    m, k, n = FFN
    bf = jnp.bfloat16

    def f(x, w, b):
        out = pk.matmul_bias_act(x, w, b, act=act)
        assert out is not None, "matmul_bias_act gave way to the jnp path"
        return out

    return f, [((m, k), bf), ((k, n), bf), ((n,), bf)]


CASES = {
    "flash-bert-dropout": functools.partial(
        _flash_case, False, BERT, 0.1, False),
    "flash-causal-s1024-multiblock": functools.partial(
        _flash_case, True, (4, 12, 1024, 64), 0.0, True),
    "flash-s1024-dropout-multiblock": functools.partial(
        _flash_case, False, (4, 12, 1024, 64), 0.1, True),
    "paged-f32": functools.partial(_paged_case, jnp.float32),
    "paged-bf16": functools.partial(_paged_case, jnp.bfloat16),
    "paged-int8": functools.partial(_paged_case, jnp.int8),
    **{f"kv_append-{jnp.dtype(t).name}-{n}-d{d}":
       functools.partial(_append_case, t, n, d)
       for t in (jnp.float32, jnp.bfloat16, jnp.int8) for n in (64, 1024)
       for d in (DEC_D, 128)},
    **{f"kv_append-{jnp.dtype(t).name}-64-d{DEC_D}-page8":
       functools.partial(_append_case, t, 64, DEC_D, 8)
       for t in (jnp.float32, jnp.bfloat16, jnp.int8)},
    **{f"bn_act-fwd-{'x'.join(map(str, s))}{'-res' if r else ''}":
       functools.partial(_bn_fwd_case, s, r)
       for s in RESNET_STAGES for r in (False, True)},
    **{f"bn_act-bwd-{'x'.join(map(str, s))}{'-g' if g else ''}":
       functools.partial(_bn_bwd_case, s, g)
       for s in RESNET_STAGES for g in (False, True)},
    **{f"matmul_bias_act-{a or 'none'}": functools.partial(_matmul_case, a)
       for a in ("",) + FUSABLE_ACTS},        # "" = bias only
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(name, v5e, monkeypatch):
    # default_backend() is "cpu" here, so the engage checks would pick
    # the jnp path: steer them from the test, never through an option
    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    fn, shapes = CASES[name]()
    text = _compile(fn, v5e, *shapes)
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


# --- mla_decode at JoyAI-LLM-Flash's widths: (rows, tables, table width) ----
MLA_POOL = (1, 24576, 16, 640)    # the cell's latent pool, bfloat16


@pytest.mark.parametrize("rows,tables,width", [
    (128, 128, 512),        # the cell's decode step
    (128, 32, 512),         # a verify call, four rows a table
    (4, 4, 32),             # the smoke's decode step
])
def test_mla_decode_compiles_for_v5e(rows, tables, width, v5e):
    """The work-list kernel as the chip's compiler takes it, its grid as
    long as a value on the device says: one Mosaic call, no branch."""
    from paddle_tpu.ops import mla_kernels as mk

    def f(q_lat, q_rope, pool, bt, cl):
        return mk._mla_decode_call(q_lat, q_rope, pool, bt, cl, scale=0.1,
                                   step=mk.DECODE_PAGES_PER_STEP,
                                   fetch=mk.DECODE_PAGES_PER_FETCH)

    text = _compile(f, v5e, ((rows, 32, 512), jnp.float32),
                    ((rows, 32, 64), jnp.float32), (MLA_POOL, jnp.bfloat16),
                    ((tables, width), jnp.int32), ((rows,), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert " conditional(" not in text


# --- moe_gmm at the two expert cells' shapes: (rows, experts, hidden, f) -----
@pytest.mark.parametrize("name,rows,experts,hidden,f,want", [
    ("joyai-prefill-4096", 32768, 256, 2048, 768, (128, None, True)),
    ("joyai-prefill-2048", 16384, 256, 2048, 768, (128, None, True)),
    ("kimi-prefill-8192", 65536, 64, 2304, 1024, (256, None, True)),
    ("kimi-prefill-4096", 32768, 64, 2304, 1024, (256, None, True)),
    # decode: JoyAI's 126 rows x 8 keep the small tiles and the pipeline's
    # fetches; Kimi's 1,024 rows over 64 held experts are 16 an expert by
    # their shape (4 owned) and take the tile of 128, as before PR 38
    ("joyai-decode", 1024, 256, 2048, 768, (32, 384, False)),
    ("kimi-decode", 1024, 64, 2304, 1024, (128, None, True)),
    # LongCat's 1/32 share: 16 experts whose matrices (2 x 6144 x 2048) are
    # too large for two experts' worth of VMEM keep column blocks and the
    # pipeline's fetch, at a prompt's 2,048 x 12 rows and a step's 128 x 12
    ("longcat-prefill-2048", 24576, 16, 6144, 2048, (256, 512, False)),
    ("longcat-decode", 1536, 16, 6144, 2048, (128, 512, False)),
])
def test_moe_gmm_compiles_for_v5e(name, rows, experts, hidden, f, want, v5e):
    """A layer's two calls (gated: bfloat16 out; down: float32 out) as the
    chip's compiler takes them with the blocks ``gmm_schedule`` chooses
    from the shapes: at prefill sizes one grid step a visit at the whole
    output width, two experts' matrices in VMEM (19 MB of Kimi's gated
    call, asked of the compiler and not reckoned), the grid as long as a
    value on the device says; one Mosaic call each."""
    from paddle_tpu.ops import mla_kernels as mk

    tm, tn, ahead = want
    assert mk.gmm_schedule(rows, experts, hidden, f, 2) == \
        (tm, tn or f, ahead)
    assert mk.gmm_schedule(rows, experts, f, hidden, 1)[::2] == (tm, ahead)
    for gated, k, n, out in ((True, hidden, f, jnp.bfloat16),
                             (False, f, hidden, jnp.float32)):
        def call(x, sizes, *ws):
            return mk._moe_gmm_call(x, ws, sizes, gated=gated,
                                    out_dtype=jnp.dtype(out))

        text = _compile(call, v5e, ((rows, k), jnp.bfloat16),
                        ((experts,), jnp.int32),
                        *[((experts, k, n), jnp.bfloat16)] * (1 + gated))
        assert text.count('custom_call_target="tpu_custom_call"') == 1


# --- the kernels that move the owned rows in and out of moe_gmm ---------------
@pytest.mark.parametrize("name,tokens,experts,hidden,f", [
    ("kimi-prefill-8192", 8192, 64, 2304, 1024),    # 65,536 rows, 16 k owned
    ("kimi-prefill-512", 512, 64, 2304, 1024),
    ("laguna-prefill-4096", 4096, 64, 2048, 512),
    ("joyai-prefill-4096", 4096, 256, 2048, 768),   # every row owned
    ("joyai-prefill-1024", 1024, 256, 2048, 768),
])
def test_moe_rows_kernels_compile_for_v5e(name, tokens, experts, hidden, f,
                                          v5e):
    """``moe_rows_in``, the down call with its rows apart and
    ``moe_combine`` at the three expert cells' shapes (8 choices a token):
    the chip's compiler takes a copy of ONE row out of ``(rows, 1, h)``
    (it refuses one out of ``(rows, h)``, which lies in tiles of eight),
    lists of ``rows`` entries in SMEM (256 KB at Kimi's largest bucket) and
    two buffers of a step's rows in VMEM; one Mosaic call each, and every
    one of these shapes is a call the kernels take."""
    from paddle_tpu.ops import mla_kernels as mk

    k = 8
    rows = tokens * k
    assert rows >= 16 * experts                 # what ``moe_rows_engage`` asks
    i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16
    for call, shapes in (
        (lambda x, order, total: mk._moe_rows_in_call(
            x, order, total, k=k, dtype=jnp.dtype(bf16)),
         [((tokens, hidden), f32), ((rows,), i32), ((), i32)]),
        (lambda x, sizes, w: mk._moe_gmm_call(
            x, (w,), sizes, gated=False, out_dtype=jnp.dtype(f32),
            rows_apart=True),
         [((rows, f), bf16), ((experts,), i32), ((experts, f, hidden), bf16)]),
        (mk._moe_combine_call,
         [((rows, 1, hidden), f32), ((rows,), i32), ((), i32),
          ((tokens, k), f32)]),
    ):
        text = _compile(call, v5e, *shapes)
        assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("tokens", [2048, 128])
def test_longcat_rows_kernels_compile_for_v5e(tokens, v5e):
    """The same three calls at LongCat's widths, 12 choices a token and rows
    of 6,144 lanes: ``moe_rows_in`` fetches 128 rows a step (256 of them
    outgrow its buffers), ``moe_combine`` sums 32 tokens a step (128 x 12
    rows would take 72 MB), its weights a block of 1,024 in SMEM of which
    384 are used; and ``mla_decode`` with 64 heads over the cell's pool."""
    from paddle_tpu.ops import mla_kernels as mk

    k, experts, hidden, f = 12, 16, 6144, 2048
    rows = tokens * k
    i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16
    for call, shapes in (
        (lambda x, order, total: mk._moe_rows_in_call(
            x, order, total, k=k, dtype=jnp.dtype(bf16)),
         [((tokens, hidden), f32), ((rows,), i32), ((), i32)]),
        (lambda x, sizes, w: mk._moe_gmm_call(
            x, (w,), sizes, gated=False, out_dtype=jnp.dtype(f32),
            rows_apart=True),
         [((rows, f), bf16), ((experts,), i32), ((experts, f, hidden), bf16)]),
        (mk._moe_combine_call,
         [((rows, 1, hidden), f32), ((rows,), i32), ((), i32),
          ((tokens, k), f32)]),
        (lambda ql, qr, pool, bt, cl: mk._mla_decode_call(
            ql, qr, pool, bt, cl, scale=0.1, step=mk.DECODE_PAGES_PER_STEP,
            fetch=mk.DECODE_PAGES_PER_FETCH),
         [((128, 64, 512), f32), ((128, 64, 64), f32),
          ((1, 16384, 16, 640), bf16), ((128, 192), i32), ((128,), i32)]),
    ):
        text = _compile(call, v5e, *shapes)
        assert text.count('custom_call_target="tpu_custom_call"') == 1


# sha256 of the Mosaic module (locations off) that commit 0167326, before
# PR 38 touched the kernel, lowered at JoyAI's decode shapes: gated, down
DECODE_GMM_MODULES = {
    True: "f4bfd2239ff806ad1bb4bcfbf4a8be4b3dc39c7d1d7917e0bbccd58e9fe3f89c",
    False: "10f880178daf1e4fc151007fc6da5288e9ae7e1a37e3aa3096831bfbee9db8d8",
}


@pytest.mark.parametrize("gated", [True, False])
def test_moe_gmm_decode_module_is_the_parents(gated, v5e, monkeypatch):
    """The decode-size branch (JoyAI's 1,024 rows over 256 experts) lowers
    to the kernel it was: the Mosaic module's text, operation for
    operation."""
    import hashlib

    from jax._src import tpu_custom_call
    from paddle_tpu.ops import mla_kernels as mk

    modules = []
    lower = tpu_custom_call._lower_mosaic_module_to_asm

    def spy(module, **kw):
        modules.append(module.operation.get_asm(enable_debug_info=False))
        return lower(module, **kw)

    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm", spy)
    k, n, out = (2048, 768, jnp.bfloat16) if gated else \
        (768, 2048, jnp.float32)

    def call(x, sizes, *ws):
        return mk._moe_gmm_call.__wrapped__(x, ws, sizes, gated=gated,
                                            out_dtype=jnp.dtype(out))

    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in
            [((1024, k), jnp.bfloat16), ((256,), jnp.int32)]
            + [((256, k, n), jnp.bfloat16)] * (1 + gated)]
    jax.jit(call).lower(*args)
    assert [hashlib.sha256(m.encode()).hexdigest() for m in modules] == \
        [DECODE_GMM_MODULES[gated]]


# --- JoyAI's serving forms, whole: every device operation has a part ---------
@pytest.mark.parametrize("mode", ["prefill", "decode"])
def test_joyai_form_compiled_for_v5e_names_every_operation(mode, v5e,
                                                           monkeypatch):
    """The form's ops lowered as the executor lowers them (``registry.
    run_op``: the part, then the op's type) and compiled by the chip's
    compiler at the configuration's widths and a depth of two (one dense,
    one expert layer; the rehearsal's widths are not the chip's: Mosaic
    refuses their latent row of 40 lanes): ``profiler.hlo_symbols`` finds a
    part for every Mosaic kernel and every fusion of the compiled program,
    the kernels under their own names."""
    import json
    import os

    from paddle_tpu import profiler
    from paddle_tpu.inference.mla_decoder import (MLADecoderConfig,
                                                  mla_param_specs)
    from paddle_tpu.ops import kda_kernels, mla_kernels, registry

    for module in (pk, mla_kernels, kda_kernels):
        monkeypatch.setattr(module, "_use_pallas", lambda: True)
        monkeypatch.setattr(module, "_interpret", lambda: False)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "joyai-llm-flash.json")) as f:
        size = dict(json.load(f), num_hidden_layers=2)
    cfg = MLADecoderConfig.from_source(
        size, max_seq_len=size["deployment"]["max_context"],
        weights_dtype=size["weights_dtype"])
    rows, batch, width = 1024, 128, 32
    i32 = jnp.int32
    feed = {"prefill": {"tokens": (1, rows), "positions": (1, rows),
                        "last_index": (1,), "slot_mapping": (rows,)},
            "decode": {"tokens": (batch,), "positions": (batch,),
                       "block_tables": (batch, width),
                       "context_lens": (batch,),
                       "slot_mapping": (batch,)}}[mode]

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=v5e)

    pools = {n: shaped(cfg.kv_cache_config(512, 16, "bfloat16").pool_shape(),
                       "bfloat16") for n in cfg.cache_pool_names()}
    weights = {n: shaped(s, cfg.weights_dtype)
               for n, s in mla_param_specs(cfg).items()}
    prog, feeds, fetch = cfg.build_program(mode, kv_dtype="bfloat16")
    assert sorted(feeds) == sorted(feed)
    block = prog.global_block()

    def pt_step(pools, weights, feed):
        env = {**weights, **pools, **feed}
        for op_ in block.ops:
            registry.run_op(op_, env, block)
        return [env[n] for n in fetch], {n: env[n] for n in pools}

    text = jax.jit(pt_step, donate_argnums=0).lower(
        pools, weights, {k: shaped(s, i32) for k, s in feed.items()}) \
        .compile().as_text()
    table = profiler.hlo_symbols(text)
    assert table["module"] == "jit_pt_step"
    ins = table["instructions"]
    kernels = [i for i in ins if i["opcode"] == "custom-call"
               and i["scopes"][-1:] == [i["name"].split(".")[0]]]
    assert len(kernels) == text.count(
        'custom_call_target="tpu_custom_call"')
    attention = "mla_prefill" if mode == "prefill" else "mla_decode"
    # two layers' cache append and attention, one expert layer's two matmuls
    # and, where the rows are a prompt's (8,192 of them; a decode step's
    # 1,024 keep XLA's take), the two kernels that move the owned rows in
    # and out of them
    rows_by = ["moe_rows_in", "moe_combine"] if mode == "prefill" else []
    assert sorted(k["scopes"][-1] for k in kernels) == sorted(
        ["latent_append", attention] * 2 + ["moe_gmm"] * 2 + rows_by)
    assert {k["scopes"][-1]: (k["part"], k["op"]) for k in kernels} == {
        "latent_append": ("mla_part", "latent_cache_append"),
        attention: ("mla_part", "mla_prefill_attention" if mode == "prefill"
                    else "mla_paged_attention"),
        "moe_gmm": ("moe_part", "moe_experts"),
        **{name: ("moe_part", "moe_experts") for name in rows_by}}
    assert [k["scopes"][-2:] for k in kernels
            if k["scopes"][-1] in rows_by] == [
        ["moe_dispatch", "moe_rows_in"], ["moe_combine", "moe_combine"]][
            :len(rows_by)]          # the scope of the XLA it replaced, itself
    fusions = [i for i in ins if i["opcode"] == "fusion"]
    assert len(fusions) > 50
    assert [i["name"] for i in fusions if i["part"] is None] == []
    assert {i["part"] for i in fusions} == {
        "embed", "mla_part", "moe_part", "dense_ffn", "head"}
    # what the compiler adds itself (layout copies, operands brought in
    # ahead of their use) takes the part it serves; what stays bare is a
    # copy or two that nothing of the model reads (a feed's, a prefetch the
    # scheduler left without a reader)
    bare = [i for i in ins if i["part"] is None
            and i["opcode"] not in ("parameter", "constant", "tuple")]
    assert len(bare) <= 4 and {i["opcode"] for i in bare} <= {
        "copy-start", "copy-done"}, bare


# --- the KDA kernels at Kimi-Linear's widths: 32 heads of 128 ----------------
KDA_POOL = (129, 32, 128, 128)    # the cell's state pool a layer, float32


@pytest.mark.parametrize("tokens", [8192, 2048, 16])
def test_kda_prefill_compiles_for_v5e(tokens, v5e):
    """The chunked kernel as the chip's compiler takes it: float32 matmuls
    at the highest precision (the triangular inverse), a transposed-left
    product (the state's update) and a transpose of the state, four heads
    of a whole chunk a grid step (eight of a prompt under a chunk) within
    the VMEM a kernel is given unasked, one Mosaic call a layer."""
    from paddle_tpu.ops import kda_kernels as kk

    chunk, group, grid = kk.prefill_grid(tokens, 32, 128)
    want = 4 if tokens >= 128 else 8
    assert (chunk, group, grid) == (min(tokens, 128), want,
                                    (32 // want, -(-tokens // 128)))

    def f(qkv, g, beta):
        return kk._kda_prefill_call(qkv, g, beta, heads=32, chunk=chunk,
                                    group=group, l2_eps=1e-6)

    text = _compile(f, v5e, ((tokens, 3 * 4096), jnp.float32),
                    ((tokens, 32, 128), jnp.float32),
                    ((tokens, 32), jnp.float32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("rows", [128, 1])
def test_kda_decode_rewrites_its_pool_in_place_on_v5e(rows, v5e):
    """The decode step against the cell's state pool, donated: the chip's
    compiler holds ``f32[129,32,128,128]`` row-major in exact tiles by its
    own choice, the kernel's output aliases it, and the program holds no
    other result of the pool's size (no copy, no re-layout)."""
    from paddle_tpu.ops import kda_kernels as kk

    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in (
        (KDA_POOL, jnp.float32), ((rows,), jnp.int32),
        ((rows, 32, 128), jnp.float32), ((rows, 32, 128), jnp.float32),
        ((rows, 32, 128), jnp.float32), ((rows, 32, 128), jnp.float32),
        ((rows, 32), jnp.float32))]
    text = jax.jit(kk._kda_decode_call, donate_argnums=0) \
        .lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    pool = "f32[129,32,128,128]"
    made = [line for line in text.splitlines()
            if f" = {pool}" in line or f" = ({pool}" in line
            or f", {pool}" in line.partition(" = ")[2].split("(")[0]]
    held = [line for line in made if " parameter(" in line]
    assert held and all("{3,2,1,0:T(8,128)}" in line for line in held)
    others = [line for line in made if " parameter(" not in line
              and "tpu_custom_call" not in line
              and "get-tuple-element" not in line and " tuple(" not in line]
    assert not others, others[:2]
    assert "may-alias" in text or "must-alias" in text


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("head_dim,page_size,decode_copies", [
    # head_dim under the 128 lanes, a page whole tiles: stored lane-full,
    # held row-major by the compiler's own choice, nothing re-laid
    (DEC_D, PAGE, 0),
    # lane-full rows as they are: the same
    (128, PAGE, 0),
    # half a tile a page: stored as the logical shape, which the chip holds
    # page-minor ({1,3,2,0}); the append works on that view, paged_decode's
    # two operands are re-laid
    (DEC_D, 8, 2),
])
def test_pool_kernels_work_where_the_chip_holds_the_pool(
        head_dim, page_size, decode_copies, kv_dtype, v5e, monkeypatch):
    """One layer of the prefill program (append alone) and of the decode
    program (append, then paged decode), the pools donated, in the shape
    the engine stores them in and in whatever layout the chip's compiler
    gives that shape — no layout is asked for anywhere: neither kernel
    moves, reshapes or transposes a whole pool and the append needs no
    temporary; a pool that cannot be stored lane-full costs paged_decode's
    operand copies and nothing else."""
    from chip_smoke import pool_forms, pool_traffic  # the smoke's reader

    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    stored = _stored(head_dim, page_size, kv_dtype)
    lane_full = stored[3] % 128 == 0
    assert lane_full == (decode_copies == 0)
    forms = pool_forms(stored, head_dim)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    pool = arg(stored, kv_dtype)
    rows = arg((DEC_SEQS, DEC_HEADS, head_dim), kv_dtype)
    ints = arg((DEC_SEQS,), jnp.int32)
    scales = [arg((DEC_HEADS, POOL_PAGES), jnp.float32)] * 2 \
        if kv_dtype == "int8" else []

    def prefill(kp, vp, k, v, slots):
        return pk.kv_append((kp, vp), (k, v), slots)

    def decode(kp, vp, k, v, slots, q, bt, cl, *sc):
        kp, vp = pk.kv_append((kp, vp), (k, v), slots)
        ks, vs = sc or (None, None)
        return kp, vp, pk.paged_attention(q, kp, vp, bt, cl,
                                          k_scale=ks, v_scale=vs)

    compiled = jax.jit(prefill, donate_argnums=(0, 1)
                       ).lower(pool, pool, rows, rows, ints).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    moved, _, at_rest = pool_traffic(compiled.as_text(), forms,
                                     views_free=not lane_full)
    assert moved == [] and len(at_rest) == 1, (moved, at_rest)
    # the compiler's own choice for the stored shape
    assert ("{3,2,1,0" in at_rest[0]) == lane_full, at_rest

    compiled = jax.jit(decode, donate_argnums=(0, 1)
                       ).lower(pool, pool, rows, rows, ints,
                               arg((DEC_SEQS, DEC_HEADS, head_dim),
                                   jnp.float32),
                               arg((DEC_SEQS, TABLE_W), jnp.int32),
                               ints, *scales).compile()
    moved, _, _ = pool_traffic(compiled.as_text(), forms,
                               views_free=not lane_full)
    assert [op for op, _ in moved] == ["copy"] * decode_copies, moved



# --- the Gated DeltaNet kernels at Olmo-Hybrid's widths: 30 heads of 96 x 192 ----
GDN_POOL = (129, 15, 96, 384)     # the cell's state pool a layer, float32


@pytest.mark.parametrize("tokens", [4096, 1024, 16])
def test_gdn_prefill_compiles_for_v5e(tokens, v5e):
    """The chunked kernel at a state that fills no lane tile: blocks whose
    last dimension is the whole 96 or 192, products that contract over 96,
    a transposed-left product (the state's update), float32 products at the
    highest precision (the solve), six heads a grid step within the VMEM a
    kernel is given unasked, one Mosaic call a layer."""
    from paddle_tpu.ops import kda_kernels as kk

    chunk, group, grid = kk.gdn_prefill_grid(tokens, 30)
    assert (chunk, group, grid) == (min(tokens, 128), 6,
                                    (5, -(-tokens // 128)))

    def f(q, k, v, g, beta):
        return kk._gdn_prefill_call(q, k, v, g, beta, chunk=chunk,
                                    group=group)

    text = _compile(f, v5e, ((tokens, 30, 96), jnp.float32),
                    ((tokens, 30, 96), jnp.float32),
                    ((tokens, 30, 192), jnp.float32),
                    ((tokens, 30), jnp.float32), ((tokens, 30), jnp.float32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("rows", [128, 1])
def test_gdn_decode_rewrites_its_pool_in_place_on_v5e(rows, v5e):
    """The decode step against the cell's state pool, donated: two heads
    side by side fill three whole tiles of lanes, the chip's compiler holds
    ``f32[129,15,96,384]`` row-major in exact tiles by its own choice, the
    kernel's output aliases it, and the program holds no other result of the
    pool's size (no copy, no re-layout)."""
    from paddle_tpu.ops import kda_kernels as kk

    assert (129,) + kk.gdn_state_shape(30, 96, 192) == GDN_POOL
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in (
        (GDN_POOL, jnp.float32), ((rows,), jnp.int32),
        ((rows, 30, 96), jnp.float32), ((rows, 30, 96), jnp.float32),
        ((rows, 30, 192), jnp.float32), ((rows, 30), jnp.float32),
        ((rows, 30), jnp.float32))]
    text = jax.jit(kk._gdn_decode_call, donate_argnums=0) \
        .lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    pool = "f32[129,15,96,384]"
    made = [line for line in text.splitlines()
            if f" = {pool}" in line or f" = ({pool}" in line
            or f", {pool}" in line.partition(" = ")[2].split("(")[0]]
    held = [line for line in made if " parameter(" in line]
    assert held and all("{3,2,1,0:T(8,128)}" in line for line in held)
    others = [line for line in made if " parameter(" not in line
              and "custom-call(" not in line
              and "get-tuple-element(" not in line and " tuple(" not in line]
    assert not others, others

# --- the grouped-query kernels at Laguna-XS.2's widths -------------------------
@pytest.mark.parametrize("heads,window,tokens", [
    (48, 0, 8192), (64, 512, 8192), (64, 512, 256), (48, 0, 256),
    (48, 0, 4096), (64, 512, 4096)])
def test_gqa_prefill_compiles_for_v5e(heads, window, tokens, v5e):
    """A K/V head's 6 or 8 query heads in one product a block: 1,536 rows
    of 512 keys in a layer without a window, 2,048 of 256 in a window layer
    (one block of 256 keys in the smallest bucket of either), the merged
    rows reshaped back a head for the mask: within the VMEM a kernel is
    given unasked, one Mosaic call."""
    from paddle_tpu.ops import gqa_kernels as gk

    block, keys, steps, seen, causal = gk.prefill_walk(tokens, window)
    assert block == 256 and keys == (256 if window or tokens < 512 else 512)
    assert steps <= 3 if window else seen == causal

    def f(q, k, v):
        return gk._gqa_prefill_call(q, k, v, scale=128 ** -0.5,
                                    window=window)

    text = _compile(f, v5e, ((heads, tokens, 128), jnp.bfloat16),
                    ((8, tokens, 128), jnp.bfloat16),
                    ((8, tokens, 128), jnp.bfloat16))
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("heads,window,rows,width,pages", [
    (48, 0, 128, 1024, 36864), (64, 512, 128, 34, 4352),
    (48, 0, 1, 32, 36864), (64, 512, 8, 34, 4352)])
def test_gqa_decode_compiles_for_v5e(heads, window, rows, width, pages, v5e):
    """A page's eight K/V-head slabs in one strided copy a pool, a chunk of
    32 pages in two buffers, the products batched over the K/V heads; the
    full layers' table (1,024 wide at 8.7 k of context) and the window
    layers' (34) in scalar memory."""
    from paddle_tpu.ops import gqa_kernels as gk

    def f(q, kp, vp, bt, cl, first):
        return gk._gqa_decode_call(q, kp, vp, bt, cl, first,
                                   scale=128 ** -0.5, window=window,
                                   step=gk.DECODE_PAGES_PER_STEP,
                                   fetch=gk.DECODE_PAGES_PER_FETCH)

    pool = ((8, pages, 16, 128), jnp.bfloat16)
    text = _compile(f, v5e, ((rows, heads, 128), jnp.float32), pool, pool,
                    ((rows, width), jnp.int32), ((rows,), jnp.int32),
                    ((rows,), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
