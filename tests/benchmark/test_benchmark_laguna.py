"""The ``laguna-xs2`` configuration and its cell: the configuration file
against the catalog row's published values, the cut and its bytes, the cell's
traffic, both attention rooflines' arithmetic on hand-worked shapes, the new
readers on made-up records, the plain reference's independence, and the cell's
rehearsal on the CPU.  Nothing here needs a chip.
"""
import importlib
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import manifest as manifest_lib  # noqa: E402
from benchmark.rooflines import gqa_decode, gqa_prefill  # noqa: E402

NAME = "laguna-xs2"
CELL = NAME + ".heavy-tail-backlog"
PEAKS = {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}
MODEL = {"layers": 9, "full_layers": 3, "window_layers": 6, "window": 512,
         "heads_full": 48, "heads_window": 64, "kv_heads": 8,
         "head_dim": 128, "expert_layers": 8, "hidden": 2048,
         "expert_width": 512, "item_bytes": 2, "cache_item_bytes": 2}
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
# the catalog row's config (architectures.jsonl, Laguna-XS.2)
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": PERIOD * 10,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
}
REDUCED = {"num_hidden_layers": 9, "num_experts": 64, "vocab_size": 25088}
SERVING_CELLS = {
    "joyai-llm-flash.long-prompt-backlog": "long-prompt-backlog",
    "kimi-linear-48b-a3b.long-doc-backlog": "long-doc-backlog",
    CELL: "heavy-tail-backlog",
}


@pytest.fixture(scope="module")
def manifest():
    return manifest_lib.load_manifest()


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(manifest_lib.traffic_file("heavy-tail-backlog")) as f:
        return json.load(f)


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


# -- the configuration ---------------------------------------------------------
@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_config_keeps_the_published_value(config, key):
    assert config[key] == REDUCED.get(key, PUBLISHED[key])


def test_config_is_the_catalog_rows(config):
    """Every key of the catalog's row, where the guide's catalog is
    installed."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
    assert row["config"] == PUBLISHED
    assert config["source"] == row["source_url"]


def test_config_states_its_cut(config, manifest):
    entry = {c["name"]: c for c in manifest["configs"]}[NAME]
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) \
        == sorted(REDUCED)
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert entry["source"] == config["source"] and "Laguna-XS.2" in \
        config["source"]
    assert config["router_experts"] == 256
    assert (config["weights_dtype"], config["kv_dtype"]) == \
        ("bfloat16", "bfloat16")
    for key in ("stands_for", "assumed", "check", "rehearsal", "kernels",
                "departures"):
        assert config[key], key
    for said in ("four chips", "five pipeline stages of 8", "chip 0",
                 "128 x 8 / 256 = 4"):
        assert said in config["stands_for"], said
    assert {"gate_width", "router", "gate_input", "qk_norm", "pool_sizes",
            "serving"} <= set(config["assumed"])
    assert "33.44 B" in config["assumed"]["gate_width"]
    assert set(config["kernels"]) == {"gqa_prefill", "gqa_decode", "moe_gmm"}
    # no width is cut, in the file or in its nested group
    assert not [k for k in config["reduced"] if re.search(
        r"_dim$|_rank$|hidden_size|intermediate|per_tok", k)]


def test_config_is_the_model_of_the_issues_arithmetic(config):
    """The dense layer on full attention and two periods of (window, window,
    window, full): 3 full and 6 window layers, 8 expert layers of 64 held
    experts, a quarter of the vocabulary: 2,109 M parameters, 4.22 GB of
    bfloat16; 12,288 B a token in the full layers' pools and at most 34 pages
    a sequence and layer in the window layers'."""
    from benchmark.runners import serve_gqa

    cfg = serve_gqa.model_config(config)
    assert cfg.mixers == ("full",) + ("window", "window", "window",
                                      "full") * 2
    assert (cfg.num_layers, cfg.first_k_dense) == (9, 1)
    assert (cfg.heads_full, cfg.heads_window, cfg.num_kv_heads,
            cfg.head_dim, cfg.window, cfg.gate) == (48, 64, 8, 128, 512, True)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.num_experts_per_tok,
            cfg.n_shared_experts) == (256, 64, 8, 1)
    assert (cfg.rope_full.lanes, cfg.rope_full.base,
            cfg.rope_full.yarn_factor, cfg.rope_full.original_max_position,
            cfg.rope_full.beta_fast, cfg.rope_full.beta_slow) == \
        (64, 500000.0, 64.0, 4096, 64.0, 1.0)
    assert cfg.rope_full.attention_factor == 1.4158883083359672
    assert (cfg.rope_window.lanes, cfg.rope_window.base,
            cfg.rope_window.yarn_factor) == (128, 10000.0, 0.0)
    specs = cfg.param_specs()
    assert specs["dec_l0_wq"] == (2048, 48 * 128) and \
        specs["dec_l1_wq"] == (2048, 64 * 128)
    assert specs["dec_l1_wk"] == (2048, 8 * 128) and \
        specs["dec_l4_wg"] == (2048, 48) and specs["dec_l5_wg"] == (2048, 64)
    assert specs["dec_l1_router"] == (2048, 256)
    assert specs["dec_l1_experts_gate"] == (64, 2048, 512)
    attn = {k: sum(int(np.prod(s)) for n, s in specs.items()
                   if n.startswith(f"dec_l{k}_w") and n[len(f"dec_l{k}_"):]
                   in ("wq", "wk", "wv", "wo", "wg")) for k in (0, 1)}
    assert round(attn[0] / 1e4) == 2946 and round(attn[1] / 1e4) == 3788
    n = sum(int(np.prod(s)) for s in specs.values())
    assert round(n / 1e6) == 2109
    deploy = config["deployment"]
    pools = cfg.kv_cache_config(deploy["num_pages"], 16, "bfloat16")
    assert pools.pool_shape() == (8, 36864, 16, 128)
    assert pools.window_pages_per_seq == 34 and pools.window == 512
    assert pools.groups()["window"]["layers"] == (1, 2, 3, 5, 6, 7)
    assert cfg.kv_token_bytes("bfloat16") == 12288
    assert cfg.kv_layer_token_bytes("bfloat16") == 4096
    assert len(cfg.cache_pool_names()) == 18 and \
        len(cfg.window_pool_names()) == 12
    assert (deploy["max_batch"], deploy["token_budget"],
            deploy["max_context"], deploy["pipeline"], deploy["page_size"]) \
        == (128, 8320, 8704, 2, 16)
    # weights, both groups' pools: under the chip's 16 GB with room for an
    # 8,192-token prefill's temporaries
    held = 2 * n + deploy["num_pages"] * 16 * 12288 \
        + 128 * 34 * 16 * 4096 * 6
    assert 12.5e9 < held < 14e9


@pytest.mark.parametrize("cell", sorted(SERVING_CELLS))
def test_manifest_has_the_serving_cells_and_no_fault(manifest, cell):
    assert manifest_lib.check(manifest) == []
    entry = {w["name"]: w for w in manifest["workloads"]}[cell]
    assert entry["chips"] == 1 and entry["traffic"] == SERVING_CELLS[cell]
    assert len(entry["why"]) <= 200
    e2e = {m["name"] for m in
           manifest_lib.metrics_of(manifest, "end_to_end", cell)}
    assert e2e == {"setup_s", "serve_tokens_per_s"}
    assert len(manifest["workloads"]) >= 7 and len(manifest["configs"]) >= 5


NEW_METRICS = ("gqa_decode_roofline", "gqa_prefill_roofline",
               "attn_full_device_pct", "attn_window_device_pct",
               "window_pool_peak_pct", "moe_gmm_roofline.laguna",
               "moe_part_device_pct.laguna", "moe_xla_device_pct.laguna",
               "device_prefill_pct.laguna", "unnamed_device_pct.laguna",
               "decode_batch_mean.laguna", "kv_pool_peak_pct.laguna",
               "device_idle_pct.laguna", "engine_host_ms_p50.laguna",
               "experts_touched_mean.laguna",
               "expert_load_max_over_mean.laguna")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_reads_this_cell_alone(manifest, name):
    m = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert m["workloads"] == [CELL]
    assert m["moves"] == "serve_tokens_per_s"
    assert os.path.exists(os.path.join(
        manifest_lib.HERE, "layer_metrics",
        manifest_lib.reader_of(name) + ".py"))


@pytest.mark.parametrize("name", [n for n in NEW_METRICS if "." in n])
def test_a_suffixed_entry_is_the_accepted_readers(manifest, name):
    """An existing reader on the new cell: the entry differs from the
    accepted one it copies in its suffix and its cell alone."""
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    base = manifest_lib.reader_of(name)
    accepted = by_name.get(base + ".joyai") or by_name[base]
    new = by_name[name]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert new[key] == accepted[key], key
    assert accepted["workloads"] == ["joyai-llm-flash.long-prompt-backlog"]


def test_the_accepted_cells_read_nothing_new(manifest):
    for w in manifest["workloads"]:
        if w["name"] == CELL:
            continue
        names = {m["name"] for m in
                 manifest_lib.metrics_of(manifest, "per_layer", w["name"])}
        assert not names & set(NEW_METRICS), w["name"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("ir_pass_s", "jax_trace_lower_s",
                 "prefill_device_share_pct.kimi", "moe_device_share_pct"):
        assert CELL not in by_name[name]["workloads"]


def test_traffic_is_the_issues(traffic):
    assert traffic["generator"] == "open_loop"
    assert traffic["arrivals"]["process"] == "at_once"
    assert traffic["arrivals"]["count"] % 100 == 0
    assert (traffic["lead_in_s"], traffic["drain_s"],
            traffic["population_seed"]) == (10.0, 0.0, 0)
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                     "sigma": 0.8, "min": 256, "max": 8192}
    assert traffic["output_len"] == {"dist": "uniform", "min": 128,
                                     "max": 384}
    assert traffic["count_from"] and traffic["who"]


def test_a_plan_fits_the_engines_context_and_the_vocabulary_held(
        config, traffic):
    from benchmark.generators import open_loop

    plan = open_loop.plan(traffic, 2 ** 31 + 5, 40.0, config["vocab_size"],
                          config["deployment"]["max_context"])
    assert len(plan) == traffic["arrivals"]["count"]
    assert all(p.due == -10.0 for p in plan)
    lens = np.array([len(p.prompt) for p in plan])
    ends = np.array([len(p.prompt) + p.want for p in plan])
    assert lens.min() >= 256 and lens.max() == 8192
    assert all(128 <= p.want <= 384 for p in plan)
    # short and long in one queue: some end inside the window, most cross
    # it, 4 % sit at the clip; about 3.0 k tokens a request
    assert 0.004 < (ends <= 512).mean() < 0.03
    assert 0.03 < (lens == 8192).mean() < 0.06
    assert 2700 < ends.mean() < 3100
    assert max(max(p.prompt) for p in plan[:50]) < 25088
    deploy = config["deployment"]
    assert lens.max() + 1 + (deploy["max_batch"] - 1) \
        <= deploy["token_budget"]
    assert ends.max() <= deploy["max_context"]


# -- the plain reference -------------------------------------------------------
def test_reference_is_independent_and_at_highest_precision():
    path = os.path.join(ROOT, "benchmark", "reference", NAME + ".py")
    with open(path) as f:
        source = f.read()
    assert not re.search(r"^\s*(import|from)\s+paddle_tpu", source, re.M)
    assert 'jax.default_matmul_precision("highest")' in source
    assert "Q_BLOCK" in source           # attention by blocks of query rows


# -- rooflines -----------------------------------------------------------------
def test_gqa_decode_needs_each_attended_row_once_a_kv_head():
    ctx = [100, 512, 513, 8000]
    assert gqa_decode.attended(ctx, 0) == 9125
    assert gqa_decode.attended(ctx, 512) == 100 + 512 + 512 + 512
    row = 2 * 8 * 128 * 2                # K and V, 8 heads of 128, bfloat16
    assert row == 4096
    assert gqa_decode.needed_bytes(ctx, MODEL) == \
        row * (3 * 9125 + 6 * 1636)
    assert gqa_decode.needed_flops(ctx, MODEL) == \
        4 * 128 * (3 * 48 * 9125 + 6 * 64 * 1636)
    by_bytes = row * (3 * 9125 + 6 * 1636) / 819e9
    by_flops = 4 * 128 * (3 * 48 * 9125 + 6 * 64 * 1636) / 197e12
    assert by_bytes > 10 * by_flops      # 12-16 operations a byte
    assert gqa_decode.least_seconds(ctx, MODEL, PEAKS) == by_bytes
    assert gqa_decode.least_seconds([], MODEL, PEAKS) == 0


def test_gqa_prefill_needs_the_unmasked_pairs_of_the_real_tokens():
    # one prompt of 1,000 tokens through 3 full and 6 window layers: pairs a
    # head by hand
    full = 1000 * 1001 // 2
    window = 512 * 513 // 2 + (1000 - 512) * 512
    counts = {"gqa_prefill_calls": 9, "gqa_prefill_tokens": 9 * 1000,
              "gqa_prefill_pairs_full": 3 * full,
              "gqa_prefill_pairs_window": 6 * window}
    flops = 4 * 128 * (48 * 3 * full + 64 * 6 * window)
    assert gqa_prefill.needed_flops(3 * full, 6 * window, MODEL) == flops
    heads = (3 * 48 + 6 * 64) / 9
    assert gqa_prefill.needed_bytes(9000, 9, MODEL) == \
        int(9000 * 2 * (heads + 8) * 128 * 2)
    assert gqa_prefill.least_seconds(counts, MODEL, PEAKS) == \
        pytest.approx(flops / 197e12)
    # a prompt of 16 tokens is bound by its bytes
    small = {"gqa_prefill_calls": 9, "gqa_prefill_tokens": 9 * 16,
             "gqa_prefill_pairs_full": 3 * 136,
             "gqa_prefill_pairs_window": 6 * 136}
    assert gqa_prefill.least_seconds(small, MODEL, PEAKS) == \
        pytest.approx(gqa_prefill.needed_bytes(144, 9, MODEL) / 819e9)


def test_the_engines_pair_count_is_the_rooflines():
    """``gqa_decoder._form_walk`` counts the pairs the roofline prices."""
    from paddle_tpu.inference.gqa_decoder import GQADecoderConfig
    from paddle_tpu.inference.gqa_decoder import _form_walk
    from paddle_tpu.inference.kv_cache import KVCacheConfig
    from paddle_tpu.ops import gqa_kernels

    cfg = GQADecoderConfig(window=8)
    feed = {"tokens": np.zeros((1, 32), np.int32),
            "last_index": np.array([19], np.int32)}
    os.environ["PT_PALLAS_INTERPRET"] = "1"
    try:
        assert gqa_kernels.prefill_engages(32, 16)
        got = _form_walk(feed, KVCacheConfig(8, 4, 2, 16), mode="prefill",
                         cfg=cfg, routed=False)
    finally:
        del os.environ["PT_PALLAS_INTERPRET"]
    rows = np.arange(20)[:, None]
    cols = np.arange(20)[None]
    assert got["gqa_prefill_pairs_full"] == 1 * (cols <= rows).sum()
    assert got["gqa_prefill_pairs_window"] == \
        3 * ((cols <= rows) & (cols > rows - 8)).sum()
    assert got["gqa_prefill_tokens"] == 4 * 20


# -- readers -------------------------------------------------------------------
def _trace(names_and_ns, busy_s=None):
    rows, at = [], 1000
    for name, ns in names_and_ns:
        rows.append(("/device:TPU:0", "XLA Ops", f"custom-call|{name}", at,
                     ns))
        at += ns + 10
    return {"rows": rows, "devices": [0], "window": (0, at + 1000),
            "busy_s": busy_s}


def test_gqa_decode_roofline_is_least_over_measured():
    # 2 decode steps of 9 layers, the same contexts in both
    ctx = [3000] * 128
    step = gqa_decode.least_seconds(ctx, MODEL, PEAKS)
    events = [(f"gqa_decode.{i}", int(step / 9 * 1e9 * 4)) for i in range(18)]
    record = {"decode_ctx": [ctx, ctx], "model": MODEL,
              "harness": {"peaks": PEAKS}}
    got = reader("gqa_decode_roofline").read(record, _trace(events), None)
    assert got == pytest.approx(25.0, rel=1e-3)
    # the trace saw half the calls the host logged: the need is scaled
    got = reader("gqa_decode_roofline").read(record, _trace(events[:9]), None)
    assert got == pytest.approx(25.0, rel=1e-3)


def test_gqa_prefill_roofline_is_least_over_measured():
    full, window = 4000 * 4001 // 2, 512 * 513 // 2 + 3488 * 512
    counts = {"gqa_prefill_calls": 9, "gqa_prefill_tokens": 9 * 4000,
              "gqa_prefill_pairs_full": 3 * full,
              "gqa_prefill_pairs_window": 6 * window}
    least = gqa_prefill.least_seconds(counts, MODEL, PEAKS)
    events = [(f"gqa_prefill.{i}", int(least / 9 * 1e9 * 2))
              for i in range(9)]
    record = {"gqa_traced": counts, "model": MODEL,
              "harness": {"peaks": PEAKS}}
    got = reader("gqa_prefill_roofline").read(record, _trace(events), None)
    assert got == pytest.approx(50.0, rel=1e-3)


def test_window_pool_peak_is_a_share_of_the_groups_pages():
    groups = {"full": {"pages_total": 36864, "peak_pages": 30000},
              "window": {"pages_total": 4352, "peak_pages": 3264}}
    record = {"kv": {"peak_pages": 30000, "pages_total": 36864,
                     "groups": groups}}
    assert reader("window_pool_peak_pct").read(record, {}, None) == 75.0
    # kv_pool_peak_pct reads the full layers' group
    assert reader("kv_pool_peak_pct").read(record, {}, None) == \
        pytest.approx(100 * 30000 / 36864)
    assert reader("window_pool_peak_pct").read(
        {"kv": {"peak_pages": 3, "pages_total": 4}}, {}, None) is None


def test_attention_parts_read_the_recorded_tables_join():
    """``attn_full`` and ``attn_window`` beside the other parts add up to the
    busy time, on tables made by hand."""
    from benchmark.lib import device_symbols

    def ins(name, shape, part):
        return {"name": name, "opcode": "fusion", "shape": shape,
                "scopes": [part, "x"], "part": part, "via": None}

    tables = [{"program": "decode", "instructions": [
        ins("fusion.1", "f32[128,2048]", "attn_full"),
        ins("fusion.2", "f32[128,8192]", "attn_window"),
        ins("fusion.3", "f32[128,512]", "moe_part")]}]
    rows = [("/device:TPU:0", "XLA Ops", "fusion f32[128,2048]|fusion.1",
             100, 300),
            ("/device:TPU:0", "XLA Ops", "fusion f32[128,8192]|fusion.2",
             500, 600),
            ("/device:TPU:0", "XLA Ops", "fusion f32[128,512]|fusion.3",
             1200, 100),
            ("/device:TPU:0", "XLA Ops", "fusion f32[1]|fusion.9", 1400, 200)]
    found = device_symbols.analyse(rows, (0, 2000), tables)
    record = {"device_symbols": found}
    full = reader("attn_full_device_pct").read(record, {}, None)
    window = reader("attn_window_device_pct").read(record, {}, None)
    assert (full, window) == (25.0, 50.0)
    rest = device_symbols.share(found, "by_part", "moe_part") \
        + device_symbols.share(found, "by_part", "unnamed")
    assert full + window + rest == pytest.approx(100.0)


@pytest.mark.parametrize("name", ["gqa_prefill_roofline",
                                  "gqa_decode_roofline",
                                  "attn_full_device_pct",
                                  "attn_window_device_pct",
                                  "window_pool_peak_pct"])
def test_a_program_without_the_model_reads_nothing(name):
    """The parent's record has none of this: the reader returns nothing and
    does not raise, with a trace and without one."""
    record = {"harness": {"peaks": PEAKS}, "device_symbols": None}
    assert reader(name).read(record, {}, None) is None
    assert reader(name).read(
        record, _trace([("fusion.1", 50)], busy_s=1.0), None) is None


# -- the runner ------------------------------------------------------------------
def test_a_program_without_the_decoder_is_told_so(monkeypatch, config):
    """What the parent commit does with the cell: a sentence and an exit
    code, before anything is built."""
    from benchmark.runners import serve_gqa

    monkeypatch.setitem(sys.modules, "paddle_tpu.inference.gqa_decoder", None)
    with pytest.raises(SystemExit, match="no grouped-query decoder"):
        serve_gqa.model_config(config)


def test_the_sample_holds_both_lifetimes():
    """The first two places: a request that ended inside the window and one
    whose window pages were freed; the rest as the seed draws."""
    from types import SimpleNamespace as NS

    from benchmark.runners import serve_gqa

    def req(i, prompt, served):
        return NS(req_id=i, prompt=[0] * prompt,
                  handle=NS(out_tokens=[0] * served))

    done = [req(i, 2000 + i, 200) for i in range(40)] \
        + [req(40, 300, 150), req(41, 500, 30)]
    cell = NS(seed=2 ** 31 + 11, config={"check": {"sample": 6}})
    sample, kinds = serve_gqa.sample_of(done, cell, NS(window=512), 16)
    assert len(sample) == 6 and len({p.req_id for p in sample}) == 6
    assert sample[0].req_id == 40 and sample[1].req_id < 40
    assert kinds["ended_inside_window"] == 1 \
        and kinds["window_pages_freed"] == 5 \
        and kinds["completed_inside_window"] == 1
    # 41 ends at 530: past the window, yet no page behind it to free
    again, _ = serve_gqa.sample_of(done, cell, NS(window=512), 16)
    assert [p.req_id for p in again] == [p.req_id for p in sample]
    none_inside, kinds = serve_gqa.sample_of(done[:40], cell,
                                             NS(window=512), 16)
    assert kinds["ended_inside_window"] == 0 and len(none_inside) == 6


@pytest.fixture(scope="module")
def rehearsal_lines(tmp_path_factory):
    """One ``--rehearse-on-cpu`` run of the cell for the tests below, through
    the runner's control entry: ``benchmark/run.py``'s own ``main``, with the
    comparison made against the reference in the next precision down too."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(
        tmp_path_factory.mktemp("cache")))
    env.pop("PT_PALLAS_INTERPRET", None)
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.runners.serve_gqa",
         "--workload", CELL, "--seed", str(2 ** 31 + 3), "--seconds", "3",
         "--trace", "1", "--rehearse-on-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    return [json.loads(line) for line in done.stdout.splitlines()
            if line.startswith("{")]


def test_the_cell_rehearses_on_the_cpu(rehearsal_lines):
    """``--rehearse-on-cpu``: tiny sizes (the dense layer and a period, 8
    experts of which 4 are held, heads of 16, a window of 16), kernels
    interpreted, the whole path from the plan to the comparison and the
    counters' readers."""
    last = rehearsal_lines[-1]
    assert last["rehearsal"] == "passed" and last["failed"] == 0
    got = last["rehearsal_metrics"]
    assert 0 < got["window_pool_peak_pct"]["value"] <= 100.0
    assert {"experts_touched_mean.laguna", "decode_batch_mean.laguna",
            "kv_pool_peak_pct.laguna"} <= set(got)
    assert got["experts_touched_mean.laguna"]["value"] <= 4     # the held
    line = next(x for x in rehearsal_lines if "check" in x)
    window = line["kv"]["groups"]["window"]
    assert window["freed_behind_window"] > 0 and window["pages_in_use"] == 0
    assert line["sample"]["window_pages_freed"] > 0
    walk = line["scheduler"]["kernels"]["decode"]
    assert walk["gqa_decode_pages_walked"] \
        < walk["gqa_decode_pages_in_context"]


def test_the_next_precision_down_is_refused(rehearsal_lines):
    """The harness's own comparison over the same served values, the
    reference once as served and once through float8_e4m3fn weights and K/V
    rows: within every limit, and beyond the logit limits."""
    line = next(x for x in rehearsal_lines if "check_lower" in x)
    served, lower = line["check"], line["check_lower"]
    assert served["within"] and not lower["within"]
    assert served["limits"] == lower["limits"]
    for reading, limit in (("logit_abs_err", "logit_abs_tol"),
                           ("logit_rms_err", "logit_rms_tol")):
        assert 2 * served[reading] < served["limits"][limit] \
            < lower[reading] / 2, reading
    assert served["route_slack"] < served["limits"]["route_slack_tol"]
