"""The ``joyai-llm-flash`` configuration and its cell: the configuration file
against the published widths, the cell's traffic, the rooflines' arithmetic,
the new readers on made-up records, the device-part classes, and the plain
reference's independence.  Nothing here needs a chip.
"""
import importlib
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import manifest as manifest_lib  # noqa: E402
from benchmark.lib import scopes  # noqa: E402
from benchmark.rooflines import mla_decode, moe_gmm  # noqa: E402

CELL = "joyai-llm-flash.long-prompt-backlog"
PEAKS = {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}
MODEL = {"layers": 5, "expert_layers": 4, "heads": 32, "latent_values": 576,
         "kv_lora_rank": 512, "hidden": 2048, "expert_width": 768,
         "item_bytes": 2, "cache_item_bytes": 2}
# the source's config.json, the keys that are sizes
PUBLISHED = {
    "hidden_size": 2048, "num_attention_heads": 32, "q_lora_rank": 1536,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "intermediate_size": 7168,
    "moe_intermediate_size": 768, "n_routed_experts": 256,
    "num_experts_per_tok": 8, "n_shared_experts": 1, "vocab_size": 129280,
    "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
    "routed_scaling_factor": 2.5, "rope_theta": 32000000,
    "rms_norm_eps": 1e-06, "max_position_embeddings": 131072,
}


@pytest.fixture(scope="module")
def manifest():
    return manifest_lib.load_manifest()


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "joyai-llm-flash.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(manifest_lib.traffic_file("long-prompt-backlog")) as f:
        return json.load(f)


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


# -- the configuration ---------------------------------------------------------
@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_config_keeps_the_published_size(config, key):
    assert config[key] == PUBLISHED[key]


def test_config_is_cut_in_depth_alone(config, manifest):
    entry = {c["name"]: c for c in manifest["configs"]}["joyai-llm-flash"]
    assert config["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 5
    assert config["published"]["num_hidden_layers"] == 40
    assert entry["source"] == config["source"]
    assert config["weights_dtype"] == config["kv_dtype"] == "bfloat16"
    for key in ("stands_for", "assumed", "check", "rehearsal", "kernels",
                "departures"):
        assert config[key], key
    assert {"mtp_hidden_state", "mtp_concat_order", "router_bias",
            "page_size", "num_pages", "max_batch", "token_budget"} \
        <= set(config["assumed"])


def test_config_is_the_model_of_the_issues_arithmetic(config):
    """One dense layer, four expert layers with all 256 experts and the
    whole vocabulary: 5,558 M parameters, 11.12 GB of bfloat16."""
    from benchmark.runners import serve_mla

    cfg = serve_mla.model_config(config)
    assert (cfg.num_layers, cfg.first_k_dense, cfg.mtp_layers) == (5, 1, 0)
    assert cfg.latent_width == 576 and cfg.latent_row == 640
    n = sum(int(np.prod(s)) for s in cfg.param_specs().values())
    assert round(n / 1e6) == 5558
    pools = cfg.kv_cache_config(
        config["deployment"]["num_pages"], 16, "bfloat16")
    assert pools.pool_shape() == (1, config["deployment"]["num_pages"], 16,
                                  640)
    assert len(cfg.cache_pool_names()) == 5


def test_manifest_has_the_cell_and_no_fault(manifest):
    assert manifest_lib.check(manifest) == []
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "long-prompt-backlog"
    assert "5 of 40" in cell["why"] and len(cell["why"]) <= 200
    e2e = {m["name"] for m in
           manifest_lib.metrics_of(manifest, "end_to_end", CELL)}
    assert e2e == {"setup_s", "serve_tokens_per_s"}


NEW_METRICS = ("mla_decode_roofline", "moe_gmm_roofline",
               "moe_device_share_pct", "mla_device_share_pct",
               "experts_touched_mean", "expert_load_max_over_mean",
               "decode_batch_mean.joyai", "kv_pool_peak_pct.joyai",
               "device_idle_pct.joyai", "engine_host_ms_p50.joyai",
               "prefill_device_share_pct.joyai")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_reads_this_cell_alone(manifest, name):
    m = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert m["workloads"] == [CELL]
    assert m["moves"] == "serve_tokens_per_s"
    assert os.path.exists(os.path.join(
        manifest_lib.HERE, "layer_metrics",
        manifest_lib.reader_of(name) + ".py"))


def test_the_accepted_cells_read_nothing_new(manifest):
    for cell in ("gpt2-small.chat-poisson", "gpt2-small.prompt-backlog",
                 "resnet50.train-b128", "resnet50.dp4-b512"):
        names = {m["name"] for m in
                 manifest_lib.metrics_of(manifest, "per_layer", cell)}
        assert not names & set(NEW_METRICS)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("ir_pass_s", "jax_trace_lower_s"):
        assert CELL not in by_name[name]["workloads"]


def test_traffic_is_the_issues(traffic):
    assert traffic["generator"] == "open_loop"
    assert traffic["arrivals"]["process"] == "at_once"
    assert traffic["arrivals"]["count"] % 100 == 0
    assert (traffic["lead_in_s"], traffic["drain_s"],
            traffic["population_seed"]) == (3.0, 0.0, 0)
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 1024,
                                     "max": 4096}
    assert traffic["output_len"] == {"dist": "uniform", "min": 64,
                                     "max": 256}


def test_a_plan_fits_the_engines_context(config, traffic):
    from benchmark.generators import open_loop

    small = dict(traffic, arrivals={"process": "at_once", "count": 50})
    plan = open_loop.plan(small, 2 ** 31 + 5, 40.0, config["vocab_size"],
                          config["deployment"]["max_context"])
    assert len(plan) == 50 and all(p.due == -3.0 for p in plan)
    assert all(1024 <= len(p.prompt) <= 4096 and 64 <= p.want <= 256
               for p in plan)
    assert max(max(p.prompt) for p in plan) < config["vocab_size"]
    budget = config["deployment"]["token_budget"]
    assert max(len(p.prompt) for p in plan) + 1 \
        + config["deployment"]["max_batch"] <= budget


# -- the plain reference -------------------------------------------------------
def test_reference_is_independent_and_at_highest_precision():
    path = os.path.join(ROOT, "benchmark", "reference", "joyai-llm-flash.py")
    with open(path) as f:
        source = f.read()
    assert not re.search(r"^\s*(import|from)\s+paddle_tpu", source, re.M)
    assert 'jax.default_matmul_precision("highest")' in source


# -- rooflines -----------------------------------------------------------------
def test_mla_decode_needs_the_true_contexts_latent_rows():
    ctx = [100, 2700, 1]
    assert mla_decode.needed_bytes(ctx, 576, 2) == 2801 * 1152
    assert mla_decode.needed_flops(ctx, 32, 576, 512) == 2801 * 32 * 2176
    # about 60 operations a byte: under the chip's 240, so memory binds
    assert mla_decode.least_seconds(ctx, MODEL, PEAKS) == \
        pytest.approx(2801 * 1152 / 819e9)


def test_moe_gmm_needs_the_experts_that_received_a_token():
    counts = [0] * 256
    counts[3], counts[200] = 5, 1
    one = 3 * 2048 * 768
    assert moe_gmm.needed_bytes(counts, 2048, 768, 2) == 2 * one * 2
    assert moe_gmm.needed_flops(counts, 2048, 768) == 6 * 2 * one
    assert moe_gmm.needed_bytes([0] * 256, 2048, 768, 2) == 0


# the chip does 240 operations in the time it reads a byte; a routed row
# does 1 an expert byte (2 operations a 2-byte weight): 240 rows an expert
@pytest.mark.parametrize("rows_each,bound", [(4, "memory"), (128, "memory"),
                                             (512, "compute")])
def test_moe_gmm_takes_the_larger_of_its_two_times(rows_each, bound):
    counts = [rows_each] * 256
    by_bytes = moe_gmm.needed_bytes(counts, 2048, 768, 2) / 819e9
    by_flops = moe_gmm.needed_flops(counts, 2048, 768) / 197e12
    assert (by_bytes > by_flops) == (bound == "memory")
    assert moe_gmm.least_seconds(counts, MODEL, PEAKS) == \
        max(by_bytes, by_flops)


# -- readers -------------------------------------------------------------------
def _trace(names_and_ns):
    """A reduction with one device and a window that holds every event."""
    rows, at = [], 1000
    for name, ns in names_and_ns:
        rows.append(("/device:TPU:0", "XLA Ops", f"custom-call|{name}", at,
                     ns))
        at += ns + 10
    return {"rows": rows, "devices": [0], "window": (0, at + 1000)}


def test_mla_decode_roofline_is_least_over_measured():
    ctx = [[1000, 3000], [2000, 2000]]              # two traced steps
    per_call = 4000 * 1152 / 819e9                  # both steps need the same
    events = [(f"mla_decode.{i}", int(per_call * 1e9 * 4))
              for i in range(10)]                   # 2 steps x 5 layers, 4x slow
    record = {"decode_ctx": ctx, "model": MODEL, "harness": {"peaks": PEAKS}}
    got = reader("mla_decode_roofline").read(record, _trace(events), None)
    assert got == pytest.approx(25.0, rel=1e-3)


def test_moe_gmm_roofline_is_least_over_measured():
    counts = np.full((4, 256), 4.0)                 # a decode call, 4 layers
    least = moe_gmm.least_seconds(counts[0], MODEL, PEAKS)
    events = [(f"moe_gmm.{i}", int(least * 1e9)) for i in range(8)]
    record = {"moe_calls": [("decode", counts)], "model": MODEL,
              "harness": {"peaks": PEAKS}}
    # two kernel calls a layer, each as long as the layer's least: 50 %
    got = reader("moe_gmm_roofline").read(record, _trace(events), None)
    assert got == pytest.approx(50.0, rel=1e-3)


def test_device_share_readers_divide_by_busy_time():
    record = {"device_parts": {"seconds": {"moe": 1.2, "mla": 0.6,
                                           "head": 0.2}, "busy_s": 2.0}}
    assert reader("moe_device_share_pct").read(record, {}, None) == 60.0
    assert reader("mla_device_share_pct").read(record, {}, None) == \
        pytest.approx(30.0)


def test_expert_readers_take_the_windows_change():
    record = {
        "moe_open": {"decode": {"layer_steps": 40.0, "experts_touched":
                                8000.0, "expert_load_max_over_mean": 60.0}},
        "moe_close": {"decode": {"layer_steps": 440.0, "experts_touched":
                                 108000.0, "expert_load_max_over_mean": 900.0}}}
    assert reader("experts_touched_mean").read(record, {}, None) == 250.0
    assert reader("expert_load_max_over_mean").read(record, {}, None) == 2.1


@pytest.mark.parametrize("name", ["mla_decode_roofline", "moe_gmm_roofline",
                                  "moe_device_share_pct",
                                  "mla_device_share_pct",
                                  "experts_touched_mean",
                                  "expert_load_max_over_mean"])
def test_a_program_without_the_model_reads_nothing(name):
    """The parent's record has none of this: the reader returns nothing and
    does not raise, with a trace and without one."""
    record = {"harness": {"peaks": PEAKS}}
    assert reader(name).read(record, {}, None) is None
    assert reader(name).read(record, _trace([("fusion.1", 50)]), None) is None


# -- device parts ----------------------------------------------------------------
@pytest.mark.parametrize("text,part", [
    ("%moe_gmm.3 = bf16[1024,768] custom-call(...)", "moe"),
    ("%fusion.7 = f32[128,2048] fusion(...) tf_op=jit(pt_decode)/"
     "matmul_f32acc/moe_part/dot_general", "moe"),
    ("%fusion.9 tf_op=jit(pt_decode)/moe_experts/moe_dispatch/sort", "moe"),
    ("%mla_decode.1 = f32[128,32,512] custom-call(...)", "mla"),
    ("%latent_append.2 = bf16[1,24576,16,640] custom-call(...)", "mla"),
    ("%fusion.2 tf_op=jit(pt_prefill)/mla_prefill_attention/mla_attention/"
     "dot_general", "mla"),
    ("%fusion.4 tf_op=jit(pt_decode)/rms_norm/mla_part/mul", "mla"),
    ("%fusion.5 tf_op=jit(pt_decode)/matmul_f32acc/dense_ffn/dot_general",
     "dense_ffn"),
    ("%fusion.6 tf_op=jit(pt_decode)/matmul_f32acc/head/dot_general", "head"),
    ("%fusion.8 tf_op=jit(pt_decode)/lookup_table_v2/gather", "embed"),
    ("%copy.3 = f32[128] copy(...)", "other"),
])
def test_part_of_a_device_event(text, part):
    assert scopes.part_of(text) == part


def test_classify_unions_inside_the_window():
    events = [("moe_gmm.1", 100, 50), ("moe_gmm.2", 120, 50),   # overlap
              ("mla_decode.1", 200, 100), ("fusion.3", 400, 10),
              ("moe_gmm.9", 5000, 50)]                          # outside
    got = scopes.classify(events, (0, 1000))
    assert got["seconds"] == {"moe": 70e-9, "mla": 100e-9, "other": 10e-9}
    assert got["busy_s"] == pytest.approx(180e-9)
    assert got["longest"]["mla"] == ["mla_decode.1"]


def test_no_profile_reads_nothing(tmp_path):
    assert scopes.of_trace(None) is None
    assert scopes.of_trace(str(tmp_path)) is None


# -- the runner's weights ---------------------------------------------------------
def test_weights_are_seeded_and_in_the_stated_type():
    import jax

    from benchmark.runners import serve_mla

    specs = {"dec_embed": (64, 32), "dec_l1_router_bias": (8,),
             "dec_l1_experts_gate": (8, 32, 16), "dec_norm_scale": (32,)}
    dev = jax.devices()[0]
    a = serve_mla.make_weights(jax, specs, 2 ** 31 + 7, dev, "bfloat16")
    b = serve_mla.make_weights(jax, specs, 2 ** 31 + 7, dev, "bfloat16")
    c = serve_mla.make_weights(jax, specs, 8, dev, "bfloat16")
    assert all(str(w.dtype) == "bfloat16" for w in a.values())
    for n in specs:
        np.testing.assert_array_equal(np.asarray(a[n], np.float32),
                                      np.asarray(b[n], np.float32))
    assert np.asarray(a["dec_norm_scale"], np.float32).tolist() == [1.0] * 32
    assert np.abs(np.asarray(a["dec_l1_router_bias"], np.float32)).max() < .1
    gate = np.asarray(a["dec_l1_experts_gate"], np.float32)
    assert 0.5 < gate.std() * np.sqrt(32) < 1.5       # over sqrt(fan-in)
    assert not np.array_equal(np.asarray(a["dec_embed"], np.float32),
                              np.asarray(c["dec_embed"], np.float32))
