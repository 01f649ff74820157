"""The ``olmo-hybrid-7b`` configuration and its cell: the configuration file
against the catalog row's published values, the cut and its bytes from the
program's own ``param_specs`` and ``state_slot_bytes``, the cell's traffic and
plan, both delta-rule rooflines' arithmetic on hand-worked shapes, the readers
on made-up records and on the recorded line of a run, the plain reference's
independence, and the cell's rehearsal on the CPU.  Nothing here needs a chip.
The benchmark's lists are held as lower bounds: a later PR adds to them.
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
from benchmark.rooflines import gdn_decode, gdn_prefill  # noqa: E402

NAME = "olmo-hybrid-7b"
TRAFFIC = "web-doc-backlog"
CELL = f"{NAME}.{TRAFFIC}"
PEAKS = {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}
MODEL = {"layers": 8, "full_layers": 2, "window_layers": 0, "window": 0,
         "heads_full": 30, "heads_window": 30, "kv_heads": 30,
         "head_dim": 128, "hidden": 3840, "item_bytes": 2,
         "cache_item_bytes": 2, "gdn_layers": 6, "gdn_heads": 30,
         "gdn_key_dim": 96, "gdn_value_dim": 192, "gdn_item_bytes": 4,
         "state_item_bytes": 4}
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# the catalog row's config (architectures.jsonl, Olmo-Hybrid-7B)
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}
REDUCED = {"num_hidden_layers": 8}
NEW_METRICS = ("gdn_prefill_roofline", "gdn_decode_roofline",
               "gdn_part_device_pct", "gqa_prefill_roofline.olmo",
               "gqa_decode_roofline.olmo", "attn_full_device_pct.olmo",
               "device_prefill_pct.olmo", "unnamed_device_pct.olmo",
               "state_slots_peak_pct.olmo", "kv_pool_peak_pct.olmo",
               "decode_batch_mean.olmo", "device_idle_pct.olmo",
               "engine_host_ms_p50.olmo")


@pytest.fixture(scope="module")
def manifest():
    return manifest_lib.load_manifest()


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(manifest_lib.traffic_file(TRAFFIC)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model(config):
    from benchmark.runners import serve_gdn

    return serve_gdn.model_config(config)


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
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    assert row["config"] == PUBLISHED
    assert config["source"] == row["source_url"]


def test_config_states_its_cut(config, manifest):
    entry = {c["name"]: c for c in manifest["configs"]}[NAME]
    assert config["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 32}
    assert entry["source"] == config["source"] and "Olmo-Hybrid-7B" in \
        config["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert (config["weights_dtype"], config["kv_dtype"],
            config["state_dtype"]) == ("bfloat16", "bfloat16", "float32")
    for key in ("stands_for", "assumed", "check", "rehearsal", "kernels",
                "departures"):
        assert config[key], key
    for said in ("four pipeline stages of 8 layers", "stage 0",
                 "two whole periods", "2,435,748,072", "14.86 GB",
                 "four times a deployment's"):
        assert said in config["stands_for"], said
    assert {"norm_after", "qk_norm", "rope", "decay_parameters",
            "state_dtype", "head_dim", "linear_layer"} \
        <= set(config["assumed"])
    for key in ("norm_after", "qk_norm"):
        assert config[key] is True
        assert "arXiv:2501.00656" in config["assumed"][key]
    assert config["kernels"] == ["gdn_prefill", "gdn_decode", "gqa_prefill",
                                 "gqa_decode"]
    assert config["runner"] == "serve_gdn"
    # no width is cut, in the file or in its rehearsal's shadow
    assert not [k for k in config["reduced"] if re.search(
        r"_dim$|_rank$|hidden_size|intermediate|per_tok", k)]
    deploy = config["deployment"]
    assert (deploy["page_size"], deploy["max_batch"], deploy["pipeline"],
            deploy["max_context"]) == (16, 128, 2, 2592)
    assert deploy["token_budget"] == 4096 + 128


def test_config_is_the_model_of_the_issues_arithmetic(config, model):
    """Two periods of (linear, linear, linear, full) at the published
    widths: 2,435.7 M parameters from ``param_specs``, 14.10 MB of state a
    sequence with nothing padded, 30,720 B of K/V rows a token."""
    assert model.mixers == ("linear", "linear", "linear", "full") * 2
    specs = model.param_specs()
    count = sum(int(np.prod(s)) for s in specs.values())
    assert count == 2 * 832_520_436 + 770_703_360 + 3_840 == 2_435_748_072
    by_layer = [sum(int(np.prod(s)) for n, s in specs.items()
                    if n.startswith(f"dec_l{i}_")) for i in range(8)]
    assert by_layer == [215_570_172] * 3 + [185_809_920] \
        + [215_570_172] * 3 + [185_809_920]
    mixer = sum(int(np.prod(s)) for n, s in specs.items()
                if n.startswith("dec_l0_gdn_") or n == "dec_l0_wo")
    assert mixer == 88_750_332
    assert specs["dec_l0_gdn_wqkvz"] == (3840, 2880 + 2880 + 5760 + 5760)
    assert specs["dec_l0_gdn_conv"] == (11520, 4)
    assert specs["dec_l3_q_norm_scale"] == (3840,)
    assert not [n for n in specs if "router" in n or "expert" in n]
    # the state: 30 x 96 x 192 float32 and 3 x 11,520 of tail a layer
    assert model.state_slot_bytes() == 6 * (2_211_840 + 138_240) \
        == 14_100_480
    pools = model.state_pool_specs(config["deployment"]["max_batch"])
    assert pools["gdn_state_0"] == ((129, 15, 96, 384), "float32")
    assert pools["gdn_conv_0"] == ((129, 3, 11520), "float32")
    held = sum(int(np.prod(shape)) * 4 for shape, _ in pools.values())
    assert held == 129 * 14_100_480                  # 1.82 GB, none padded
    # K/V: pools for the two full layers alone
    assert model.cache_pool_names() == ["kv_k_3", "kv_v_3", "kv_k_7",
                                        "kv_v_7"]
    assert model.kv_token_bytes("bfloat16") == 30_720
    kvc = model.kv_cache_config(config["deployment"]["num_pages"], 16,
                                "bfloat16")
    assert kvc.pool_shape() == (30, config["deployment"]["num_pages"], 16,
                                128)
    assert kvc.num_layers == 2 and not kvc.window
    assert model.norm_after and model.qk_norm and not model.gate
    assert model.rope_full.lanes == 0 and model.linear_neg_eigval


def test_weights_are_seeded_under_the_names_the_hybrid_runner_seeds():
    """``serve_hybrid.make_weights`` unedited: a linear layer's ``A_log``,
    ``dt_bias`` and taps are drawn as a KDA layer's."""
    import jax

    from benchmark.runners import serve_gdn

    specs = {"dec_l0_gdn_a_log": (6,), "dec_l0_gdn_dt_bias": (6,),
             "dec_l0_gdn_conv": (24, 4), "dec_l0_gdn_wba": (16, 12),
             "dec_l0_gdn_onorm_scale": (8,), "dec_embed": (32, 16)}
    w = serve_gdn.seeded_weights(jax, specs, 2 ** 31 + 7, jax.devices()[0],
                                 "float32")
    assert set(w) == set(specs)
    rate = np.exp(np.asarray(w["dec_l0_gdn_a_log"]))
    assert rate.min() >= 1.0 and rate.max() <= 16.0
    step = np.log1p(np.exp(np.asarray(w["dec_l0_gdn_dt_bias"])))
    assert step.min() >= 9e-4 and step.max() <= 0.11
    assert bool((np.asarray(w["dec_l0_gdn_onorm_scale"]) == 1).all())
    assert {k: tuple(v.shape) for k, v in w.items()} == specs


# -- the manifest ----------------------------------------------------------------
def test_manifest_has_the_cell_and_no_fault(manifest):
    assert manifest_lib.check(manifest) == []
    entry = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert entry["chips"] == 1 and entry["traffic"] == TRAFFIC
    assert entry["config"] == NAME and len(entry["why"]) <= 200
    e2e = {m["name"] for m in
           manifest_lib.metrics_of(manifest, "end_to_end", CELL)}
    assert e2e == {"setup_s", "serve_tokens_per_s"}
    assert len(manifest["workloads"]) >= 8 and len(manifest["configs"]) >= 6
    assert sum(w["config"] == NAME for w in manifest["workloads"]) == 1


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_reads_this_cell_alone(manifest, name):
    m = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert m["workloads"] == [CELL]
    assert m["moves"] == "serve_tokens_per_s"
    assert os.path.exists(os.path.join(
        manifest_lib.HERE, "layer_metrics",
        manifest_lib.reader_of(name) + ".py"))
    if name.endswith("_roofline"):
        assert m["unit"] == "%" and m["layer"] == "kernels"


@pytest.mark.parametrize("name", [n for n in NEW_METRICS if "." in n])
def test_a_suffixed_entry_is_the_accepted_readers(manifest, name):
    """An existing reader on the new cell: the entry differs from an accepted
    one of the same reader in its suffix and its cell alone."""
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    base = manifest_lib.reader_of(name)
    accepted = by_name.get(base) or by_name.get(base + ".laguna") \
        or by_name[base + ".kimi"]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert by_name[name][key] == accepted[key], key
    assert CELL not in accepted["workloads"]


def test_the_accepted_cells_read_nothing_new(manifest):
    for w in manifest["workloads"]:
        if w["name"] == CELL:
            continue
        names = {m["name"] for m in
                 manifest_lib.metrics_of(manifest, "per_layer", w["name"])}
        assert not names & set(NEW_METRICS), w["name"]


# -- the traffic -------------------------------------------------------------------
def test_traffic_is_the_issues(traffic):
    assert traffic["generator"] == "open_loop"
    assert traffic["arrivals"]["process"] == "at_once"
    assert traffic["arrivals"]["count"] % 100 == 0
    assert (traffic["lead_in_s"], traffic["drain_s"],
            traffic["population_seed"]) == (15.0, 0.0, 0)
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 768,
                                     "max": 2304}
    assert traffic["output_len"] == {"dist": "uniform", "min": 96,
                                     "max": 288}
    assert traffic["count_from"] and traffic["who"] and traffic["why"]


def test_a_plan_fits_the_engines_context_and_its_buckets(config, traffic):
    from benchmark.generators import open_loop
    from benchmark.runners.serve_decoder import _buckets

    plan = open_loop.plan(traffic, 2 ** 31 + 5, 40.0, config["vocab_size"],
                          config["deployment"]["max_context"])
    assert len(plan) == traffic["arrivals"]["count"]
    assert all(p.due == -15.0 for p in plan)
    lens = [len(p.prompt) for p in plan]
    ends = [len(p.prompt) + p.want for p in plan]
    assert 768 <= min(lens) and max(lens) <= 2304
    assert max(ends) <= config["deployment"]["max_context"]
    assert max(max(p.prompt) for p in plan[:50]) < config["vocab_size"]
    # at most four prefill buckets, the largest within the token budget
    buckets = _buckets(min(lens), max(lens))
    assert buckets == [1024, 2048, 4096]
    assert buckets[-1] + config["deployment"]["max_batch"] \
        == config["deployment"]["token_budget"]
    # a completion is about 0.2 % of a window's tokens at 20 k tokens/s
    assert 1600 < np.mean(ends) < 1800
    # the same schedule whatever the seed; the token ids from the seed
    other = open_loop.plan(traffic, 7, 40.0, config["vocab_size"], 2592)
    assert [len(p.prompt) for p in other] == lens
    assert other[0].prompt != plan[0].prompt


# -- the reference -----------------------------------------------------------------
def test_reference_is_independent_and_at_highest_precision():
    path = os.path.join(ROOT, "benchmark", "reference", NAME + ".py")
    with open(path) as f:
        text = f.read()
    assert "paddle_tpu" not in re.sub(r'""".*?"""', "", text, flags=re.S)
    assert "pallas" not in text
    assert 'default_matmul_precision("highest")' in text
    assert "lax.scan" in text and "float8_e4m3fn" in text


def test_reference_returns_what_the_comparison_reads():
    """``serve_mla.compare`` takes the worst ``slack`` and the least
    ``margin`` a row: a reference of a model that routes nothing gives one
    column of each, no choice near and none followed."""
    spec = importlib.util.spec_from_file_location(
        "ref_olmo", os.path.join(ROOT, "benchmark", "reference",
                                 NAME + ".py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    from paddle_tpu.inference.gqa_decoder import init_gqa_weights
    from benchmark.runners import serve_gdn

    with open(os.path.join(ROOT, "benchmark", "configs",
                           NAME + ".json")) as f:
        size = json.load(f)
    size.update(size["rehearsal"])
    cfg = serve_gdn.model_config(size)
    weights = init_gqa_weights(cfg, 0)
    out = ref.served_token_scores(weights, size, [3, 5, 7, 9], [1, 2, 4],
                                  routes=None, pad_to=16)
    assert out["logit"].shape == out["lse"].shape == (3,)
    assert out["slack"].shape == out["margin"].shape == (3, 1)
    assert float(out["slack"].max()) == 0.0
    assert bool(np.isinf(out["margin"].min(axis=1)).all()) and out["finite"]
    again = ref.served_token_scores(weights, size, [3, 5, 7, 9], [1, 2, 4])
    np.testing.assert_allclose(out["logit"], again["logit"], atol=1e-5)


# -- the rooflines -------------------------------------------------------------------
def test_gdn_decode_needs_the_state_read_and_written_once():
    # one sequence, one layer: 30 x 96 x 192 float32 twice
    assert gdn_decode.needed_bytes(1, 30, 96, 192, 4) == 2 * 2_211_840
    assert gdn_decode.needed_flops(1, 30, 96, 192) == 7 * 552_960
    # 128 sequences in 6 layers: 3.4 GB, bound by memory
    least = gdn_decode.least_seconds(6 * 128, MODEL, PEAKS)
    assert least == pytest.approx(6 * 128 * 2 * 2_211_840 / 819e9)
    assert least > gdn_decode.needed_flops(6 * 128, 30, 96, 192) / 197e12


def test_gdn_prefill_needs_the_recurrences_work_on_the_real_tokens():
    # a token and head: q and k of 96, v and o of 192, float32
    assert gdn_prefill.needed_bytes(1, 0, 1, 96, 192, 4, 4) == 4 * 576
    assert gdn_prefill.needed_bytes(0, 1, 30, 96, 192, 4, 4) == 2_211_840
    assert gdn_prefill.needed_flops(1000, 30, 96, 192) \
        == 7 * 1000 * 552_960
    # 56 operations a byte: under the chip's 240, bound by the rows streamed
    tokens, calls = 6 * 1500, 6
    least = gdn_prefill.least_seconds(tokens, calls, MODEL, PEAKS)
    assert least == pytest.approx(
        (tokens * 30 * 576 * 4 + calls * 2_211_840) / 819e9)


def test_the_engines_counts_are_the_rooflines_inputs(model):
    """``_form_walk`` from a feed: the real tokens (not the bucket's) and
    the live sequences (not the padded rows), a layer each."""
    from paddle_tpu.inference import gqa_decoder
    from paddle_tpu.ops import kda_kernels

    kvc = model.kv_cache_config(64, 16, "bfloat16")
    import dataclasses
    kvc = dataclasses.replace(kvc, state_slots=8)
    feed = {"tokens": np.zeros((1, 2048), np.int32),
            "last_index": np.array([1499], np.int32)}
    engages = kda_kernels.gdn_engages(30, 96, 192)
    got = gqa_decoder._form_walk(feed, kvc, mode="prefill", cfg=model,
                                 routed=False)
    if engages:
        assert got["gdn_prefill_calls"] == 6
    else:     # no chip and no interpreter: the recurrence, nothing counted
        assert "gdn_prefill_calls" not in got
    os.environ["PT_PALLAS_INTERPRET"] = "1"
    try:
        got = gqa_decoder._form_walk(feed, kvc, mode="prefill", cfg=model,
                                     routed=False)
        assert (got["gdn_prefill_calls"], got["gdn_prefill_tokens"],
                got["gdn_prefill_chunks"]) == (6, 6 * 1500, 6 * 16)
        feed = {"tokens": np.zeros(8, np.int32),
                "state_slots": np.array([0, 3, 5, 8, 8, 8, 8, 8], np.int32),
                "slot_mapping": np.full(8, kvc.pad_slot, np.int32),
                "context_lens": np.ones(8, np.int32),
                "block_tables": np.zeros((8, 4), np.int32)}
        got = gqa_decoder._form_walk(feed, kvc, mode="decode", cfg=model,
                                     routed=False)
        assert (got["gdn_decode_calls"], got["gdn_decode_sequences"]) \
            == (6, 18)
    finally:
        del os.environ["PT_PALLAS_INTERPRET"]


# -- the readers -----------------------------------------------------------------------
def _trace(names_and_ns, busy_s=None):
    rows, at = [], 1000
    for name, ns in names_and_ns:
        rows.append(("/device:TPU:0", "XLA Ops", f"custom-call|{name}", at,
                     ns))
        at += ns + 10
    return {"rows": rows, "devices": [0], "window": (0, at + 1000),
            "busy_s": busy_s}


def test_gdn_decode_roofline_is_least_over_measured():
    # 3 decode steps of 6 linear layers, 100 live sequences each
    counts = {"gdn_decode_calls": 18, "gdn_decode_sequences": 1800}
    least = gdn_decode.least_seconds(1800, MODEL, PEAKS)
    events = [(f"gdn_decode.{i}", int(least / 18 * 1e9 * 4))
              for i in range(18)]
    record = {"gdn_traced": counts, "model": MODEL,
              "harness": {"peaks": PEAKS}}
    got = reader("gdn_decode_roofline").read(record, _trace(events), None)
    assert got == pytest.approx(25.0, rel=1e-3)
    # the trace saw half the calls the host logged: the need is scaled
    got = reader("gdn_decode_roofline").read(record, _trace(events[:9]), None)
    assert got == pytest.approx(25.0, rel=1e-3)
    # a kernel of another name is not read
    assert reader("gdn_decode_roofline").read(
        record, _trace([("kda_decode.1", 50)]), None) is None


def test_gdn_prefill_roofline_is_least_over_measured():
    counts = {"gdn_prefill_calls": 12, "gdn_prefill_tokens": 12 * 1500,
              "gdn_prefill_chunks": 12 * 16}
    least = gdn_prefill.least_seconds(12 * 1500, 12, MODEL, PEAKS)
    events = [(f"gdn_prefill.{i}", int(least / 12 * 1e9 * 5))
              for i in range(12)]
    record = {"gdn_traced": counts, "model": MODEL,
              "harness": {"peaks": PEAKS}}
    got = reader("gdn_prefill_roofline").read(record, _trace(events), None)
    assert got == pytest.approx(20.0, rel=1e-3)


def test_the_accepted_attention_readers_serve_the_cells_record():
    """``gqa_*_roofline`` on a record of this runner's keys: no window
    layer, 30 K/V heads."""
    from benchmark.rooflines import gqa_decode, gqa_prefill

    ctx = [1600] * 128
    assert gqa_decode.needed_bytes(ctx, MODEL) == 2 * 128 * 1600 * 15_360
    step = gqa_decode.least_seconds(ctx, MODEL, PEAKS)
    events = [(f"gqa_decode.{i}", int(step / 2 * 1e9 * 2)) for i in range(4)]
    record = {"decode_ctx": [ctx, ctx], "model": MODEL,
              "harness": {"peaks": PEAKS}}
    assert reader("gqa_decode_roofline").read(record, _trace(events), None) \
        == pytest.approx(50.0, rel=1e-3)
    counts = {"gqa_prefill_calls": 2, "gqa_prefill_tokens": 2 * 1500,
              "gqa_prefill_pairs_full": 2 * 1500 * 1501 // 2,
              "gqa_prefill_pairs_window": 0}
    least = gqa_prefill.least_seconds(counts, MODEL, PEAKS)
    events = [(f"gqa_prefill.{i}", int(least / 2 * 1e9 * 4))
              for i in range(2)]
    record = {"gqa_traced": counts, "model": MODEL,
              "harness": {"peaks": PEAKS}}
    assert reader("gqa_prefill_roofline").read(record, _trace(events), None) \
        == pytest.approx(25.0, rel=1e-3)


def test_scheduler_readers_on_the_cells_record():
    kv = {"peak_pages": 12902, "pages_total": 14336,
          "state_slots": {"total": 128, "in_use": 0, "peak": 128,
                          "freed_by_preemption": 0}}
    record = {"kv": kv,
              "stats_open": {"decode_steps": 10, "decode_tokens": 1000},
              "stats_close": {"decode_steps": 110, "decode_tokens": 13500}}
    assert reader("state_slots_peak_pct").read(record, {}, None) == 100.0
    assert reader("kv_pool_peak_pct").read(record, {}, None) \
        == pytest.approx(100 * 12902 / 14336)
    assert reader("decode_batch_mean").read(record, {}, None) == 125.0


def test_gdn_part_reads_the_recorded_tables_join():
    """``gdn_part`` beside ``attn_full`` and ``dense_ffn`` adds up to the
    busy time, on tables made by hand."""
    from benchmark.lib import device_symbols

    def ins(name, shape, part):
        return {"name": name, "opcode": "fusion", "shape": shape,
                "scopes": [part, "x"], "part": part, "via": None}

    tables = [{"program": "decode", "instructions": [
        ins("fusion.1", "f32[128,5760]", "gdn_part"),
        ins("fusion.2", "f32[128,3840]", "attn_full"),
        ins("fusion.3", "f32[128,11008]", "dense_ffn")]}]
    rows = [("/device:TPU:0", "XLA Ops", "fusion f32[128,5760]|fusion.1",
             100, 500),
            ("/device:TPU:0", "XLA Ops", "fusion f32[128,3840]|fusion.2",
             700, 200),
            ("/device:TPU:0", "XLA Ops", "fusion f32[128,11008]|fusion.3",
             1000, 200),
            ("/device:TPU:0", "XLA Ops", "fusion f32[1]|fusion.9", 1300, 100)]
    found = device_symbols.analyse(rows, (0, 2000), tables)
    record = {"device_symbols": found}
    gdn = reader("gdn_part_device_pct").read(record, {}, None)
    full = reader("attn_full_device_pct").read(record, {}, None)
    assert (gdn, full) == (50.0, 20.0)
    rest = device_symbols.share(found, "by_part", "dense_ffn") \
        + device_symbols.share(found, "by_part", "unnamed")
    assert gdn + full + rest == pytest.approx(100.0)


@pytest.mark.parametrize("name", ["gdn_prefill_roofline",
                                  "gdn_decode_roofline",
                                  "gdn_part_device_pct"])
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
    code, before anything is built; a grouped-query description that knows no
    linear layer is such a program too."""
    from benchmark.runners import serve_gdn
    from paddle_tpu.inference import gqa_decoder

    monkeypatch.delattr(gqa_decoder.GQADecoderConfig, "state_pool_specs")
    with pytest.raises(SystemExit, match="this program has no grouped-query "
                                         "decoder with linear"):
        serve_gdn.model_config(config)
    monkeypatch.setitem(sys.modules, "paddle_tpu.inference.gqa_decoder", None)
    with pytest.raises(SystemExit, match="this program has no"):
        serve_gdn.model_config(config)


def test_the_runner_copies_none_of_what_it_imports():
    from benchmark.runners import (serve_decoder, serve_gdn, serve_hybrid,
                                   serve_mla)

    assert serve_gdn.Reference is serve_hybrid.Reference
    assert serve_gdn.make_weights is serve_hybrid.make_weights
    assert serve_gdn.warm_up is serve_hybrid.warm_up
    assert serve_gdn.compare is serve_mla.compare
    assert serve_gdn.plan is serve_decoder.plan


@pytest.fixture(scope="module")
def rehearsal_lines(tmp_path_factory):
    """One ``--rehearse-on-cpu`` run of the cell for the tests below, through
    the runner's control entry: ``benchmark/run.py``'s own ``main``, with the
    comparison made against the reference in the next precision down too."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(
        tmp_path_factory.mktemp("cache")))
    env.pop("PT_PALLAS_INTERPRET", None)
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.runners.serve_gdn",
         "--workload", CELL, "--seed", str(2 ** 31 + 3), "--seconds", "3",
         "--trace", "1", "--rehearse-on-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    return [json.loads(line) for line in done.stdout.splitlines()
            if line.startswith("{")]


def test_the_cell_rehearses_on_the_cpu(rehearsal_lines):
    """``--rehearse-on-cpu``: tiny sizes (two periods, six heads of 24 x 48,
    eight of 16), kernels interpreted, the whole path from the plan to the
    comparison and the counters' readers."""
    last = rehearsal_lines[-1]
    assert last["rehearsal"] == "passed" and last["failed"] == 0
    got = last["rehearsal_metrics"]
    # (``decode_batch_mean.olmo`` reads the steps INSIDE the window: ten
    # tiny requests may all be served in the lead-in on a fast machine)
    assert {"state_slots_peak_pct.olmo", "kv_pool_peak_pct.olmo",
            "build_s"} <= set(got)
    assert got["state_slots_peak_pct.olmo"]["value"] == 100.0
    line = next(x for x in rehearsal_lines if "check" in x)
    assert line["kernel_calls"] == {"gdn_prefill": 1, "gdn_decode": 1,
                                    "gqa_prefill": 1, "gqa_decode": 1}
    assert line["types"] == {"kv": "bfloat16", "pools": ["bfloat16"],
                             "weights": ["bfloat16"], "state": ["float32"]}
    assert line["types_as_stated"] and not line["wrong_token_count"]
    counts = line["scheduler"]["kernels"]
    assert counts["prefill"]["gdn_prefill_calls"] \
        == 3 * counts["prefill"]["gqa_prefill_calls"]
    assert counts["decode"]["gdn_decode_sequences"] \
        == 3 * counts["decode"]["gqa_decode_sequences"]
    assert line["kv"]["state_slots"]["in_use"] == 0
    assert line["state_slot_bytes"] == 6 * (6 * 24 * 48 + 3 * 6 * 96) * 4


def test_the_next_precision_down_is_refused(rehearsal_lines):
    """The harness's own comparison over the same served values, the
    reference once as served and once through float8_e4m3fn weights and K/V
    rows and a bfloat16 state: within every limit, and beyond both."""
    line = next(x for x in rehearsal_lines if "check_lower" in x)
    served, lower = line["check"], line["check_lower"]
    assert served["within"] and not lower["within"]
    assert served["limits"] == lower["limits"]
    for reading, limit in (("logit_abs_err", "logit_abs_tol"),
                           ("logit_rms_err", "logit_rms_tol")):
        assert 2 * served[reading] < served["limits"][limit] \
            < lower[reading] / 2, reading
    assert served["route_slack"] == lower["route_slack"] == 0.0
