"""The ``kimi-linear-48b-a3b`` configuration and its cell: the configuration
file against the catalog row's published values, the cut and its bytes, the
cell's traffic, the KDA rooflines' arithmetic, the new readers on made-up
records, the runner's weights, the plain reference's independence, and the
cell's rehearsal on the CPU.  Nothing here needs a chip.
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
from benchmark.rooflines import kda_decode, kda_prefill  # noqa: E402

NAME = "kimi-linear-48b-a3b"
CELL = NAME + ".long-doc-backlog"
PEAKS = {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}
MODEL = {"layers": 2, "kda_layers": 6, "expert_layers": 7, "heads": 32,
         "latent_values": 576, "kv_lora_rank": 512, "hidden": 2304,
         "expert_width": 1024, "item_bytes": 2, "cache_item_bytes": 2,
         "kda_heads": 32, "kda_head_dim": 128, "kda_item_bytes": 4,
         "state_item_bytes": 4}
# the catalog row's config (architectures.jsonl, Kimi-Linear-48B-A3B-Instruct)
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840,
}
REDUCED = {"num_hidden_layers": 8, "num_experts": 64, "vocab_size": 40960}


@pytest.fixture(scope="module")
def manifest():
    return manifest_lib.load_manifest()


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(manifest_lib.traffic_file("long-doc-backlog")) as f:
        return json.load(f)


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


# -- the configuration ---------------------------------------------------------
@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_config_keeps_the_published_value(config, key):
    assert config[key] == REDUCED.get(key, PUBLISHED[key])


def test_config_states_its_cut(config, manifest):
    entry = {c["name"]: c for c in manifest["configs"]}[NAME]
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) \
        == sorted(REDUCED)
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert entry["source"] == config["source"] and "Kimi-Linear" in \
        config["source"]
    assert config["router_experts"] == 256
    assert (config["weights_dtype"], config["kv_dtype"],
            config["state_dtype"]) == ("bfloat16", "bfloat16", "float32")
    for key in ("stands_for", "assumed", "check", "rehearsal", "kernels",
                "departures"):
        assert config[key], key
    for said in ("four chips", "at most 8", "chip 0", "head"):
        assert said in config["stands_for"], said
    assert {"kda_gate_rank", "decay_parameters", "short_convolution",
            "output_gate", "l2_norm", "state_dtype", "router_bias"} \
        <= set(config["assumed"])
    assert set(config["kernels"]) >= {"kda_prefill", "kda_decode",
                                      "mla_decode", "moe_gmm"}
    # no width is cut, in the file or in its nested group
    assert not [k for k in config["reduced"] if re.search(
        r"_dim$|_rank$|hidden_size|intermediate|per_token", k)]


def test_config_is_the_model_of_the_issues_arithmetic(config):
    """Two periods of (KDA, KDA, KDA, MLA), a dense FFN then 7 expert layers
    of 64 held experts, a quarter of the vocabulary: 3,772 M parameters, 7.54
    GB of bfloat16; 13.47 MB of state a sequence; 2,560 B of latent rows a
    token."""
    from benchmark.runners import serve_hybrid

    cfg = serve_hybrid.model_config(config)
    assert cfg.mixers == ("kda", "kda", "kda", "mla") * 2
    assert (cfg.num_layers, cfg.first_k_dense, cfg.mtp_layers) == (8, 1, 0)
    assert (cfg.n_routed_experts, cfg.experts_held,
            cfg.num_experts_per_tok) == (256, 64, 8)
    assert (cfg.q_lora_rank, cfg.rope) == (0, False)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv_taps,
            cfg.kda_gate_rank) == (32, 128, 4, 128)
    specs = cfg.param_specs()
    assert specs["dec_l1_router"] == (2304, 256)
    assert specs["dec_l1_experts_gate"] == (64, 2304, 1024)
    assert specs["dec_l3_wq"] == (2304, 32 * 192) and "dec_l3_wq_a" not in \
        specs
    n = sum(int(np.prod(s)) for s in specs.values())
    assert round(n / 1e6) == 3772
    deploy = config["deployment"]
    pools = cfg.kv_cache_config(deploy["num_pages"], 16, "bfloat16")
    assert pools.pool_shape() == (1, 69632, 16, 640)
    assert deploy["num_pages"] * 16 == deploy["max_batch"] * deploy["max_context"]
    assert cfg.cache_pool_names() == ["kv_lat_3", "kv_lat_7"]
    assert cfg.kv_token_bytes("bfloat16") == 2560
    assert "state_slots" not in deploy      # a slot a sequence of max_batch
    state = cfg.state_pool_specs(deploy["max_batch"])
    assert len(state) == 12
    assert state["kda_state_0"] == ((129, 32, 128, 128), "float32")
    assert cfg.state_slot_bytes() == 6 * (2097152 + 147456)
    assert deploy["max_batch"] == 128
    assert deploy["token_budget"] == 8192 + 128


def test_manifest_has_the_cell_and_no_fault(manifest):
    assert manifest_lib.check(manifest) == []
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "long-doc-backlog"
    assert len(cell["why"]) <= 200
    e2e = {m["name"] for m in
           manifest_lib.metrics_of(manifest, "end_to_end", CELL)}
    assert e2e == {"setup_s", "serve_tokens_per_s"}
    assert len(manifest["workloads"]) == 6 and len(manifest["configs"]) == 4


NEW_METRICS = ("kda_prefill_roofline", "kda_decode_roofline",
               "kda_device_share_pct", "state_slots_peak_pct",
               "mla_decode_roofline.kimi", "moe_gmm_roofline.kimi",
               "moe_device_share_pct.kimi", "mla_device_share_pct.kimi",
               "experts_touched_mean.kimi", "expert_load_max_over_mean.kimi",
               "decode_batch_mean.kimi", "kv_pool_peak_pct.kimi",
               "device_idle_pct.kimi", "engine_host_ms_p50.kimi",
               "prefill_device_share_pct.kimi")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_reads_this_cell_alone(manifest, name):
    m = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert m["workloads"] == [CELL]
    assert m["moves"] == "serve_tokens_per_s"
    assert os.path.exists(os.path.join(
        manifest_lib.HERE, "layer_metrics",
        manifest_lib.reader_of(name) + ".py"))


def test_the_accepted_cells_read_nothing_new(manifest):
    for w in manifest["workloads"]:
        if w["name"] == CELL:
            continue
        names = {m["name"] for m in
                 manifest_lib.metrics_of(manifest, "per_layer", w["name"])}
        assert not names & set(NEW_METRICS), w["name"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("ir_pass_s", "jax_trace_lower_s"):
        assert CELL not in by_name[name]["workloads"]


def test_traffic_is_the_issues(traffic):
    assert traffic["generator"] == "open_loop"
    assert traffic["arrivals"]["process"] == "at_once"
    assert traffic["arrivals"]["count"] % 100 == 0
    assert (traffic["lead_in_s"], traffic["drain_s"],
            traffic["population_seed"]) == (10.0, 0.0, 0)
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 2048,
                                     "max": 8192}
    assert traffic["output_len"] == {"dist": "uniform", "min": 128,
                                     "max": 512}
    assert traffic["count_from"] and traffic["who"]


def test_a_plan_fits_the_engines_context_and_the_vocabulary_held(
        config, traffic):
    from benchmark.generators import open_loop

    small = dict(traffic, arrivals={"process": "at_once", "count": 50})
    plan = open_loop.plan(small, 2 ** 31 + 5, 40.0, config["vocab_size"],
                          config["deployment"]["max_context"])
    assert len(plan) == 50 and all(p.due == -10.0 for p in plan)
    assert all(2048 <= len(p.prompt) <= 8192 and 128 <= p.want <= 512
               for p in plan)
    assert max(max(p.prompt) for p in plan) < 40960
    deploy = config["deployment"]
    # admission wants prompt + 1 inside the budget less the running rows
    assert max(len(p.prompt) for p in plan) + 1 \
        + (deploy["max_batch"] - 1) <= deploy["token_budget"]
    assert max(len(p.prompt) + p.want for p in plan) <= deploy["max_context"]


# -- the plain reference -------------------------------------------------------
def test_reference_is_independent_and_at_highest_precision():
    path = os.path.join(ROOT, "benchmark", "reference", NAME + ".py")
    with open(path) as f:
        source = f.read()
    assert not re.search(r"^\s*(import|from)\s+paddle_tpu", source, re.M)
    assert 'jax.default_matmul_precision("highest")' in source
    assert "lax.scan" in source          # the recurrence, not a chunked form


# -- rooflines -----------------------------------------------------------------
def test_kda_decode_needs_each_live_state_read_and_written_once():
    state = 32 * 128 * 128
    assert kda_decode.needed_bytes(100, 32, 128, 128, 4) == 2 * 100 * state * 4
    assert kda_decode.needed_flops(100, 32, 128, 128) == 7 * 100 * state
    # 7 operations for 8 bytes: memory binds
    assert kda_decode.least_seconds(100, MODEL, PEAKS) == \
        pytest.approx(2 * 100 * state * 4 / 819e9)
    assert kda_decode.least_seconds(0, MODEL, PEAKS) == 0


def test_kda_prefill_needs_the_recurrence_of_the_real_tokens():
    rows = 32 * 5 * 128 * 4
    state = 32 * 128 * 128 * 4
    assert kda_prefill.needed_bytes(5000, 2, 32, 128, 128, 4, 4) == \
        5000 * rows + 2 * state
    assert kda_prefill.needed_flops(5000, 32, 128, 128) == \
        7 * 5000 * 32 * 128 * 128
    by_bytes = (5000 * rows + 2 * state) / 819e9
    by_flops = 7 * 5000 * 32 * 128 * 128 / 197e12
    assert by_bytes > by_flops          # 45 operations a byte: under 240
    assert kda_prefill.least_seconds(5000, 2, MODEL, PEAKS) == by_bytes


# -- readers -------------------------------------------------------------------
def _trace(names_and_ns, busy_s=None):
    rows, at = [], 1000
    for name, ns in names_and_ns:
        rows.append(("/device:TPU:0", "XLA Ops", f"custom-call|{name}", at,
                     ns))
        at += ns + 10
    return {"rows": rows, "devices": [0], "window": (0, at + 1000),
            "busy_s": busy_s}


def test_kda_decode_roofline_is_least_over_measured():
    # 2 decode steps x 6 layers, 100 live sequences each
    kda = {"kda_decode_calls": 12, "kda_decode_sequences": 1200}
    per_call = kda_decode.least_seconds(100, MODEL, PEAKS)
    events = [(f"kda_decode.{i}", int(per_call * 1e9 * 2))
              for i in range(12)]
    record = {"kda_traced": kda, "model": MODEL, "harness": {"peaks": PEAKS}}
    got = reader("kda_decode_roofline").read(record, _trace(events), None)
    assert got == pytest.approx(50.0, rel=1e-3)
    # the trace saw half the calls the host logged: the need is scaled
    got = reader("kda_decode_roofline").read(record, _trace(events[:6]), None)
    assert got == pytest.approx(50.0, rel=1e-3)


def test_kda_prefill_roofline_is_least_over_measured():
    kda = {"kda_prefill_calls": 6, "kda_prefill_tokens": 6 * 5000}
    per_call = kda_prefill.least_seconds(5000, 1, MODEL, PEAKS)
    events = [(f"kda_prefill.{i}", int(per_call * 1e9 * 10))
              for i in range(6)]
    record = {"kda_traced": kda, "model": MODEL, "harness": {"peaks": PEAKS}}
    got = reader("kda_prefill_roofline").read(record, _trace(events), None)
    assert got == pytest.approx(10.0, rel=1e-3)


def test_kda_device_share_is_its_named_events_over_busy_time():
    events = [("kda_decode.1", 300_000_000), ("kda_prefill.2", 100_000_000),
              ("moe_gmm.3", 500_000_000), ("short_conv_fusion", 100_000_000)]
    got = reader("kda_device_share_pct").read({}, _trace(events, 2.0), None)
    assert got == pytest.approx(25.0)


def test_state_slots_peak_is_a_share_of_the_slots_reserved():
    record = {"kv": {"peak_pages": 3, "pages_total": 4, "state_slots":
                     {"total": 128, "in_use": 0, "peak": 112,
                      "freed_by_preemption": 0}}}
    assert reader("state_slots_peak_pct").read(record, {}, None) == 87.5
    assert reader("state_slots_peak_pct").read(
        {"kv": {"peak_pages": 3, "pages_total": 4}}, {}, None) is None


@pytest.mark.parametrize("name", ["kda_prefill_roofline",
                                  "kda_decode_roofline",
                                  "kda_device_share_pct",
                                  "state_slots_peak_pct"])
def test_a_program_without_the_model_reads_nothing(name):
    """The parent's record has none of this: the reader returns nothing and
    does not raise, with a trace and without one."""
    record = {"harness": {"peaks": PEAKS}}
    assert reader(name).read(record, {}, None) is None
    assert reader(name).read(
        record, _trace([("fusion.1", 50)], busy_s=1.0), None) is None


# -- the runner ------------------------------------------------------------------
def test_weights_are_seeded_and_in_the_stated_type():
    import jax

    from benchmark.runners import serve_hybrid

    specs = {"dec_embed": (64, 32), "dec_l1_router_bias": (8,),
             "dec_l0_kda_wqkv": (32, 96), "dec_l0_kda_conv": (96, 4),
             "dec_l0_kda_a_log": (4,), "dec_l0_kda_dt_bias": (32,),
             "dec_l0_kda_onorm_scale": (8,)}
    dev = jax.devices()[0]
    a = serve_hybrid.make_weights(jax, specs, 2 ** 31 + 7, dev, "bfloat16")
    b = serve_hybrid.make_weights(jax, specs, 2 ** 31 + 7, dev, "bfloat16")
    c = serve_hybrid.make_weights(jax, specs, 8, dev, "bfloat16")
    assert all(str(w.dtype) == "bfloat16" for w in a.values())
    for n in specs:
        np.testing.assert_array_equal(np.asarray(a[n], np.float32),
                                      np.asarray(b[n], np.float32))
    rate = np.exp(np.asarray(a["dec_l0_kda_a_log"], np.float32))
    assert (rate > 0.99).all() and (rate < 16.1).all()
    step = np.log1p(np.exp(np.asarray(a["dec_l0_kda_dt_bias"], np.float32)))
    assert (step > 0.9e-3).all() and (step < 0.11).all()
    assert np.asarray(a["dec_l0_kda_onorm_scale"], np.float32).tolist() \
        == [1.0] * 8
    wide = np.asarray(a["dec_l0_kda_wqkv"], np.float32)
    assert 0.5 < wide.std() * np.sqrt(32) < 1.5       # over sqrt(fan-in)
    assert not np.array_equal(np.asarray(a["dec_embed"], np.float32),
                              np.asarray(c["dec_embed"], np.float32))


def test_a_program_without_the_decoder_is_told_so(monkeypatch, config):
    """What the parent commit does with the cell: a sentence and an exit
    code, before anything is built."""
    from benchmark.runners import serve_hybrid
    from paddle_tpu.inference import mla_decoder

    monkeypatch.delattr(mla_decoder.MLADecoderConfig, "state_pool_specs")
    with pytest.raises(SystemExit, match="no hybrid"):
        serve_hybrid.model_config(config)


@pytest.fixture(scope="module")
def rehearsal_lines(tmp_path_factory):
    """One ``--rehearse-on-cpu`` run of the cell for the tests below, through
    the runner's control entry: ``benchmark/run.py``'s own ``main``, with the
    comparison made against the reference in the next precision down too."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(
        tmp_path_factory.mktemp("cache")))
    env.pop("PT_PALLAS_INTERPRET", None)
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.runners.serve_hybrid",
         "--workload", CELL, "--seed", str(2 ** 31 + 3), "--seconds", "3",
         "--trace", "1", "--rehearse-on-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    return [json.loads(line) for line in done.stdout.splitlines()
            if line.startswith("{")]


def test_the_cell_rehearses_on_the_cpu(rehearsal_lines):
    """``--rehearse-on-cpu``: tiny sizes (two periods of four layers, 8
    experts of which 4 are held, heads of 16), kernels interpreted, the
    whole path from the plan to the comparison and the counters' readers."""
    last = rehearsal_lines[-1]
    assert last["rehearsal"] == "passed" and last["failed"] == 0
    got = last["rehearsal_metrics"]
    assert got["state_slots_peak_pct"]["value"] == 100.0
    assert {"experts_touched_mean.kimi", "decode_batch_mean.kimi",
            "kv_pool_peak_pct.kimi"} <= set(got)
    assert got["experts_touched_mean.kimi"]["value"] <= 4      # the held


def test_the_next_precision_down_is_refused_by_each_limit(rehearsal_lines):
    """The harness's own comparison (``serve_mla.compare``) over the same
    served values, the reference once as served and once through
    float8_e4m3fn weights and a bfloat16 state: within every limit, and
    beyond every limit.  What following the engine's routing replaced is
    counted, and is held by the slack limit."""
    line = next(x for x in rehearsal_lines if "check_lower" in x)
    served, lower = line["check"], line["check_lower"]
    assert served["within"] and not lower["within"]
    assert served["limits"] == lower["limits"]
    for reading, limit in (("logit_abs_err", "logit_abs_tol"),
                           ("logit_rms_err", "logit_rms_tol"),
                           ("route_slack", "route_slack_tol")):
        assert 2 * served[reading] < served["limits"][limit] \
            < lower[reading] / 2, reading
    followed = line["routing_followed"]
    assert len(followed) == served["checked"]
    assert all(0 <= f["rows_routed_otherwise"] <= f["prompt_rows"]
               and f["worst_slack"] <= served["route_slack"]
               for f in followed)
