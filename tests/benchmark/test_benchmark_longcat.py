"""The ``longcat-flash-chat`` configuration and its cell: the configuration
file against the catalog row's published values, the cut and its bytes from
the program's own ``param_specs``, the cell's traffic and plan, the new
readers on made-up records, the runner's seeding, the plain reference's
independence and its controls, and the cell's rehearsal on the CPU.  Nothing
here needs a chip.  The benchmark's lists are held as lower bounds: a later
PR adds to them.
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

NAME = "longcat-flash-chat"
TRAFFIC = "long-answer-backlog"
CELL = f"{NAME}.{TRAFFIC}"
# the catalog row's config (architectures.jsonl, LongCat-Flash-Chat)
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12,
}
REDUCED = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}
SUFFIXED = ("mla_decode_roofline", "moe_gmm_roofline", "mla_part_device_pct",
            "moe_part_device_pct", "moe_xla_device_pct", "device_prefill_pct",
            "unnamed_device_pct", "device_idle_pct", "decode_batch_mean",
            "kv_pool_peak_pct", "engine_host_ms_p50", "experts_touched_mean",
            "expert_load_max_over_mean", "dense_ffn_device_pct")
NEW_READERS = ("dense_ffn_device_pct", "zero_expert_choice_pct",
               "held_expert_choice_pct")
NEW_METRICS = tuple(n + ".longcat" for n in SUFFIXED) + NEW_READERS[1:]


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
    from benchmark.runners import serve_longcat

    return serve_longcat.model_config(config)


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


# -- the configuration ------------------------------------------------------
@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_config_keeps_the_published_value(config, key):
    assert config[key] == REDUCED.get(key, PUBLISHED[key])


def test_config_is_the_catalog_rows(config):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LongCat-Flash-Chat")
    assert row["config"] == PUBLISHED
    assert config["source"] == row["source_url"]


def test_config_states_its_cut(config, manifest):
    entry = {c["name"]: c for c in manifest["configs"]}[NAME]
    assert config["reduced"] == entry["reduced"] == list(REDUCED)
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert entry["source"] == config["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert config["router_experts"] == 512      # the router keeps its width
    assert (config["weights_dtype"], config["kv_dtype"]) \
        == ("bfloat16", "bfloat16")
    for key in ("stands_for", "assumed", "check", "rehearsal", "kernels",
                "departures"):
        assert config[key], key
    for said in ("one chip of 32", "16 a chip", "7 pipeline stages",
                 "eighths", "32 times their deployed share"):
        assert said in config["stands_for"], said
    assert {"norm_topk_prob", "mla_scales", "rope", "router_bias", "weights",
            "block_topology", "router"} <= set(config["assumed"])
    assert config["runner"] == "serve_longcat"
    assert config["kernels"] == ["mla_decode", "latent_append", "moe_gmm"]
    # no width is cut, in the file or in its rehearsal's shadow
    assert not [k for k in config["reduced"] if re.search(
        r"_dim$|_rank$|hidden_size|ffn|topk", k)]
    deploy = config["deployment"]
    assert (deploy["page_size"], deploy["max_batch"], deploy["pipeline"],
            deploy["max_context"], deploy["token_budget"]) \
        == (16, 128, 2, 3072, 4096)
    assert 16384 <= deploy["num_pages"] <= 24576


def test_check_states_its_units_and_its_refused_readings(config):
    check = config["check"]
    assert check["sample"] == 6
    assert "1/768" in check["slack_unit"]
    for said in ("no_routed", "no_identity", "float8_e4m3fn"):
        assert said in check["why"], said
    assert 0 < check["logit_rms_tol"] < check["logit_abs_tol"] <= 0.06
    # a limit in score units would be under 1/768 of this one
    assert check["route_slack_tol"] > 0.008


def test_config_is_the_model_of_the_issues_arithmetic(config, model):
    """The issue's count, from the program's own specs: 638.9 M a layer
    beside its experts, 37.75 M an expert, 16 held: 2.486 GB a layer."""
    specs = model.param_specs()
    size = {n: int(np.prod(s)) for n, s in specs.items()}

    def of(j, *names):
        return sum(size[f"dec_l{j}_{n}"] for n in names)

    attention = of(0, "wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
    assert attention == 6144 * 1536 + 1536 * 12288 + 6144 * 576 \
        + 512 * 16384 + 8192 * 6144 == 90_570_752
    dense = of(1, "w_gate", "w_up", "w_down")
    assert dense == 3 * 6144 * 12288 == 226_492_416
    assert size["dec_l0_router"] == 6144 * 768
    assert of(0, "experts_gate", "experts_up", "experts_down") \
        == 16 * 3 * 6144 * 2048
    layer = sum(v for n, v in size.items()
                if n.startswith(("dec_l0_", "dec_l1_")))
    assert abs(layer - 1_242.9e6) < 0.1e6
    total = sum(size.values())
    assert abs(2 * total - 10.35e9) < 0.01e9
    assert model.num_layers == 4 and len(model.mla_layers) == 8
    assert model.kv_token_bytes("bfloat16") == 8 * 640 * 2 == 10_240
    assert model.experts_here == 16 and model.n_routed_experts == 512
    assert model.zero_experts == 256 and model.num_experts_per_tok == 12
    assert model.router_scoring == "softmax" and not model.norm_topk_prob
    assert model.scale_q_lora and model.scale_kv_lora and model.shortcut
    model.validate(kv_dtype="bfloat16")
    pools = config["deployment"]["num_pages"] * 16 * 10_240
    assert 2.6e9 < pools < 3.5e9


# -- the manifest -----------------------------------------------------------
def test_manifest_has_the_cell_and_no_fault(manifest):
    assert manifest_lib.check(manifest) == []
    entry = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert entry["chips"] == 1 and entry["traffic"] == TRAFFIC
    assert entry["config"] == NAME and len(entry["why"]) <= 200
    assert "32x their share" in entry["why"]
    described = {c["name"]: c for c in manifest["configs"]}[NAME]
    assert 1 <= len(described["why"]) <= 200 and described["why"].isprintable()
    e2e = {m["name"] for m in
           manifest_lib.metrics_of(manifest, "end_to_end", CELL)}
    assert e2e == {"setup_s", "serve_tokens_per_s"}
    assert len(manifest["workloads"]) >= 9 and len(manifest["configs"]) >= 7
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert sum(w["config"] == NAME for w in manifest["workloads"]) == 1


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_reads_this_cell_alone(manifest, name):
    m = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert m["workloads"] == [CELL]
    assert m["moves"] == "serve_tokens_per_s"
    assert os.path.exists(os.path.join(
        manifest_lib.HERE, "layer_metrics",
        manifest_lib.reader_of(name) + ".py"))
    if manifest_lib.reader_of(name).endswith("_roofline"):
        assert m["unit"] == "%" and m["layer"] == "kernels"


@pytest.mark.parametrize("name", [n + ".longcat" for n in SUFFIXED[:-1]])
def test_a_suffixed_entry_is_the_accepted_readers(manifest, name):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    base = manifest_lib.reader_of(name)
    accepted = by_name.get(base) or by_name[base + ".joyai"]
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


# -- the traffic ------------------------------------------------------------
def test_traffic_is_the_issues(traffic):
    assert traffic["generator"] == "open_loop"
    assert traffic["arrivals"]["process"] == "at_once"
    assert traffic["arrivals"]["count"] % 100 == 0
    assert (traffic["lead_in_s"], traffic["drain_s"],
            traffic["population_seed"]) == (15.0, 0.0, 0)
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 512,
                                     "max": 2048}
    assert traffic["output_len"] == {"dist": "uniform", "min": 512,
                                     "max": 1024}
    assert traffic["count_from"] and traffic["who"] and traffic["why"]
    depth = traffic["depth"]
    assert traffic["arrivals"]["count"] >= 4 * depth["completed"] - 100
    assert depth["queue_end"] > traffic["arrivals"]["count"] / 2


def test_a_plan_fits_the_engines_context_and_its_buckets(config, traffic):
    from benchmark.generators import open_loop
    from benchmark.runners.serve_decoder import _buckets

    plan = open_loop.plan(traffic, 2 ** 31 + 5, 40.0, config["vocab_size"],
                          config["deployment"]["max_context"])
    assert len(plan) == traffic["arrivals"]["count"]
    assert all(p.due == -15.0 for p in plan)
    lens = [len(p.prompt) for p in plan]
    ends = [len(p.prompt) + p.want for p in plan]
    assert 512 <= min(lens) and max(lens) <= 2048
    assert max(ends) <= config["deployment"]["max_context"]
    assert max(max(p.prompt) for p in plan[:50]) < config["vocab_size"]
    assert _buckets(min(lens), max(lens)) == [512, 1024, 2048]
    # decode-heavy: an answer is over a third of a request's tokens
    assert np.mean([p.want for p in plan]) / np.mean(ends) > 0.35
    other = open_loop.plan(traffic, 7, 40.0, config["vocab_size"], 3072)
    assert [len(p.prompt) for p in other] == lens
    assert other[0].prompt != plan[0].prompt


# -- the reference ------------------------------------------------------------
def test_reference_is_independent_and_at_highest_precision():
    path = os.path.join(ROOT, "benchmark", "reference", NAME + ".py")
    with open(path) as f:
        text = f.read()
    assert "paddle_tpu" not in re.sub(r'""".*?"""', "", text, flags=re.S)
    assert "pallas" not in text
    assert 'default_matmul_precision("highest")' in text
    assert "float8_e4m3fn" in text and "softmax" in text


# -- the new readers ----------------------------------------------------------
def test_choice_readers_take_the_decode_phases_share():
    record = {"choices": {
        "prefill": {"choices_held": 9.0, "choices_identity": 9.0,
                    "choices_all": 18.0},
        "decode": {"choices_held": 2.0, "choices_identity": 33.0,
                   "choices_all": 100.0}}}
    assert reader("zero_expert_choice_pct").read(record, {}, None) == 33.0
    assert reader("held_expert_choice_pct").read(record, {}, None) == 2.0


@pytest.mark.parametrize("record", [
    {}, {"choices": {}}, {"choices": {"decode": {}}},
    {"choices": {"decode": {"choices_all": 0.0, "choices_held": 0.0,
                            "choices_identity": 0.0}}},
    {"choices": {"decode": {"rows_all_absent": 3.0}}}])
@pytest.mark.parametrize("name", NEW_READERS[1:])
def test_a_program_that_counts_no_choice_reads_nothing(name, record):
    """The parent's record has none of this: the reader returns nothing and
    does not raise."""
    assert reader(name).read(record, {}, None) is None


def test_dense_ffn_reads_its_part_of_the_busy_time():
    """``of_run`` keeps its analysis on the record: the reader divides the
    part's seconds by the busy seconds, and reads nothing where the program
    gave no table."""
    record = {"device_symbols": {"busy_s": 2.0, "by_part": {
        "dense_ffn": 0.7, "mla_part": 0.8, "unnamed": 0.01}}}
    assert reader("dense_ffn_device_pct").read(record, {}, None) == 35.0
    assert reader("dense_ffn_device_pct").read(
        {"device_symbols": None}, {}, None) is None


# -- the runner ---------------------------------------------------------------
def test_a_program_without_the_decoder_is_told_so(monkeypatch, config):
    """What the parent commit does with the cell: a sentence and an exit
    code, before anything is built."""
    from benchmark.lib.harness import Cell
    from benchmark.runners import serve_longcat
    from paddle_tpu.inference.mla_decoder import MLADecoderConfig

    cell = Cell(name=CELL, config=config, traffic={}, chips=1, seed=0,
                seconds=1.0, trace=False, rehearsal=True)
    parents = classmethod(lambda cls, source, **ours: cls(
        num_layers=source["num_hidden_layers"]))
    monkeypatch.setattr(MLADecoderConfig, "from_source", parents)
    with pytest.raises(SystemExit, match="describes no shortcut-connected"):
        serve_longcat.build(cell, None)
    monkeypatch.setitem(sys.modules, "paddle_tpu.inference.mla_decoder", None)
    with pytest.raises(SystemExit, match="this program has no MLA decoder"):
        serve_longcat.build(cell, None)


def test_the_runner_copies_none_of_what_it_imports():
    from benchmark.runners import serve_decoder, serve_longcat, serve_mla

    assert serve_longcat.model_config is serve_mla.model_config
    assert serve_longcat.warm_up is serve_mla.warm_up
    assert serve_longcat.compare is serve_mla.compare
    assert serve_longcat.plan is serve_decoder.plan
    assert serve_longcat.make_weights is not serve_mla.make_weights


def test_weights_are_seeded_small_bias_and_aligned_low_rank_streams():
    """The bias a tenth of the uniform score; the two matrices that read a
    scaled low-rank stream over sqrt(hidden), every other over its rows."""
    import jax

    from benchmark.runners import serve_longcat

    specs = {"dec_embed": (64, 256), "dec_l0_router_bias": (768,),
             "dec_l0_wq_b": (64, 512), "dec_l0_wkv_b": (16, 512),
             "dec_l0_wq_a": (256, 64), "dec_l0_q_norm_scale": (64,)}
    w = serve_longcat.make_weights(jax, specs, 2 ** 31 + 9,
                                   jax.devices()[0], "float32")
    again = serve_longcat.make_weights(jax, specs, 2 ** 31 + 9,
                                       jax.devices()[0], "float32")
    for name in specs:
        np.testing.assert_array_equal(w[name], again[name])
    std = {n: float(np.std(np.asarray(v))) for n, v in w.items()}
    assert abs(std["dec_l0_router_bias"] * 7680 - 1.0) < 0.1
    assert abs(std["dec_l0_wq_b"] * 16 - 1.0) < 0.05       # 256 ** -0.5
    assert abs(std["dec_l0_wkv_b"] * 16 - 1.0) < 0.05
    assert abs(std["dec_l0_wq_a"] * 16 - 1.0) < 0.05
    assert std["dec_l0_q_norm_scale"] == 0.0


def test_the_programs_own_seeds_align_the_same_streams(model):
    from paddle_tpu.inference.mla_decoder import seed_fan_in

    assert seed_fan_in(model, "dec_l3_wq_b", (1536, 12288)) == 6144.0
    assert seed_fan_in(model, "dec_l3_wkv_b", (512, 16384)) == 6144.0
    assert seed_fan_in(model, "dec_l3_wo", (8192, 6144)) == 8192.0


# -- the rehearsal ------------------------------------------------------------
@pytest.fixture(scope="module")
def rehearsal_lines(tmp_path_factory):
    """One ``--rehearse-on-cpu`` run of the cell for the tests below, through
    the runner's control entry: ``benchmark/run.py``'s own ``main``, with the
    comparison made against each control too."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(
        tmp_path_factory.mktemp("cache")))
    env.pop("PT_PALLAS_INTERPRET", None)
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.runners.serve_longcat",
         "--workload", CELL, "--seed", str(2 ** 31 + 3), "--seconds", "3",
         "--trace", "1", "--rehearse-on-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    return [json.loads(line) for line in done.stdout.splitlines()
            if line.startswith("{")]


def test_the_cell_rehearses_on_the_cpu(rehearsal_lines):
    """``--rehearse-on-cpu``: tiny sizes (two layers of two sub-layers, 2 of
    8 routed experts held beside 4 identity experts, top-3), kernels
    interpreted, the whole path from the plan to the comparison and the
    counters' readers."""
    last = rehearsal_lines[-1]
    assert last["rehearsal"] == "passed" and last["failed"] == 0
    got = last["rehearsal_metrics"]
    assert {"kv_pool_peak_pct.longcat", "zero_expert_choice_pct",
            "held_expert_choice_pct", "build_s"} <= set(got)
    # 4 of 12 outputs are identity experts, 2 are held
    assert 20 < got["zero_expert_choice_pct"]["value"] < 50
    assert 5 < got["held_expert_choice_pct"]["value"] < 35
    line = next(x for x in rehearsal_lines if "check" in x)
    assert line["kernel_calls"] == {"mla_decode": 1, "latent_append": 1,
                                    "moe_gmm": 2}
    assert line["types"] == {"kv": "bfloat16", "weights": ["bfloat16"]}
    assert line["types_as_stated"] and not line["wrong_token_count"]
    counts = line["scheduler"]["kernels"]["decode"]
    # a layer and step: two latent pools walked, two grouped matmuls
    assert counts["mla_decode_calls"] == counts["moe_gmm_calls"]
    moe = line["moe"]["prefill"]
    assert moe["choices_all"] > moe["choices_identity"] > 0
    assert moe["choices_all"] > moe["choices_held"] > 0


def test_every_control_is_refused(rehearsal_lines):
    """The harness's own comparison over the same served values: the
    reference as served within every limit; in the next precision down,
    without the held experts' sum and without the identity term beyond a
    limit each."""
    line = next(x for x in rehearsal_lines if x.get("check_controls"))
    served, controls = line["check"], line["check_controls"]
    assert served["within"]
    assert set(controls) == {"lower", "no_routed", "no_identity"}
    for name, verdict in controls.items():
        assert not verdict["within"], name
        assert verdict["limits"] == served["limits"]
        assert 2 * served["logit_abs_err"] < served["limits"][
            "logit_abs_tol"] < verdict["logit_abs_err"] / 2, name
        assert 2 * served["logit_rms_err"] < served["limits"][
            "logit_rms_tol"] < verdict["logit_rms_err"] / 2, name
