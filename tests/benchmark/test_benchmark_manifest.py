"""The manifest check, on the manifest as committed and on broken copies."""
import copy
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import manifest as manifest_lib


@pytest.fixture(scope="module")
def manifest():
    return manifest_lib.load_manifest()


def test_the_committed_manifest_passes(manifest):
    assert manifest_lib.check(manifest) == []


def test_contract_keys_and_no_others(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(manifest_lib.ROOT,
                                        "BENCHMARK.json")) < 64 * 1024


def _broken(manifest, edit):
    m = copy.deepcopy(manifest)
    edit(m)
    return manifest_lib.check(m)


@pytest.mark.parametrize("edit,needle", [
    (lambda m: m["workloads"][0].update(name="has space"), "not a name"),
    (lambda m: m["per_layer"][0].update(unit="tokens per second"), "unit"),
    (lambda m: m["per_layer"][0].update(moves="nothing"),
     "no end-to-end metric"),
    (lambda m: m["per_layer"][0].pop("workloads"), "do not report"),
    (lambda m: m["workloads"][0].update(traffic="no-such-mix"),
     "no traffic file"),
    (lambda m: m["workloads"][0].update(config="no-such-config"),
     "unknown config"),
    (lambda m: m["per_layer"][0].update(name="unread_metric"), "no reader"),
    (lambda m: [w.update(chips=4) for w in m["workloads"][:2]],
     "four chips"),
    (lambda m: m["end_to_end"][1].update(bound=0.5), "bound"),
    (lambda m: m["end_to_end"].pop(0), "setup_s"),
    (lambda m: m["workloads"].append(dict(m["workloads"][0], name="twin")),
     "pair appears twice"),
])
def test_a_broken_manifest_is_refused(manifest, edit, needle):
    faults = _broken(manifest, edit)
    assert any(needle in f for f in faults), faults


def test_a_generator_without_a_file_is_refused(manifest, tmp_path):
    here = tmp_path / "benchmark"
    here.mkdir()
    for folder in ("runners", "reference", "end_to_end", "layer_metrics"):
        os.symlink(os.path.join(manifest_lib.HERE, folder), here / folder)
    (here / "generators").mkdir()
    faults = manifest_lib.check(manifest, here=str(here))
    assert faults and all("generator 'open_loop' has no file" in f
                          for f in faults), faults


def test_every_named_file_resolves(manifest):
    for c in manifest["configs"]:
        body = json.load(open(os.path.join(manifest_lib.ROOT, c["file"])))
        assert os.path.exists(os.path.join(
            manifest_lib.HERE, "runners", body["runner"] + ".py"))
        assert "rehearsal" in body and "source" in body
    for w in manifest["workloads"]:
        assert os.path.exists(manifest_lib.traffic_file(w["traffic"]))


def test_an_unknown_device_kind_is_an_error():
    assert manifest_lib.check_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        manifest_lib.check_peaks("TPU v9 imaginary")
