"""The join of the device's rows with the program's tables of its compiled
steps (``benchmark/lib/device_symbols.py``), on the fixture laid out by hand
(``benchmark/lib/recorded_device_symbols.json``), in ms on the trace's clock.

Window 0-1000.  Tables: one ``prefill`` step and the ``decode`` step compiled
twice (table widths 16 and 32: the same names, shapes and parts, which is
agreement).  Device 0, inside the window:

    prefill step                          decode step
    10-11    fusion.3 s32[1]      (a)     615-620  copy-done.2      moe (users)
    20-100   fusion.11            mla     620-670  moe_gmm.1 [1024] moe kernel
    100-220  mla_prefill.1        mla k.  670-680  fusion.10 [1024] moe
    220-320  kda_prefill.1        kda k.  680-720  mla_decode.1     mla kernel
    320-360  fusion.12            kda     720-750  kda_decode.1     kda kernel
    360-510  moe_gmm.1 [4096]     moe k.  750-751  fusion.3 s32[1]  (a)
    510-570  fusion.10 [4096]     moe     760-769  fusion.99        (c)
    520-540  fusion.10 again, nested
    570-590  copy.5               (b)
    590-600  fusion.20            head

(a) ``fusion.3 s32[1]`` is ``embed`` in the prefill table and ``head`` in the
decode tables: no form and no part.  (b) ``copy.5`` has no part in its table:
the form is known, the part is not.  (c) no table holds ``fusion.99``.  One
``fusion.11`` crosses the window's end (990-1020) and device 1 is busy all
through: neither is read.

Busy: 1 + 580 (20-600 without a gap) + 135 (615-750) + 1 + 9 = 726.
By form: prefill 580 (20-600), decode 135.  By part: moe 150 + 60 + 5 + 50 +
10 = 275 (the nested event counts once), of which the kernels 200, so moe_xla
75; mla 80 + 120 + 40 = 240; kda 100 + 40 + 30 = 170; head 10; unnamed 1 + 20
+ 1 + 9 = 31; 275 + 240 + 170 + 10 + 31 = 726.

    device_prefill_pct   580 / 726 = 79.8898
    moe_part_device_pct  275 / 726 = 37.8788
    moe_xla_device_pct    75 / 726 = 10.3306
    mla_part_device_pct  240 / 726 = 33.0579
    kda_part_device_pct  170 / 726 = 23.4160
    unnamed_device_pct    31 / 726 =  4.2700
"""
import importlib
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import device_symbols
from benchmark.lib import manifest as manifest_lib
from benchmark.lib import trace as trace_lib

HERE = manifest_lib.HERE
WANT = {"device_prefill_pct": 100 * 580 / 726,
        "moe_part_device_pct": 100 * 275 / 726,
        "moe_xla_device_pct": 100 * 75 / 726,
        "mla_part_device_pct": 100 * 240 / 726,
        "kda_part_device_pct": 100 * 170 / 726,
        "unnamed_device_pct": 100 * 31 / 726}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "lib", "recorded_device_symbols.json")) as f:
        body = json.load(f)
    return {"rows": [tuple(r) for r in body["rows"]],
            "tables": body["tables"]}


@pytest.fixture
def reduction(recorded):
    return trace_lib.reduce(recorded["rows"])


def _reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


@pytest.fixture
def as_the_program(monkeypatch, recorded):
    """The readers take the fixture's tables as the program's."""
    def offer(tables=recorded["tables"]):
        monkeypatch.setattr(device_symbols, "program_tables",
                            lambda: tables)
    return offer


def test_the_analysis_by_hand(recorded, reduction):
    found = device_symbols.analyse(recorded["rows"], reduction["window"],
                                   recorded["tables"])
    ms = pytest.approx
    assert found["busy_s"] == ms(0.726)
    assert found["by_program"] == {"prefill": ms(0.580), "decode": ms(0.135)}
    assert found["by_part"] == {
        "moe_part": ms(0.275), "mla_part": ms(0.240), "kda_part": ms(0.170),
        "head": ms(0.010), "unnamed": ms(0.031)}
    # parts and unnamed are disjoint and cover the busy time
    assert sum(found["by_part"].values()) == ms(found["busy_s"])
    assert found["unnamed_why"] == {"ambiguous": ms(0.002),
                                    "no_scope": ms(0.020),
                                    "no_table": ms(0.009)}
    assert found["named_via"] == {"own": ms(0.690), "users": ms(0.005)}
    assert found["kernels_s"] == {
        "moe_part": {"moe_gmm": ms(0.200)},
        "mla_part": {"mla_prefill": ms(0.120), "mla_decode": ms(0.040)},
        "kda_part": {"kda_prefill": ms(0.100), "kda_decode": ms(0.030)}}
    assert found["largest"][0] == ["prefill", "moe_part", "moe_gmm",
                                   "moe_gmm bf16[4096,768]", ms(0.150), 1]
    # the nested event: once in the part's seconds, twice in the group's
    assert ["prefill", "moe_part", "moe_combine", "fusion f32[4096,2048]",
            ms(0.080), 2] in found["largest"]
    # a row with a form and no part; rows with neither
    assert ["prefill", None, None, "copy f32[4096,2048]", ms(0.020), 1] \
        in found["largest"]
    assert [None, None, "reshape2", "fusion s32[1]", ms(0.002), 2] \
        in found["largest"]
    assert [None, None, None, "fusion f32[7,7]", ms(0.009), 1] \
        in found["largest"]


def test_a_key_is_ambiguous_only_where_the_steps_disagree(recorded):
    known = device_symbols.index(recorded["tables"])
    both = known[("fusion.3", "s32[1]")]
    assert both["labels"] == {"prefill", "decode"}
    assert both["parts"] == {"embed", "head"}
    # the decode step compiled twice: one label, one part
    twice = known[("mla_decode.1", "f32[128,32,512]")]
    assert twice["labels"] == {"decode"} and twice["parts"] == {"mla_part"}
    # one name, two shapes: two keys
    assert known[("moe_gmm.1", "bf16[4096,768]")]["labels"] == {"prefill"}
    assert known[("moe_gmm.1", "bf16[1024,768]")]["labels"] == {"decode"}
    assert known[("moe_gmm.1", "bf16[1024,768]")]["kernel"] == "moe_gmm"
    assert known[("fusion.10", "f32[1024,2048]")]["kernel"] is None
    # same part under two labels: the part stands, the form does not
    tables = [dict(t, program="chunk") if i == 2 else t
              for i, t in enumerate(recorded["tables"])]
    mixed = device_symbols.index(tables)[("mla_decode.1", "f32[128,32,512]")]
    assert mixed["labels"] == {"decode", "chunk"}
    assert mixed["parts"] == {"mla_part"}


def test_a_rows_key_is_the_tables(recorded):
    """The row's name and shape as ``trace.read_rows`` writes them give the
    key the program's table holds for the same instruction."""
    event = ("%fusion.11 = f32[4096,2048]{1,0:T(8,128)} fusion(f32[4096,512]"
             "{1,0} %copy.5, f32[512,2048]{1,0} %p.3), kind=kOutput")
    row = trace_lib.kind_and_shape(event) + "|" + trace_lib.short_name(event)
    assert device_symbols.key_of(row) == ("fusion.11", "f32[4096,2048]")
    assert device_symbols.key_of("moe_gmm bf16[1024,768]|moe_gmm.1") == \
        ("moe_gmm.1", "bf16[1024,768]")
    # an event that printed no shape
    assert device_symbols.key_of("slice-start|slice-start.12") == \
        ("slice-start.12", None)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_by_hand(name, as_the_program, reduction, capsys):
    as_the_program()
    record = {}
    assert _reader(name).read(record, reduction, None) == \
        pytest.approx(WANT[name])
    # one note line a run, however many readers ask
    assert _reader(name).read(record, reduction, None) == \
        pytest.approx(WANT[name])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert len(lines) == 1
    note = lines[0]["device_symbols"]
    assert [p["program"] for p in note["programs"]] == \
        ["prefill", "decode", "decode"]
    assert note["programs"][0]["instructions"] == 9
    assert note["by_program"][0] == ["prefill", pytest.approx(0.580)]
    assert note["by_part"][0] == ["moe_part", pytest.approx(0.275)]
    assert len(note["largest"]) <= device_symbols.TOP


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_has_nothing_to_read(name, as_the_program, recorded,
                                    reduction, monkeypatch, capsys):
    read = _reader(name).read
    # the parent's program offers no device_symbols
    as_the_program(None)
    assert read({}, reduction, None) is None
    # it offers them and noted nothing
    as_the_program([])
    assert read({}, reduction, None) is None
    # no device plane (the CPU rehearsal): the harness hands an empty
    # reduction and the program is not even asked
    monkeypatch.setattr(device_symbols, "program_tables",
                        lambda: pytest.fail("asked without a device plane"))
    assert read({}, {}, None) is None
    # a plane of another device alone
    as_the_program()
    host_only = [r for r in recorded["rows"]
                 if not r[0].startswith("/device:TPU:0")]
    assert device_symbols.analyse(
        host_only, (0, 10 ** 9), recorded["tables"])["busy_s"] == 1.0
    assert device_symbols.analyse(
        [r for r in host_only if not r[0].startswith("/device")],
        (0, 10 ** 9), recorded["tables"]) is None


def test_the_program_without_device_symbols(monkeypatch):
    """``program_tables`` itself: None where ``paddle_tpu.profiler`` has no
    ``device_symbols`` (the parent), the program's answer where it has."""
    from paddle_tpu import profiler

    monkeypatch.setattr(profiler, "device_symbols", lambda: ["tables"],
                        raising=False)
    assert device_symbols.program_tables() == ["tables"]
    monkeypatch.delattr(profiler, "device_symbols")
    assert device_symbols.program_tables() is None


def test_manifest_lists_the_readers():
    manifest = manifest_lib.load_manifest()
    assert manifest_lib.check(manifest) == []
    joyai = "joyai-llm-flash.long-prompt-backlog"
    kimi = "kimi-linear-48b-a3b.long-doc-backlog"
    new = {m["name"]: m for m in manifest["per_layer"]
           if manifest_lib.reader_of(m["name"]) in WANT}
    assert sorted(new) == sorted(
        [f"{n}.{c}" for n in WANT if n != "kda_part_device_pct"
         for c in ("joyai", "kimi")] + ["kda_part_device_pct"])
    for name, m in new.items():
        assert (m["unit"], m["source"], m["layer"], m["moves"]) == \
            ("%", "device_trace", "step execution", "serve_tokens_per_s")
        assert m["workloads"] == [joyai if name.endswith(".joyai") else kimi]
