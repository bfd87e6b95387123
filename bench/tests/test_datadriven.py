"""A configuration, a traffic mix and a per-layer metric are each added as
new files, found by the names in BENCHMARK.json, and run, with no file
that the benchmark already had edited: so are a cell of several replicas
and an open-loop mix with shared prefixes."""
import hashlib
import json

import pytest

from bench.tests import tiny

NEW_METRIC = '''"""Share of the window's engine calls that computed a prompt."""


def read(r):
    if not r.steps:
        return None
    return 100.0 * sum(bool(s.prefill_lens) for s in r.steps) / len(r.steps)
'''


def _digest(root):
    return {p.relative_to(root).as_posix():
            hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_config_mix_and_metric_run_from_new_files(tmp_path):
    root = tiny.make_root(tmp_path)
    before = _digest(root)

    spec = tiny.tiny_spec(name="tiny-mha", num_key_value_heads=4,
                          qk_norm=False, tie_word_embeddings=False)
    (root / "bench/configs/tiny-mha.json").write_text(json.dumps(spec))
    mix = dict(tiny.TINY_MIX, clients=2, pairs=[[24, 10], [20, 12]])
    (root / "bench/traffic/pairs.json").write_text(json.dumps(mix))
    (root / "bench/metrics/prefill_call_share.py").write_text(NEW_METRIC)
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-mha", "source": "test", "reduced": [],
                         "file": "bench/configs/tiny-mha.json",
                         "why": "test"})
    cell = "tiny-mha.pairs"
    b["workloads"].append({"name": cell, "config": "tiny-mha",
                           "traffic": "pairs", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "prefill_call_share", "unit": "%",
                           "better": "lower", "source": "host_clock",
                           "layer": "scheduler", "moves": "ttft_p95_s",
                           "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    e2e = tiny.run(root, cell=cell, seconds=3.0)
    assert set(e2e["metrics"]) == {"output_tok_s", "ttft_p95_s",
                                   "itl_p95_ms", "setup_s"}
    assert e2e["correct"] and e2e["attempted"] >= 2
    layer = tiny.run(root, cell=cell, seconds=3.0, trace=True)
    assert "prefill_call_share" in layer["metrics"]
    assert 0 < layer["metrics"]["prefill_call_share"]["value"] <= 100
    assert list(layer)[-4:] == ["compared", "_summary", "_records",
                                "_served"]

    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before


def _add_cell(root, cell, config, mix_name, mix, chips):
    (root / f"bench/traffic/{mix_name}.json").write_text(json.dumps(mix))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": cell, "config": config,
                           "traffic": mix_name, "chips": chips,
                           "why": "test"})
    for m in b["per_layer"]:
        m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(b))


OPEN_PREFIX = {"loop": "open", "rate": 6.0, "arrival_shape": 0.5,
               "catalog_seed": 3, "max_total": 1024,
               "pairs": [[40, 20], [48, 16], [56, 24], [44, 18]],
               "prefix": {"groups": 2, "tokens": 32, "sessions": True}}


def test_open_loop_shared_prefix_mix_on_two_replicas(tmp_path):
    root = tiny.make_root(tmp_path)
    before = _digest(root)
    cell = "tiny.open2"
    _add_cell(root, cell, "tiny", "open-prefix", OPEN_PREFIX, chips=2)
    e2e = tiny.run(root, cell=cell, seconds=3.0)
    assert e2e["correct"] and e2e["failed"] == 0
    # arrivals at 6/s for 3 s, bursty: about 18 sent whatever is in flight
    assert 8 <= e2e["attempted"] <= 40
    assert e2e["_summary"]["send_late_max_s"] >= 0
    served = dict(e2e["_served"])
    assert len(served) == 2 and all(n > 0 for n in served.values())
    layer = tiny.run(root, cell=cell, seconds=3.0, trace=True)
    assert layer["correct"]
    assert layer["metrics"]["gateway_ms"]["value"] > 0
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before


@pytest.mark.parametrize("what, match", [
    ("chips", "needs 8 chips"),
    ("deployment", "not honoured"),
    ("loop", "loop 'replay'"),
])
def test_what_the_harness_does_not_honour_is_refused(tmp_path, what, match):
    root = tiny.make_root(tmp_path)
    mix, chips, config = dict(tiny.TINY_MIX), 1, "tiny"
    if what == "chips":
        chips = 8
    elif what == "loop":
        mix["loop"] = "replay"
    else:
        spec = tiny.tiny_spec(name="tiny-dp")
        spec["deployment"] = {"replicas_per_chip": 2}
        (root / "bench/configs/tiny-dp.json").write_text(json.dumps(spec))
        b = json.loads((root / "BENCHMARK.json").read_text())
        b["configs"].append({"name": "tiny-dp", "source": "test",
                             "reduced": [], "why": "test",
                             "file": "bench/configs/tiny-dp.json"})
        (root / "BENCHMARK.json").write_text(json.dumps(b))
        config = "tiny-dp"
    _add_cell(root, "tiny.bad", config, "bad", mix, chips)
    with pytest.raises((ValueError, SystemExit), match=match):
        tiny.run(root, cell="tiny.bad", seconds=1.0)
