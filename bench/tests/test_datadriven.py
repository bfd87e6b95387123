"""A configuration, a traffic mix and a per-layer metric are each added as
new files, found by the names in BENCHMARK.json, and run, with no file
that the benchmark already had edited: so are a cell of several replicas,
an open-loop mix with shared prefixes, and a model family of its own
(weights, work counts, reference and engine sizes)."""
import hashlib
import json
import shutil
from pathlib import Path

import pytest

from bench.tests import tiny

NEW_FAMILY = Path(__file__).parent / "newfamily"

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


# `mfu` counted by hand for the tiny MoE configuration below: 2 layers,
# d 256, 4 query and 2 KV heads of 64, top-2 of 4 experts of width 128,
# vocabulary 512; the dense width its file keeps (512) is not served
HAND_MFU = '''"""mfu of the tiny MoE configuration, counted by hand."""
L, D, V = 2, 256, 512
LAYER = (2 * 2 * D * 4 * 64        # q and o
         + 2 * 2 * D * 2 * 64      # k and v
         + 2 * D * 4               # the router over 4 experts
         + 2 * 3 * 2 * D * 128)    # gate, up and down of the 2 experts used
KEY = 4 * 4 * 64                   # q.k and p.v of 4 heads per key


def read(r):
    flops = 0
    for s in r.steps:
        for t in s.prefill_lens:
            flops += t * L * LAYER + L * KEY * t * (t + 1) // 2 + 2 * D * V
        for c in s.decode_ctx:
            flops += L * LAYER + L * KEY * c + 2 * D * V
    return 100.0 * flops / (r.window_s * r.peaks.flops_bf16 * r.chips)
'''


def _add_config(root, spec):
    name = spec["name"]
    (root / f"bench/configs/{name}.json").write_text(json.dumps(spec))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": name, "source": "test", "reduced": [],
                         "file": f"bench/configs/{name}.json", "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))


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


def test_new_family_runs_from_new_files(tmp_path, monkeypatch):
    root = tiny.make_root(tmp_path)
    before = _digest(root)
    shutil.copy(NEW_FAMILY / "tinymoe.py", root / "bench/families/tinymoe.py")
    shutil.copy(NEW_FAMILY / "moe_decoder.py",
                root / "bench/reference/moe_decoder.py")
    (root / "bench/metrics/hand_mfu.py").write_text(HAND_MFU)
    spec = tiny.tiny_spec(name="tiny-moe")
    # float32 weights: the served path then picks the reference's greedy
    # token at every position (widest gap 0.0 on 8 seeds of 8). In
    # bfloat16 the router's near ties pick other experts than the float32
    # reference does, and the widest gap reads 0.004-0.34 on the same
    # seeds: a limit for that is set from readings where the family is
    # served, not in this test of the harness.
    spec.update(torch_dtype="float32", family="tinymoe",
                reference="moe_decoder", num_experts=4,
                num_experts_per_tok=2, moe_intermediate_size=128,
                deployment=dict(spec["deployment"], max_num_seqs=4,
                                num_blocks=128))
    _add_config(root, spec)
    cell = "tiny-moe.mix"
    _add_cell(root, cell, "tiny-moe", "mix4", tiny.TINY_MIX, chips=1)
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["per_layer"].append({"name": "hand_mfu", "unit": "%",
                           "better": "higher", "source": "host_clock",
                           "layer": "model step", "moves": "output_tok_s",
                           "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    from repro.engine.executor import RealExecutor
    inner, pools = RealExecutor.__init__, []

    def init(self, *a, **kw):
        inner(self, *a, **kw)
        pools.append((self.pool["k"].shape[1], self.max_slots,
                      sorted(self.params["layers"])))

    monkeypatch.setattr(RealExecutor, "__init__", init)
    e2e = tiny.run(root, cell=cell, seconds=3.0)
    gap = e2e["compared"]["max_logit_gap"]
    assert e2e["correct"] and gap["tokens"] > 0, gap
    assert gap["value"] < tiny.LIMIT / 10, gap
    layer = tiny.run(root, cell=cell, seconds=3.0, trace=True)
    assert layer["correct"], layer["compared"]
    # one replica a run: 128 blocks and the padding rows' one, 4 rows
    assert pools == [(129, 4, ["attn", "ln1", "ln2", "moe"])] * 2
    m = {k: v["value"] for k, v in layer["metrics"].items()}
    # 4 clients fill at most half of 8 rows: more is counted against 4
    assert 50 < m["decode_rows_used"] <= 100
    assert m["mfu"] == pytest.approx(m["hand_mfu"], rel=1e-12)
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
