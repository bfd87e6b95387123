"""The qwen3-moe configuration's files: its family's work counts against
hand counts, the two expert-layer metrics on recorded readings, and the
served path against the plain reference
(bench/reference/qwen3_moe_decoder.py) at a small size in float32, through
the files as a copy of bench/ holds them."""
import json

import pytest

from bench import loader, model, work
from bench.tests import tiny

SPEC = model.load(loader.ROOT / "bench" / "configs" /
                  "qwen3-moe-30b-a3b.json")
D, F, E, K, V = 2048, 768, 128, 8, 151936


def _reader(name):
    return loader.load_module(tiny.ROOT / "bench" / "metrics" / f"{name}.py")


def test_family_counts_are_the_published_shapes():
    s = model.shape(SPEC)
    assert (s.num_layers, s.num_experts, s.experts_per_tok, s.moe_d_ff) \
        == (6, E, K, F)
    attn = 2 * 2 * D * 32 * 128 + 2 * 2 * D * 4 * 128   # q, o; k, v
    ffn = 2 * D * E + K * 3 * 2 * D * F                  # router, 8 experts
    assert s.ffn_flops() == ffn
    assert work.layer_matmul_flops(s) == attn + ffn
    # the file's dense width (6144) is served nowhere
    assert work.decode_flops(s, 100) == (6 * (attn + ffn)
                                         + 6 * 4 * 32 * 128 * 100
                                         + 2 * D * V)
    cfg = model.model_config(SPEC)
    assert (cfg.family, cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_d_ff, cfg.tie_embeddings) == ("moe", E, K, F, False)


def test_expert_work_by_hand():
    s = model.shape(SPEC)
    # 31 live rows of 8 experts over 6 layers, 600 of 768 pairs given a row
    flops, nbytes = s.expert_work(31 * 8 * 6, 600, 2)
    assert flops == 1488 * (2 * D * F + 2 * D * F + 2 * F * D)
    # the weights in bf16, each routed row in and out in float32
    assert nbytes == 600 * (D * F + D * F + F * D) * 2 + 1488 * (D + D) * 4


def test_family_refuses_a_program_that_drops_routed_rows(monkeypatch):
    """A serving expert layer with a capacity per expert (the training
    router's, here) is refused before anything is served; the program's
    own dropless layer is not."""
    from bench.families import qwen3_moe
    from repro.models import moe
    qwen3_moe.refuse_a_dropping_expert_layer()
    block = moe.moe_block
    monkeypatch.setattr(moe, "moe_block", lambda p, cfg, x, capacity_factor:
                        block(p, cfg, x, capacity_factor=1.0))
    with pytest.raises(SystemExit, match="drops routed rows"):
        model.model_config(SPEC)


@pytest.mark.parametrize("key, value", [
    ("norm_topk_prob", False), ("decoder_sparse_step", 2),
    ("mlp_only_layers", [0]), ("shared_expert_intermediate_size", 5632)])
def test_family_refuses_what_the_program_does_not_serve(key, value):
    with pytest.raises(ValueError, match=key):
        model.shape(dict(SPEC, **{key: value}))


class _Reading:
    def __init__(self, window_s, trace=None, shape=None):
        from bench.peaks import peaks_for
        self.window_s, self.trace, self.shape = window_s, trace, shape
        self.peaks = peaks_for("TPU v5 lite")
        self.q_bytes = 2


def _recorded(monkeypatch, routing):
    """A recorder holding one decode fetch span per (rows, active) of
    `routing`, 10 ms apart, the last ending at t = 100."""
    from repro.core import tracing
    rec = tracing.HostSpans()
    monkeypatch.setattr(tracing, "HOST_SPANS", rec)
    rec.start()
    t = 100.0 - 0.01 * len(routing)
    for attrs in routing:
        clock = iter([t, t + 0.005])
        monkeypatch.setattr(tracing, "host_clock", lambda: next(clock))
        with rec.span("executor.decode.fetch", rows=31) as span:
            if attrs:
                span.set(moe_rows=attrs[0], moe_active=attrs[1])
        t += 0.01
    return rec


def test_rows_per_expert_on_recorded_spans(monkeypatch):
    _recorded(monkeypatch, [(1488, 660), None, (1464, 650)])
    got = _reader("moe_rows_per_expert").read(_Reading(1.0))
    assert got == pytest.approx((1488 + 1464) / (660 + 650))


def test_expert_roofline_on_a_recorded_trace(monkeypatch):
    from bench.trace import TraceSummary
    _recorded(monkeypatch, [(1488, 660), (1464, 650)])
    s = model.shape(SPEC)
    spent = {"jit_decode_step:moe_experts": 0.004,
             "jit_prefill:moe_experts": 9.0,       # not the decode step's
             "jit_decode_step:paged_attention": 1.0}
    r = _Reading(1.0, TraceSummary(1.0, 0.5, 1, op_seconds=spent), s)
    flops, nbytes = s.expert_work(1488 + 1464, 660 + 650, 2)
    least = max(flops / 197e12, nbytes / 819e9)
    assert nbytes / 819e9 > flops / 197e12          # memory bound
    got = _reader("moe_experts_roofline").read(r)
    assert got == pytest.approx(100.0 * least / 0.004)


def test_expert_metrics_read_nothing_without_their_counters(monkeypatch):
    from bench.trace import TraceSummary
    _recorded(monkeypatch, [None, None])
    trace = TraceSummary(1.0, 0.5, 1,
                         op_seconds={"jit_decode_step:moe_experts": 0.1})
    dense = model.shape(tiny.tiny_spec())
    for r in (_Reading(1.0), _Reading(1.0, trace, model.shape(SPEC))):
        assert _reader("moe_rows_per_expert").read(r) is None
        assert _reader("moe_experts_roofline").read(r) is None
    _recorded(monkeypatch, [(1488, 660)])
    assert _reader("moe_experts_roofline").read(
        _Reading(1.0, trace, dense)) is None          # no expert layer
    assert _reader("moe_experts_roofline").read(
        _Reading(1.0, TraceSummary(1.0, 0.5, 1), model.shape(SPEC))) is None


def small_moe_spec(**over) -> dict:
    """The configuration file at a small size, its weights in bfloat16 as
    the file states: the program serves them in float32, so routing
    near-ties go the float32 reference's way."""
    spec = dict(SPEC, name="moe-small", hidden_size=128,
                moe_intermediate_size=64, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                vocab_size=512, num_experts=16, num_experts_per_tok=4,
                deployment=dict(SPEC["deployment"], max_num_seqs=4,
                                num_blocks=128),
                check={"max_logit_gap": tiny.LIMIT})
    spec.update(over)
    return spec


def test_moe_cell_served_against_the_reference(tmp_path):
    """The configuration's family and reference as bench/ holds them, run
    through the whole harness on the CPU: every run compares the served
    tokens with the float32 reference, and the traced run reads the
    routing counters of the decode step."""
    root = tiny.make_root(tmp_path)
    (root / "bench/configs/moe-small.json").write_text(
        json.dumps(small_moe_spec()))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "moe-small", "source": "test",
                         "reduced": [], "why": "test",
                         "file": "bench/configs/moe-small.json"})
    cell = "moe-small.mix"
    b["workloads"].append({"name": cell, "config": "moe-small",
                           "traffic": "tiny", "chips": 1, "why": "test"})
    b["per_layer"] = [
        {"name": name, "unit": "rows", "better": "higher",
         "source": "program_counter", "layer": "expert layer",
         "moves": "output_tok_s", "workloads": [cell]}
        for name in ("moe_rows_per_expert", "decode_rows_used")]
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    e2e = tiny.run(root, cell=cell, seconds=3.0)
    gap = e2e["compared"]["max_logit_gap"]
    assert e2e["correct"] and gap["tokens"] > 0, gap
    assert gap["value"] < tiny.LIMIT / 10, gap
    line = tiny.run(root, cell=cell, seconds=3.0, trace=True)
    assert line["correct"], line["compared"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # at most 4 live rows of 4 experts each over 16 experts a layer: each
    # expert given a row shares it with fewer than 4 others
    assert 1.0 <= m["moe_rows_per_expert"] <= 4.0
