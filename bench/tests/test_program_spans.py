"""The per-layer metrics read from the program's own host spans and
stamps (bench/program_spans.py): their arithmetic on recorded spans, their
silence on a program without them, a traced run that prints them, and the
spans' place in a JAX profile, inside the benchmark's window."""
import json

import pytest

from bench import loader
from bench.tests import tiny

METRICS = ("queue_wait_ms", "prefill_ms", "decode_dispatch_ms",
           "engine_host_ms")


def _reader(name):
    return loader.load_module(tiny.ROOT / "bench" / "metrics" / f"{name}.py")


class _Reading:
    def __init__(self, window_s):
        self.window_s = window_s


def _recorded(monkeypatch):
    """A recorder holding two engine calls of a 2 s window, which ends as
    the second call returns (t = 101.15), and requests stamped before it
    and in it."""
    from repro.core import tracing
    rec = tracing.HostSpans()
    monkeypatch.setattr(tracing, "HOST_SPANS", rec)
    clock = iter([])
    monkeypatch.setattr(tracing, "host_clock", lambda: next(clock))
    rec.start()

    def call(times, prefill):
        nonlocal clock
        clock = iter(times)
        with rec.span("engine.step"):
            with rec.span("engine.schedule"):
                pass
            if prefill:
                with rec.span("executor.prefill"):
                    pass
            with rec.span("executor.decode.dispatch"):
                pass
            with rec.span("executor.decode.fetch"):
                pass

    # each span's start and end in the order they are read: the calls'
    # executor spans take 302 and 120 ms, their own time 10 and 30 ms
    call([100.0, 100.001, 100.003, 100.003, 100.203, 100.203, 100.303,
          100.303, 100.305, 100.312], prefill=True)
    call([101.0, 101.001, 101.002, 101.002, 101.102, 101.102, 101.122,
          101.150], prefill=False)
    for rid, event, t in ((1, "enqueue", 99.0),     # before the window
                          (1, "admit", 100.5),
                          (2, "enqueue", 100.1), (2, "admit", 100.4),
                          (3, "enqueue", 100.9),    # never admitted
                          (4, "enqueue", 101.0), (4, "admit", 101.1)):
        clock = iter([t])
        rec.stamp(rid, event)
    return _Reading(2.0)


def test_readers_on_recorded_spans(monkeypatch):
    r = _recorded(monkeypatch)
    got = {m: _reader(m).read(r) for m in METRICS}
    assert got["prefill_ms"] == pytest.approx(200.0)
    assert got["decode_dispatch_ms"] == pytest.approx((100 + 100) / 2)
    # engine.step less its executor spans: 312 - 302 and 150 - 120 ms
    assert got["engine_host_ms"] == pytest.approx((10 + 30) / 2)
    # waits 300 ms, 250 ms of age at the window's end, and 100 ms
    import numpy as np
    assert got["queue_wait_ms"] == pytest.approx(
        np.percentile([300.0, 250.0, 100.0], 95))


def test_readers_read_nothing_without_program_spans(monkeypatch):
    from repro.core import tracing
    r = _Reading(10.0)
    monkeypatch.setattr(tracing, "HOST_SPANS", tracing.HostSpans())
    assert [_reader(m).read(r) for m in METRICS] == [None] * 4
    monkeypatch.delattr(tracing, "HOST_SPANS")      # the program before them
    assert [_reader(m).read(r) for m in METRICS] == [None] * 4


def test_traced_run_prints_the_program_metrics(tmp_path):
    from repro.core.tracing import HOST_SPANS
    root = tiny.make_root(tmp_path)
    HOST_SPANS.drain()
    e2e = tiny.run(root, seconds=3.0)
    assert e2e["correct"]
    assert HOST_SPANS.drain() == ([], [])     # an untraced run records none
    line = tiny.run(root, seconds=4.0, trace=True)
    assert line["correct"]
    got = {m: line["metrics"][m]["value"] for m in METRICS}
    print(json.dumps(got))
    assert all(v > 0 for v in got.values())
    assert got["decode_dispatch_ms"] <= line["metrics"]["decode_step_ms"][
        "value"]
    assert got["queue_wait_ms"] < 4000


def test_four_replicas_each_stamp_and_span(tmp_path):
    """More clients than the four replicas' rows: requests wait at each
    engine, and every replica's spans carry its own device."""
    from repro.core.tracing import HOST_SPANS
    root = tiny.make_root(tmp_path)
    mix = dict(tiny.TINY_MIX, clients=40)
    (root / "bench/traffic/x4.json").write_text(json.dumps(mix))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "tiny.x4", "config": "tiny",
                           "traffic": "x4", "chips": 4, "why": "test"})
    for m in b["per_layer"]:
        m["workloads"].append("tiny.x4")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    HOST_SPANS.drain()
    line = tiny.run(root, cell="tiny.x4", seconds=20.0, trace=True)
    assert line["correct"]
    served = dict(line["_served"])
    assert len(served) == 4 and all(n > 0 for n in served.values())
    spans, stamps = HOST_SPANS.drain()
    assert {s.attrs["replica"] for s in spans} == set(served)
    assert {s.attrs["replica"] for s in stamps} == set(served)
    assert all(m in line["metrics"] for m in METRICS)
    assert line["metrics"]["queue_wait_ms"]["value"] > 0


def test_profile_holds_program_spans_inside_the_window(tmp_path):
    """A JAX profile captured on the CPU: the program's spans are
    `repro.` annotations on the benchmark's thread, inside `bench.window`,
    the executor's nested in the engine call's."""
    import jax
    from bench import trace
    from repro import configs
    from repro.config import TPU_V5E
    from repro.core.tracing import HOST_SPANS
    from repro.engine.engine import LLMEngine
    from repro.engine.executor import RealExecutor
    from repro.engine.request import Request, SamplingParams
    from repro.models import api

    cfg = configs.get("qwen3-1.7b").reduced()
    params, _ = api.init_params(cfg, jax.random.key(3))
    ex = RealExecutor(cfg, params, num_blocks=32, block_size=16, hw=TPU_V5E,
                      max_model_len=128, max_slots=2, backend="ref")
    eng = LLMEngine(cfg, ex, num_blocks=32, block_size=16, max_num_seqs=2,
                    max_prefill_tokens=64, max_model_len=128)
    eng.add_request(Request(prompt_tokens=list(range(1, 12)),
                            sampling=SamplingParams(temperature=0.0,
                                                    max_new_tokens=3)), 0.0)
    HOST_SPANS.drain()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            while eng.has_work():
                eng.step(0.0)
    finally:
        jax.profiler.stop_trace()
    assert not HOST_SPANS.on
    recorded, _ = HOST_SPANS.drain()
    assert {s.name for s in recorded} >= {"engine.step", "executor.prefill",
                                          "executor.decode.dispatch"}

    profile = trace.load(str(tmp_path))
    lines = [list(trace._events(line)) for plane in profile.planes
             if plane.name.startswith("/host:") for line in plane.lines]
    (thread,) = [ev for ev in lines if any(n == trace.WINDOW
                                           for n, _, _ in ev)]
    (_, lo, hi), = [e for e in thread if e[0] == trace.WINDOW]
    steps = [e for e in thread if e[0] == "repro.engine.step"]
    dispatch = [e for e in thread if e[0] == "repro.executor.decode.dispatch"]
    assert len(steps) == 3 and len(dispatch) == 2
    assert all(lo <= s and e <= hi for _, s, e in steps + dispatch)
    assert all(any(s0 <= s and e <= e0 for _, s0, e0 in steps)
               for _, s, e in dispatch)
