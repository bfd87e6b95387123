"""Distributed request tracing (repro.core.tracing + repro.api.traces).

Unit tests cover the span/trace primitives, critical-path extraction and
the head-sampling + retention policy; integration tests drive real
unified and disaggregated planes on the virtual clock and assert the
recorded span trees, the per-hop `local_queue_time` satellite, the
MetricsGateway histogram fold and the AdminClient trace verbs."""
import pytest

from repro import configs
from repro.api import AdminClient, ServingClient
from repro.config import SLOTarget, ServiceConfig
from repro.core.controller import ClusterSpec, ControlPlane
from repro.core.deployments import ModelDeploymentSpec
from repro.core.disagg import DisaggregationSpec
from repro.core.tracing import (COMPUTE_KINDS, HostSpans, RequestTrace,
                                SPAN_KINDS, Tracer, critical_path,
                                head_sampled, host_clock)

MODEL = "smollm-135m"


# ---------------------------------------------------------------------------
# unit: span / trace primitives
# ---------------------------------------------------------------------------

def test_span_close_is_idempotent_first_close_wins():
    tr = RequestTrace("trace-1", 0.0)
    s = tr.start_span("gateway.auth", 1.0, cache_hit=True)
    s.close(2.0, status="ok", extra=1)
    s.close(9.0, status="error")          # must not clobber
    assert s.end == 2.0 and s.status == "ok" and s.attrs["extra"] == 1
    assert s.duration == 1.0


def test_close_span_targets_newest_open_and_noops_when_absent():
    tr = RequestTrace("trace-1", 0.0)
    a = tr.start_span("engine.queue", 1.0)
    b = tr.start_span("engine.queue", 2.0)     # second hop
    assert tr.close_span("engine.queue", 3.0) is b
    assert tr.close_span("engine.queue", 4.0) is a
    assert tr.close_span("engine.queue", 5.0) is None
    assert tr.close_span("router.select", 5.0) is None
    assert a.end == 4.0 and b.end == 3.0


def test_interrupt_marks_open_spans_as_errors_reruns_are_siblings():
    tr = RequestTrace("trace-1", 0.0)
    tr.start_span("router.select", 0.0).close(0.1)
    tr.start_span("engine.prefill", 0.1)
    tr.interrupt(5.0, "instance_lost")
    dead = [s for s in tr.spans if s.status == "error"]
    assert [s.name for s in dead] == ["engine.prefill"]
    assert dead[0].attrs["reason"] == "instance_lost"
    assert tr.root.end is None            # the request itself lives on
    # the re-run appears NEXT TO the interrupted hop, not instead of it
    tr.start_span("engine.prefill", 5.0).close(7.0)
    assert [s.name for s in tr.spans].count("engine.prefill") == 2


def test_finish_force_closes_leftovers_and_detaches_stragglers():
    tr = RequestTrace("trace-1", 0.0)
    tr.start_span("gateway.queue", 0.0)
    tr.finish(3.0, status="error")
    leak = next(s for s in tr.spans if s.name == "gateway.queue")
    assert leak.end == 3.0 and leak.attrs.get("force_closed") is True
    n = len(tr.spans)
    late = tr.start_span("stream.emit", 4.0)   # after terminal close
    assert late.span_id == -1 and len(tr.spans) == n


def test_span_kinds_vocabulary_is_closed():
    assert set(COMPUTE_KINDS) < set(SPAN_KINDS)
    assert "request" in SPAN_KINDS and "kv.handoff.chunk" in SPAN_KINDS


# ---------------------------------------------------------------------------
# unit: critical path
# ---------------------------------------------------------------------------

def test_critical_path_walks_the_gating_chain():
    tr = RequestTrace("trace-1", 0.0)
    tr.start_span("gateway.auth", 0.0).close(2.0)
    tr.start_span("engine.prefill", 2.0).close(7.0)
    # overlapped span: ran concurrently, never gated the tail
    tr.start_span("kv.handoff", 3.0).close(6.0)
    tr.start_span("engine.decode", 7.0).close(10.0)
    tr.finish(10.0)
    path = critical_path(tr)
    assert [s.name for s in path] == \
        ["gateway.auth", "engine.prefill", "engine.decode"]
    assert sum(s.duration for s in path) == tr.root.duration == 10.0


def test_critical_path_uses_leaf_spans_not_parents():
    tr = RequestTrace("trace-1", 0.0)
    par = tr.start_span("kv.handoff", 0.0)
    tr.start_span("kv.handoff.chunk", 0.0, parent=par).close(2.0)
    tr.start_span("kv.handoff.chunk", 2.0, parent=par).close(4.0)
    par.close(4.0)
    tr.start_span("engine.decode", 4.0).close(9.0)
    tr.finish(9.0)
    names = [s.name for s in critical_path(tr)]
    assert "kv.handoff" not in names          # represented by its chunks
    assert names == ["kv.handoff.chunk", "kv.handoff.chunk",
                     "engine.decode"]


def test_critical_path_empty_for_bare_trace():
    tr = RequestTrace("trace-1", 0.0)
    tr.finish(1.0)
    assert critical_path(tr) == []


# ---------------------------------------------------------------------------
# unit: sampling + retention (duck-typed request/stream)
# ---------------------------------------------------------------------------

class FakeMetrics:
    def __init__(self, arrival=0.0, finish=1.0, ttft=0.1):
        self.arrival_time = arrival
        self.finish_time = finish
        self.ttft = ttft
        self.preemptions = 0
        self.kv_transfer_time = 0.0


class FakeReq:
    _next = 0

    def __init__(self, tenant=None, slo_class="standard", finish=1.0):
        FakeReq._next += 1
        self.request_id = FakeReq._next
        self.trace = None
        self.metrics = FakeMetrics(finish=finish)
        self.tenant = tenant
        self.slo_class = slo_class
        self.model = MODEL
        self.disagg_retries = 0
        self.output_len = 4


class FakeStream:
    def __init__(self, error=None):
        self.error = error
        self.transport_delay = 0.0
        self.events = []


def _run_request(tracer, tenant=None, slo_class="standard", error=None):
    req = FakeReq(tenant=tenant, slo_class=slo_class)
    tracer.begin(req, 0.0)
    tracer.finish(req, FakeStream(error=error), 1.0)
    return req


def test_retention_is_bounded_oldest_evicted():
    tracer = Tracer(ServiceConfig(trace_max_retained=4))
    reqs = [_run_request(tracer) for _ in range(10)]
    assert len(tracer.traces) == 4
    kept = list(tracer.traces)
    assert kept == [r.trace.trace_id for r in reqs[-4:]]
    assert tracer.stats()["retained"] == 10    # total ever retained


def test_rate_zero_drops_ok_but_always_keeps_errors_and_slo_misses():
    svc = ServiceConfig(
        trace_sample_rate=0.0,
        slo_targets={"interactive": SLOTarget(ttft=1e-9, e2el=1e-9)})
    tracer = Tracer(svc)
    ok = _run_request(tracer)
    assert ok.trace.trace_id not in tracer.traces
    assert tracer.sampled_out == 1

    class Err:
        code = "instance_lost"
    bad = _run_request(tracer, error=Err())
    assert bad.trace.trace_id in tracer.traces
    assert bad.trace.root.status == "error"
    assert bad.trace.root.attrs["error"] == "instance_lost"

    miss = _run_request(tracer, slo_class="interactive")
    assert miss.trace.trace_id in tracer.traces
    assert miss.trace.root.attrs["slo_miss"] is True
    assert tracer.slo_miss_total == 1


def test_per_tenant_sample_rate_override():
    svc = ServiceConfig(trace_sample_rate=0.0,
                        tenant_trace_sample_rates={"vip": 1.0})
    tracer = Tracer(svc)
    vip = _run_request(tracer, tenant="vip")
    std = _run_request(tracer, tenant="steerage")
    assert vip.trace.trace_id in tracer.traces
    assert std.trace.trace_id not in tracer.traces


def test_head_sampling_is_a_pure_function_of_the_trace_id():
    assert head_sampled("trace-00000001", 1.0) is True
    assert head_sampled("trace-00000001", 0.0) is False
    ids = [f"trace-{i:08d}" for i in range(2000)]
    picked = [tid for tid in ids if head_sampled(tid, 0.3)]
    assert picked == [tid for tid in ids if head_sampled(tid, 0.3)]
    assert 0.2 < len(picked) / len(ids) < 0.4


def test_disabled_tracer_records_nothing():
    tracer = Tracer(ServiceConfig(tracing_enabled=False))
    req = FakeReq()
    assert tracer.begin(req, 0.0) is None
    assert req.trace is None
    tracer.finish(req, FakeStream(), 1.0)   # must be a no-op
    assert tracer.stats() == {"enabled": False, "started": 0,
                              "finished": 0, "retained": 0, "resident": 0,
                              "sampled_out": 0, "errors": 0,
                              "slo_misses": 0}


def test_fold_drains_histograms_and_exemplars():
    svc = ServiceConfig(
        slo_targets={"interactive": SLOTarget(ttft=1e-9, e2el=1e-9)})
    tracer = Tracer(svc)
    for _ in range(3):
        _run_request(tracer)
    miss = _run_request(tracer, slo_class="interactive")
    out = tracer.fold(MODEL)
    assert out["span_request_count"] == 4
    assert out["span_request_p50_ms"] == pytest.approx(1000.0)
    assert {"span_request_p95_ms", "span_request_p99_ms",
            "span_stream.emit_p50_ms"} <= set(out)
    assert out["slo_miss_count"] == 1
    assert out["slo_miss_exemplars"] == [miss.trace.trace_id]
    # the fold DRAINS: a second scrape of a quiet window carries nothing
    assert tracer.fold(MODEL) == {}


def test_watchers_see_retained_traces_only():
    svc = ServiceConfig(trace_sample_rate=0.0,
                        tenant_trace_sample_rates={"vip": 1.0})
    tracer = Tracer(svc)
    seen = []
    tracer.watch(seen.append)
    _run_request(tracer, tenant="steerage")
    vip = _run_request(tracer, tenant="vip")
    assert [t.trace_id for t in seen] == [vip.trace.trace_id]
    tracer.unwatch(seen.append)
    _run_request(tracer, tenant="vip")
    assert len(seen) == 1


# ---------------------------------------------------------------------------
# integration: real planes on the virtual clock
# ---------------------------------------------------------------------------

def plane(services=None, **cluster_kw):
    cp = ControlPlane(ClusterSpec(num_nodes=4,
                                  services=services or ServiceConfig(),
                                  **cluster_kw),
                      alert_rules=[])
    cp.add_tenant("t", "sk-test")
    cp.register_model(configs.get(MODEL))
    return cp


def unified_plane(services=None):
    cp = plane(services=services)
    AdminClient(cp).apply(ModelDeploymentSpec(
        model=MODEL, replicas=1, max_replicas=2, est_load_time=5.0))
    cp.run_until(120.0)
    return cp


def disagg_plane(services=None, transfer_bandwidth=1e9):
    cp = plane(services=services)
    AdminClient(cp).apply(ModelDeploymentSpec(
        model=MODEL, replicas=2, max_replicas=4, est_load_time=5.0,
        disaggregation=DisaggregationSpec(
            prefill_replicas=1, decode_replicas=1,
            max_prefill_replicas=2, max_decode_replicas=2,
            transfer_bandwidth=transfer_bandwidth)))
    cp.run_until(120.0)
    return cp


def complete_one(cp, prompt_len=120, out=8):
    client = ServingClient(cp, api_key="sk-test")
    pending = client.completions(model=MODEL,
                                 prompt=list(range(1, prompt_len + 1)),
                                 max_tokens=out, target_output_len=out)
    resp = pending.result(max_wait=600.0)
    assert resp.choices[0].finish_reason == "length"
    return pending.request


def test_unified_request_span_tree():
    cp = unified_plane()
    req = complete_one(cp)
    tr = req.trace
    assert tr is not None and tr.finished
    names = [s.name for s in tr.spans]
    # no gateway.queue span: the request forwarded directly without ever
    # being held in the WFQ queue — an absent hop, not a zero-length one
    assert names == ["request", "gateway.auth", "router.select",
                     "engine.queue", "engine.prefill", "engine.decode",
                     "stream.emit"]
    assert all(s.end is not None and s.end >= s.start for s in tr.spans)
    # flat tree: every hop hangs off the root
    root = tr.root
    assert all(s.parent_id == root.span_id
               for s in tr.spans if s is not root)
    assert root.attrs["tenant"] == "t"
    assert root.attrs["model"] == MODEL
    assert root.attrs["slo_miss"] is False
    # the path tiles the root exactly (no untraced gaps)
    path = cp.tracer.critical_path(tr)
    total = sum(s.duration for s in path)
    assert total == pytest.approx(root.duration, rel=1e-6)
    assert tr.trace_id in cp.tracer.traces


def test_disagg_two_hop_span_tree_with_handoff_chunks():
    cp = disagg_plane()
    req = complete_one(cp, prompt_len=200, out=12)
    tr = req.trace
    assert tr is not None and tr.finished
    by_name = {}
    for s in tr.spans:
        by_name.setdefault(s.name, []).append(s)
    # one router/queue hop per phase, each labelled with its hop
    assert [s.attrs["hop"] for s in by_name["router.select"]] == \
        ["prefill", "decode"]
    assert [s.attrs["phase"] for s in by_name["engine.queue"]] == \
        ["prefill", "decode"]
    assert len(by_name["engine.prefill"]) == 1
    assert len(by_name["engine.decode"]) == 1
    # the KV payload rode the contended link as chunk children
    handoff = by_name["kv.handoff"][0]
    chunks = by_name["kv.handoff.chunk"]
    assert chunks and all(c.parent_id == handoff.span_id for c in chunks)
    assert handoff.attrs["chunks"] == len(chunks)
    assert sum(c.attrs["bytes"] for c in chunks) == \
        pytest.approx(handoff.attrs["bytes"])
    assert handoff.end == pytest.approx(max(c.end for c in chunks))
    assert "force_closed" not in handoff.attrs
    # path still tiles the root despite the two-hop handoff
    path = cp.tracer.critical_path(tr)
    total = sum(s.duration for s in path)
    assert total == pytest.approx(tr.root.duration, rel=1e-6)


def test_local_queue_time_measures_the_last_hop_only():
    cp = disagg_plane()
    req = complete_one(cp, prompt_len=200, out=12)
    m = req.metrics
    # the decode hop re-enqueued the request after the KV transfer, so
    # the per-hop wait must be measured from the RE-enqueue, not arrival
    assert m.last_enqueue_time is not None
    assert m.last_enqueue_time > m.arrival_time
    assert m.last_scheduled_time is not None
    assert m.local_queue_time is not None and m.local_queue_time >= 0.0
    global_wait = m.last_scheduled_time - m.arrival_time
    assert m.local_queue_time < global_wait   # prefill + transfer excluded
    # the engine.queue spans record exactly the per-hop waits
    tr = req.trace
    decode_queue = [s for s in tr.spans if s.name == "engine.queue"
                    and s.attrs.get("phase") == "decode"][-1]
    assert decode_queue.duration == pytest.approx(m.local_queue_time)


def test_scheduler_queue_signal_uses_the_local_hop_wait():
    from repro.engine.engine import LLMEngine
    from repro.engine.executor import SimExecutor
    from repro.engine.request import Request, SamplingParams
    from repro.config import GPU_H100
    cfg = configs.get(MODEL)
    eng = LLMEngine(cfg, SimExecutor(cfg, GPU_H100), num_blocks=64,
                    block_size=16, max_num_seqs=4, max_prefill_tokens=256,
                    max_model_len=2048)
    r = Request(prompt_tokens=list(range(1, 40)),
                sampling=SamplingParams(target_output_len=4,
                                        max_new_tokens=4))
    r.metrics.arrival_time = 0.0
    r.metrics.last_enqueue_time = 50.0       # decode hop re-enqueue
    eng.scheduler.add_request(r, 50.0)
    # the autoscaling signal must report the 2 s LOCAL wait, not the 52 s
    # since global arrival — otherwise every handoff looks like backlog
    assert eng.scheduler.queue_time_of_head(52.0) == pytest.approx(2.0)


def test_metrics_gateway_folds_span_histograms_into_series():
    cp = unified_plane()
    complete_one(cp)
    cp.run_until(cp.loop.now + 30.0)          # let a scrape cycle run
    mg = cp.metrics_gateway
    cfg_id = next(iter(mg.history))
    series = mg.series(cfg_id, "span_request_p50_ms", 0.0)
    assert series and series[-1][1] > 0.0
    assert mg.series(cfg_id, "span_engine.decode_p95_ms", 0.0)
    # fold keys appear only in windows that saw finishes — later quiet
    # samples simply lack them, and series() skips those
    counts = [v for _, v in mg.series(cfg_id, "span_request_count", 0.0)]
    assert sum(counts) == 1


def test_metrics_history_stays_bounded_by_the_window():
    cp = unified_plane()
    mg = cp.metrics_gateway
    cp.run_until(cp.loop.now + 4 * mg.history_window)
    for series in list(mg.history.values()) + \
            list(mg.tenant_history.values()):
        assert series, "scrapes should have accumulated"
        ts = [t for t, _ in series]
        assert ts == sorted(ts)
        assert ts[-1] - ts[0] <= mg.history_window


def test_admin_trace_verbs_and_watch():
    cp = unified_plane()
    admin = AdminClient(cp)
    watch = admin.watch_traces()
    got = []
    watch.subscribe(got.append)
    req = complete_one(cp)
    tid = req.trace.trace_id

    rows = admin.traces(model=MODEL)
    assert [r["trace_id"] for r in rows] == [tid]
    assert rows[0]["slo_miss"] is False and rows[0]["error"] is None
    assert admin.traces(model="nope") == []
    assert admin.traces(slo_miss=True) == []

    full = admin.trace(tid)
    assert full["trace_id"] == tid
    assert [s["name"] for s in full["spans"]][0] == "request"
    assert admin.trace("trace-99999999") is None

    cp_dict = admin.trace_critical_path(tid)
    assert cp_dict["coverage"] == pytest.approx(1.0)
    assert cp_dict["path_duration"] == pytest.approx(cp_dict["e2el"])
    assert [seg["name"] for seg in cp_dict["segments"]][-1] == \
        "stream.emit"

    assert [t.trace_id for t in watch.traces] == [tid]
    assert got and got[0].trace_id == tid
    watch.stop()
    complete_one(cp)
    assert len(watch.traces) == 1             # unsubscribed on stop


def test_admin_without_tracer_raises():
    cp = unified_plane()
    admin = AdminClient(cp.reconciler)        # bare reconciler: no tracer
    with pytest.raises(TypeError):
        admin.traces()


def test_tracing_disabled_plane_serves_identically_with_no_traces():
    cp = unified_plane(services=ServiceConfig(tracing_enabled=False))
    req = complete_one(cp)
    assert req.trace is None
    assert cp.tracer.stats()["started"] == 0
    assert len(cp.tracer.traces) == 0


# ---------------------------------------------------------------------------
# unit: host spans (the host-clock recorder)
# ---------------------------------------------------------------------------

def test_host_spans_off_return_the_shared_noop_and_record_nothing():
    rec = HostSpans()
    a = rec.span("engine.step", replica=0)
    b = rec.span("executor.prefill", replica=0, tokens=9)
    assert a is b                       # one shared object, nothing made
    with a as sp:
        sp.set(rows=8)
    rec.stamp(1, "enqueue", replica=0)
    assert not rec.on
    assert rec.drain() == ([], [])


def test_host_spans_nest_and_record_their_parent():
    rec = HostSpans()
    rec.start()
    t0 = host_clock()
    with rec.span("engine.step", replica=3) as outer:
        with rec.span("executor.decode.dispatch", replica=3) as inner:
            inner.set(rows=8)
        with rec.span("engine.tokens", replica=3):
            rec.stamp(7, "admit", replica=3)
        outer.set(kind="mixed")
    t1 = host_clock()
    spans, stamps = rec.drain()
    by = {s.name: s for s in spans}
    step = by["engine.step"]
    assert [s.name for s in spans] == ["executor.decode.dispatch",
                                       "engine.tokens", "engine.step"]
    assert step.parent is None
    assert by["executor.decode.dispatch"].parent == step.span_id
    assert by["engine.tokens"].parent == step.span_id
    assert by["executor.decode.dispatch"].attrs == {"replica": 3, "rows": 8}
    assert step.attrs == {"replica": 3, "kind": "mixed"}
    assert t0 <= step.start <= by["engine.tokens"].start
    assert by["engine.tokens"].end <= step.end <= t1
    assert sum(s.duration for s in spans[:2]) <= step.duration
    (st,) = stamps
    assert st.request_id == 7 and st.event == "admit"
    assert by["engine.tokens"].start <= st.t <= by["engine.tokens"].end
    rec.stop()
    assert rec.span("engine.step") is rec.span("engine.tokens")


def test_host_spans_drain_clears_and_the_buffers_are_bounded():
    rec = HostSpans(capacity=4)
    rec.start()
    for i in range(6):
        with rec.span("engine.step", i=i):
            pass
        rec.stamp(i, "enqueue")
    assert rec.dropped == 4             # two spans and two stamps fell off
    spans, stamps = rec.drain()
    assert [s.attrs["i"] for s in spans] == [2, 3, 4, 5]
    assert [s.request_id for s in stamps] == [2, 3, 4, 5]
    assert rec.drain() == ([], []) and rec.dropped == 0
