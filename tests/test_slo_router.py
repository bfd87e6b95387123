"""SLO classes end-to-end + the predictive SLO-cost router.

Unit tests drive `SLOCostRouter` against synthetic endpoint rows, scrape
snapshots and finished-request metrics (no control plane); wire tests
cover the `slo_class` field's strict 422 validation and round-trip;
integration tests reconcile a `routing_policy: slo_cost` deployment and
check the queue's class-aware ordering and the harness attainment metric.
"""
import math

import pytest

from repro import configs
from repro.api.errors import APIStatusError
from repro.api.schemas import ChatCompletionRequest, ChatMessage, \
    CompletionRequest
from repro.config import DEFAULT_SLO_TARGETS, SLO_CLASSES, ServiceConfig
from repro.core.controller import ClusterSpec, ControlPlane
from repro.core.router import GatewayQueue, SLOCostRouter, make_policy
from repro.engine.request import Request, SamplingParams

MODEL = "mistral-small-24b"


def eps(n):
    return [{"id": i + 1, "node": f"node{i:03d}", "port": 8000,
             "model_name": MODEL, "bearer_token": f"tok{i}",
             "ready_at": 1.0} for i in range(n)]


def req(n=16, out=4, slo="standard", prompt=None):
    r = Request(prompt_tokens=prompt if prompt is not None else [1] * n,
                sampling=SamplingParams(target_output_len=out,
                                        max_new_tokens=out))
    r.model = MODEL
    r.slo_class = slo
    return r


def finished(ttft, tbt, out=5):
    """A request carrying the metrics a real finish would: TTFT from
    arrival, TBT spread over out-1 decode steps."""
    r = req(out=out)
    r.metrics.arrival_time = 0.0
    r.metrics.first_token_time = ttft
    r.metrics.finish_time = ttft + tbt * (out - 1)
    r.output_tokens = list(range(out))
    return r


# ---------------------------------------------------------------------------
# wire: slo_class validation + round-trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cls", SLO_CLASSES)
def test_slo_class_accepted_and_stamped(cls):
    wire = ChatCompletionRequest(model=MODEL,
                                 messages=[ChatMessage("user", [1, 2])],
                                 slo_class=cls)
    wire.validate()
    assert wire.to_engine_request().slo_class == cls
    back = ChatCompletionRequest.from_dict(wire.to_dict())
    assert back == wire and back.to_dict()["slo_class"] == cls


@pytest.mark.parametrize("bad", ["gold", "", 3, None, "INTERACTIVE"])
def test_slo_class_rejected_with_422(bad):
    for wire in (ChatCompletionRequest(model=MODEL,
                                       messages=[ChatMessage("user", [1])],
                                       slo_class=bad),
                 CompletionRequest(model=MODEL, prompt=[1, 2],
                                   slo_class=bad)):
        with pytest.raises(APIStatusError) as ei:
            wire.validate()
        assert ei.value.status == 422
        assert ei.value.error.param == "slo_class"


def test_completion_from_engine_carries_slo_class():
    r = req(slo="batch")
    wire = CompletionRequest.from_engine(r, MODEL, stream=True)
    assert wire.slo_class == "batch"
    assert wire.to_engine_request().slo_class == "batch"


def test_default_slo_targets_golden():
    # interactive must be strictly tighter than standard, standard than
    # batch, on both targets — the ordering the queue and router assume
    for tight, loose in zip(SLO_CLASSES, SLO_CLASSES[1:]):
        assert DEFAULT_SLO_TARGETS[tight].ttft \
            < DEFAULT_SLO_TARGETS[loose].ttft
        assert DEFAULT_SLO_TARGETS[tight].e2el \
            < DEFAULT_SLO_TARGETS[loose].e2el
    assert set(DEFAULT_SLO_TARGETS) == set(SLO_CLASSES)
    assert set(ServiceConfig().slo_targets) == set(SLO_CLASSES)


# ---------------------------------------------------------------------------
# unit: SLOCostRouter scoring
# ---------------------------------------------------------------------------

def mk_router(load=None, prior=None, **kw):
    return SLOCostRouter(load_fn=lambda k: (load or {}).get(k, {}),
                         prior_fn=prior, **kw)


def test_cold_start_degrades_to_least_loaded():
    load = {("node000", 8000): {"time": 1.0, "num_waiting": 4,
                                "num_running": 2},
            ("node001", 8000): {"time": 1.0, "num_waiting": 0,
                                "num_running": 1}}
    pol = mk_router(load)          # no prior, no observations
    assert pol.select(eps(2), req())["id"] == 2


def test_prior_prices_queue_depth_without_observations():
    # equal scraped depth 1 vs 2: with a roofline prior the deeper queue
    # costs depth * tbt more even before any finish is observed
    load = {("node000", 8000): {"time": 1.0, "num_waiting": 2,
                                "num_running": 0},
            ("node001", 8000): {"time": 1.0, "num_waiting": 1,
                                "num_running": 0}}
    pol = mk_router(load, prior=lambda m, r: (0.5, 0.02))
    r = req(slo="interactive")
    assert pol.score(eps(2)[0], r) > pol.score(eps(2)[1], r)
    assert pol.select(eps(2), r)["id"] == 2


def test_observed_pace_beats_equal_depth():
    """The straggler case: equal queue depth, but endpoint 1's observed
    TTFT/TBT is 4x endpoint 2's — every class must prefer endpoint 2."""
    load = {k: {"time": 1.0, "num_waiting": 1, "num_running": 0}
            for k in [("node000", 8000), ("node001", 8000)]}
    pol = mk_router(load)
    for _ in range(4):
        pol.note_finish(("node000", 8000), finished(ttft=0.8, tbt=0.08))
        pol.note_finish(("node001", 8000), finished(ttft=0.2, tbt=0.02))
    for cls in SLO_CLASSES:
        assert pol.select(eps(2), req(slo=cls))["id"] == 2, cls
    est = pol.stats()["endpoint_estimates"]
    assert est["node000:8000"]["ttft_mean"] == pytest.approx(0.8)
    assert est["node001:8000"]["tbt_mean"] == pytest.approx(0.02)


def test_variance_penalty_only_binds_latency_sensitive_classes():
    """Same mean service time, but endpoint 1 is jittery: interactive
    (z=2) must avoid it; batch (z=0) is indifferent and falls back to the
    id tie-break, keeping the jittery endpoint utilised."""
    load = {k: {"time": 1.0, "num_waiting": 0, "num_running": 0}
            for k in [("node000", 8000), ("node001", 8000)]}
    pol = mk_router(load)
    for ttft in (0.1, 0.9, 0.1, 0.9, 0.1, 0.9):       # mean 0.5, jittery
        pol.note_finish(("node000", 8000), finished(ttft=ttft, tbt=0.02))
    for _ in range(6):                                # mean 0.5, steady
        pol.note_finish(("node001", 8000), finished(ttft=0.5, tbt=0.02))
    assert pol.select(eps(2), req(slo="interactive"))["id"] == 2
    assert pol.select(eps(2), req(slo="batch"))["id"] == 1
    r = req(slo="interactive")
    assert pol.score(eps(2)[0], r) > pol.score(eps(2)[1], r)


def test_kv_hit_rate_discount_windowed_between_scrapes():
    load = {("node000", 8000): {"time": 5.0, "num_waiting": 0,
                                "num_running": 0,
                                "prefix_queries_total": 100,
                                "prefix_hits_total": 90},
            ("node001", 8000): {"time": 5.0, "num_waiting": 0,
                                "num_running": 0,
                                "prefix_queries_total": 100,
                                "prefix_hits_total": 0}}
    pol = mk_router(load, prior=lambda m, r: (0.5, 0.02))
    assert pol._hit_rate(("node000", 8000)) == pytest.approx(0.9)
    # the hot-cache endpoint's prefill discount wins at equal depth/prior
    assert pol.select(eps(2), req(slo="interactive"))["id"] == 1
    # next scrape: endpoint 0 went cold (no new hits), 1 turned hot —
    # the WINDOWED rate must flip, not the lifetime ratio
    load[("node000", 8000)] = {"time": 10.0, "num_waiting": 0,
                               "num_running": 0,
                               "prefix_queries_total": 200,
                               "prefix_hits_total": 90}
    load[("node001", 8000)] = {"time": 10.0, "num_waiting": 0,
                               "num_running": 0,
                               "prefix_queries_total": 200,
                               "prefix_hits_total": 95}
    assert pol._hit_rate(("node000", 8000)) == pytest.approx(0.0)
    assert pol._hit_rate(("node001", 8000)) == pytest.approx(0.95)
    assert pol.select(eps(2), req(slo="interactive"))["id"] == 2
    # engine restart (counters reset): falls back to the cumulative ratio
    load[("node000", 8000)] = {"time": 15.0, "num_waiting": 0,
                               "num_running": 0,
                               "prefix_queries_total": 10,
                               "prefix_hits_total": 5}
    assert pol._hit_rate(("node000", 8000)) == pytest.approx(0.5)


def test_failed_request_contributes_no_signal():
    pol = mk_router()
    r = req()
    r.metrics.arrival_time = 0.0          # never produced a token
    pol.note_finish(("node000", 8000), r)
    assert pol.observations == 0 and pol.stats()["endpoint_estimates"] == {}


def test_make_policy_injects_prior_fn():
    prior = lambda m, r: (1.0, 0.1)
    pol = make_policy("slo_cost", load_fn=lambda k: {}, prior_fn=prior)
    assert isinstance(pol, SLOCostRouter) and pol.prior_fn is prior
    # non-cost policies must not receive the kwarg
    assert make_policy("round_robin", prior_fn=prior).name == "round_robin"


def test_ew_stat_matches_closed_form():
    from repro.core.router import _EWStat
    s = _EWStat()
    xs = [1.0, 3.0, 2.0, 4.0]
    s.update(xs[0], 0.5)
    mean, var = xs[0], 0.0
    for x in xs[1:]:
        d = x - mean
        mean += 0.5 * d
        var = 0.5 * (var + d * 0.5 * d)
        s.update(x, 0.5)
    assert s.mean == pytest.approx(mean)
    assert s.var == pytest.approx(var) and s.var > 0.0
    assert s.n == len(xs)


# ---------------------------------------------------------------------------
# unit: SLO-class-aware queue ordering
# ---------------------------------------------------------------------------

def test_queue_dequeues_interactive_before_batch():
    q = GatewayQueue(capacity=8, ttl=60.0)
    order = []
    disp = lambda r: (order.append(r.slo_class), 200)[1]
    q.offer(req(slo="batch"), MODEL, 0.0, dispatch=disp)
    q.offer(req(slo="standard"), MODEL, 1.0, dispatch=disp)
    q.offer(req(slo="interactive"), MODEL, 2.0, dispatch=disp)
    q.offer(req(slo="interactive"), MODEL, 3.0, dispatch=disp)
    q.drain(MODEL, 5.0, can_dispatch=lambda m: True)
    assert order == ["interactive", "interactive", "standard", "batch"]


def test_queue_priority_orders_within_slo_class():
    q = GatewayQueue(capacity=8, ttl=60.0)
    order = []
    disp = lambda r: (order.append((r.slo_class, r.priority)), 200)[1]
    lo, hi = req(slo="standard"), req(slo="standard")
    hi.priority = 5
    b = req(slo="batch")
    b.priority = 99                     # class outranks priority ints
    q.offer(b, MODEL, 0.0, dispatch=disp)
    q.offer(lo, MODEL, 1.0, dispatch=disp)
    q.offer(hi, MODEL, 2.0, dispatch=disp)
    q.drain(MODEL, 5.0, can_dispatch=lambda m: True)
    assert order == [("standard", 5), ("standard", 0), ("batch", 99)]


def test_displacement_evicts_batch_before_interactive():
    q = GatewayQueue(capacity=2, ttl=60.0,
                     weight_fn=lambda t: 1.0)
    dropped = []
    q.on_displaced = lambda item: dropped.append(item.req.slo_class)
    hog_i, hog_b = req(n=64, slo="interactive"), req(n=64, slo="batch")
    hog_i.tenant = hog_b.tenant = "hog"
    q.offer(hog_i, MODEL, 0.0, dispatch=lambda r: 200)
    q.offer(hog_b, MODEL, 1.0, dispatch=lambda r: 200)
    small = req(n=4, slo="interactive")
    small.tenant = "under"
    assert q.offer(small, MODEL, 2.0, dispatch=lambda r: 200)
    assert dropped == ["batch"]         # the victim's batch entry, not
    assert q.depth(MODEL) == 2          # its older interactive one


# ---------------------------------------------------------------------------
# harness: SLO attainment metric
# ---------------------------------------------------------------------------

def test_slo_attainment_counts_unfinished_as_misses():
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks.harness import ClientRecord, ClientRecorder

    rec = ClientRecorder()
    ok = ClientRecord(t_submit=0.0, t_first=1.0, t_last=5.0, n_tokens=5,
                      slo_class="interactive")
    late = ClientRecord(t_submit=0.0, t_first=3.0, t_last=5.0, n_tokens=5,
                        slo_class="interactive")          # TTFT > 2 s
    hung = ClientRecord(t_submit=0.0, slo_class="interactive")
    batch = ClientRecord(t_submit=0.0, t_first=30.0, t_last=200.0,
                         n_tokens=9, slo_class="batch")
    rec.records = dict(enumerate([ok, late, hung, batch]))
    assert ok.meets_slo() is True
    assert late.meets_slo() is False
    assert hung.meets_slo() is None     # no finish: scored as a miss
    att = rec.slo_attainment()
    assert att["slo_attainment_interactive"] == pytest.approx(1 / 3)
    assert att["slo_attainment_batch"] == 1.0
    assert "slo_attainment_standard" not in att
    assert att["ttft_p99_batch_ms"] == pytest.approx(30_000.0)
    # summary() reports attainment next to the p99s
    s = rec.summary()
    assert s["slo_attainment_interactive"] == att["slo_attainment_interactive"]
    assert "ttft_p99_ms" in s


# ---------------------------------------------------------------------------
# integration: slo_cost through the declarative control plane
# ---------------------------------------------------------------------------

def mk_plane(**kw):
    spec = ClusterSpec(num_nodes=kw.pop("num_nodes", 4),
                       gpus_per_node=kw.pop("gpus_per_node", 2),
                       max_num_seqs=16, num_blocks=512, block_size=16,
                       max_model_len=2048, **kw)
    cp = ControlPlane(spec)
    cp.add_tenant("uni", "sk-test")
    cp.register_model(configs.get(MODEL))
    return cp


def test_slo_cost_reconciles_through_deployment_spec():
    from repro.api.admin import AdminClient
    cp = mk_plane()
    admin = AdminClient(cp)
    admin.apply(model=MODEL, replicas=2, max_replicas=4,
                routing_policy="slo_cost", est_load_time=5.0)
    assert admin.wait(MODEL, "Ready", timeout=120.0)
    gw = cp.web_gateway
    router = gw.router_for(MODEL)
    assert router.name == "slo_cost"
    assert router.prior_fn is not None          # control-plane roofline
    for cls in ("interactive", "batch", "standard", "interactive"):
        assert gw.handle("sk-test", MODEL, req(out=2, slo=cls)) == 200
    cp.run_until(cp.loop.now + 60.0)
    st = gw.router_stats()["per_model"][MODEL]
    assert st["policy"] == "slo_cost"
    assert st["selections_by_class"]["interactive"] == 2
    assert st["observations"] >= 4              # finishes fed the estimators
    assert st["endpoint_estimates"]             # learned per-endpoint stats
    # the roofline prior is a sane (ttft, tbt) pair for this model
    prior = cp.roofline_prior(MODEL, req())
    assert prior is not None and prior[0] > 0.0 and prior[1] > 0.0
    assert cp.roofline_prior("no-such-model", req()) is None


def test_slo_cost_avoids_straggler_for_interactive():
    """End-to-end skew scenario in miniature: one of two engines runs at a
    quarter of nominal speed; after a warmup burst teaches the router each
    endpoint's pace, interactive requests concentrate on the fast chip."""
    import dataclasses
    from repro.engine.engine import LLMEngine
    from repro.engine.executor import SimExecutor

    spec = ClusterSpec(num_nodes=2, gpus_per_node=2, max_num_seqs=16,
                       num_blocks=512, block_size=16, max_model_len=2048,
                       services=ServiceConfig(routing_policy="slo_cost"))
    built = []

    def factory(cfg, tp, gpu):
        hw = spec.hardware
        if len(built) % 2:
            hw = dataclasses.replace(
                hw, name=hw.name + "-slow",
                peak_flops_bf16=hw.peak_flops_bf16 * 0.25,
                hbm_bandwidth=hw.hbm_bandwidth * 0.25,
                link_bandwidth=hw.link_bandwidth * 0.25)
        built.append(hw.name)
        ex = SimExecutor(cfg, hw, tp=tp)
        return LLMEngine(cfg, ex, num_blocks=spec.num_blocks,
                         block_size=spec.block_size,
                         max_num_seqs=spec.max_num_seqs,
                         max_model_len=spec.max_model_len)

    cp = ControlPlane(spec, engine_factory=factory, alert_rules=[])
    cp.add_tenant("uni", "sk-test")
    cp.add_model(configs.get(MODEL), instances=2, est_load_time=10.0)
    cp.run_until(120.0)
    assert len(cp.ready_endpoints(MODEL)) == 2
    gw = cp.web_gateway
    # warmup: let the router observe both endpoints' pace
    for i in range(8):
        assert gw.handle("sk-test", MODEL, req(n=128, out=8)) == 200
        cp.run_until(cp.loop.now + 4.0)
    router = gw.router_for(MODEL)
    est = router.stats()["endpoint_estimates"]
    assert len(est) == 2
    # measurement burst: interactive requests go to the faster endpoint
    before = dict(router.picks)
    fast_key = min(est, key=lambda k: est[k]["ttft_mean"])
    for _ in range(6):
        assert gw.handle("sk-test", MODEL,
                         req(n=128, out=4, slo="interactive")) == 200
        cp.run_until(cp.loop.now + 2.0)
    gained = {f"{n}:{p}": c - before.get((n, p), 0)
              for (n, p), c in router.picks.items()}
    assert gained.get(fast_key, 0) >= 5, (gained, est)
    cp.run_until(cp.loop.now + 120.0)
