"""Engine integration tests: paged generation vs dense oracle, preemption,
prefix caching, mixed-batch scheduling, metrics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.config import TPU_V5E
from repro.engine.engine import LLMEngine
from repro.engine.executor import RealExecutor, SimExecutor
from repro.engine.request import Request, SamplingParams
from repro.models import api


@pytest.fixture(scope="module")
def dense_setup():
    cfg = configs.get("qwen3-1.7b").reduced()
    params, _ = api.init_params(cfg, jax.random.key(7))
    return cfg, params


def oracle_generate(cfg, params, prompt, n_new):
    toks = jnp.asarray(prompt, jnp.int32)[None]
    logits, cache = api.prefill_fn(params, cfg, {"tokens": toks})
    cache = api.pad_cache(cfg, cache, len(prompt) + n_new + 8)
    out = [int(jnp.argmax(logits[0]))]
    for i in range(n_new - 1):
        pos = jnp.asarray([len(prompt) + i], jnp.int32)
        logits, cache = api.decode_fn(
            params, cfg, jnp.asarray([out[-1]], jnp.int32), cache, pos)
        out.append(int(jnp.argmax(logits[0])))
    return out


def run_engine(eng, reqs, max_steps=2000):
    now = 0.0
    for r in reqs:
        eng.add_request(r, now)
    steps = 0
    while eng.has_work() and steps < max_steps:
        rep = eng.step(now)
        now += max(rep.elapsed, 1e-4)
        steps += 1
    return steps


def test_paged_engine_matches_oracle(dense_setup, rng):
    cfg, params = dense_setup
    # 64-token prompt exercises chunked prefill (max_prefill_tokens=32)
    prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
               for n in (11, 64)]
    oracle = [oracle_generate(cfg, params, p, 6) for p in prompts]
    ex = RealExecutor(cfg, params, num_blocks=256, block_size=16,
                      hw=TPU_V5E, max_model_len=256, max_slots=8,
                      backend="ref")
    eng = LLMEngine(cfg, ex, num_blocks=256, block_size=16, max_num_seqs=8,
                    max_prefill_tokens=32, max_model_len=256)
    reqs = [Request(prompt_tokens=p,
                    sampling=SamplingParams(temperature=0.0,
                                            max_new_tokens=6))
            for p in prompts]
    run_engine(eng, reqs)
    for r, o in zip(reqs, oracle):
        assert r.status.value == "finished"
        assert r.output_tokens == o
    eng.allocator.check_invariants()
    assert eng.allocator.num_free() == 256


def test_decode_batch_padded_to_max_slots(dense_setup, rng, monkeypatch):
    """Every decode step runs max_slots rows, so one program serves every
    batch; the padding rows leave a sequence's tokens as they were alone."""
    from repro.engine import paged_model
    cfg, params = dense_setup
    rows = []
    decode_step = paged_model.decode_step

    def spy(params, cfg, tokens, *args, **kw):
        rows.append(tokens.shape[0])
        return decode_step(params, cfg, tokens, *args, **kw)

    monkeypatch.setattr(paged_model, "decode_step", spy)
    prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
               for n in (5, 19, 33)]
    first = {}
    for n in (1, 3):
        ex = RealExecutor(cfg, params, num_blocks=32, block_size=8,
                          hw=TPU_V5E, max_model_len=64, max_slots=4,
                          backend="ref")
        eng = LLMEngine(cfg, ex, num_blocks=32, block_size=8,
                        max_num_seqs=4, max_prefill_tokens=64,
                        max_model_len=64)
        reqs = [Request(prompt_tokens=p,
                        sampling=SamplingParams(temperature=0.0,
                                                max_new_tokens=5))
                for p in prompts[:n]]
        run_engine(eng, reqs)
        assert all(r.status.value == "finished" for r in reqs)
        first[n] = reqs[0].output_tokens
    assert rows and set(rows) == {4}
    assert first[1] == first[3]


def test_state_executor_matches_oracle(rng):
    """ssm family goes through the slot-state executor, not the paged pool."""
    cfg = configs.get("mamba2-780m").reduced()
    params, _ = api.init_params(cfg, jax.random.key(3))
    prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
               for n in (9, 17)]
    oracle = [oracle_generate(cfg, params, p, 5) for p in prompts]
    ex = RealExecutor(cfg, params, num_blocks=64, block_size=16,
                      hw=TPU_V5E, max_model_len=128, max_slots=4,
                      backend="ref")
    eng = LLMEngine(cfg, ex, num_blocks=64, block_size=16, max_num_seqs=4,
                    max_prefill_tokens=64, max_model_len=128,
                    enable_prefix_caching=False)
    reqs = [Request(prompt_tokens=p,
                    sampling=SamplingParams(temperature=0.0,
                                            max_new_tokens=5))
            for p in prompts]
    run_engine(eng, reqs)
    for r, o in zip(reqs, oracle):
        assert r.status.value == "finished"
        assert r.output_tokens == o


def test_preemption_under_block_pressure(dense_setup, rng):
    # 3 seqs prefill into 15/16 blocks; decode growth forces eviction
    cfg, params = dense_setup
    ex = RealExecutor(cfg, params, num_blocks=16, block_size=8, hw=TPU_V5E,
                      max_model_len=96, max_slots=4,
                      backend="ref")
    eng = LLMEngine(cfg, ex, num_blocks=16, block_size=8, max_num_seqs=4,
                    max_prefill_tokens=64, max_model_len=96,
                    enable_prefix_caching=False)
    reqs = [Request(prompt_tokens=list(rng.integers(1, cfg.vocab_size,
                                                    size=40)),
                    sampling=SamplingParams(temperature=0.0,
                                            max_new_tokens=6))
            for _ in range(3)]
    run_engine(eng, reqs)
    assert all(r.status.value == "finished" for r in reqs)
    assert all(len(r.output_tokens) == 6 for r in reqs)
    assert eng.metrics.preemptions > 0, "scenario exerted no block pressure"
    eng.allocator.check_invariants()
    assert eng.allocator.num_free() == 16


def test_prefix_caching_does_not_change_outputs(dense_setup, rng):
    """Same requests with and without prefix caching -> identical tokens
    (shared prompt prefixes make the cache actually fire)."""
    cfg, params = dense_setup
    shared = list(rng.integers(1, cfg.vocab_size, size=32))
    prompts = [shared + list(rng.integers(1, cfg.vocab_size, size=8))
               for _ in range(2)]
    outs = {}
    for caching in (False, True):
        ex = RealExecutor(cfg, params, num_blocks=128, block_size=8,
                          hw=TPU_V5E, max_model_len=128, max_slots=4,
                          backend="ref")
        eng = LLMEngine(cfg, ex, num_blocks=128, block_size=8,
                        max_num_seqs=4, max_prefill_tokens=128,
                        max_model_len=128, enable_prefix_caching=caching)
        reqs = [Request(prompt_tokens=list(p),
                        sampling=SamplingParams(temperature=0.0,
                                                max_new_tokens=4))
                for p in prompts]
        run_engine(eng, reqs)
        outs[caching] = [r.output_tokens for r in reqs]
        if caching:
            assert eng.metrics.tokens_prefilled < sum(len(p)
                                                      for p in prompts)
    assert outs[False] == outs[True]


def test_fcfs_admission_order():
    cfg = configs.get("mistral-small-24b")
    from repro.config import GPU_H100
    ex = SimExecutor(cfg, GPU_H100)
    eng = LLMEngine(cfg, ex, num_blocks=64, block_size=16, max_num_seqs=2,
                    max_prefill_tokens=256, max_model_len=512,
                    enable_prefix_caching=False)
    reqs = [Request(prompt_tokens=[i + 1] * 64,
                    sampling=SamplingParams(target_output_len=4,
                                            max_new_tokens=4))
            for i in range(5)]
    now = 0.0
    for i, r in enumerate(reqs):
        eng.add_request(r, now + i * 1e-3)
    order = []
    while eng.has_work():
        rep = eng.step(now)
        now += max(rep.elapsed, 1e-4)
        for r in reqs:
            if r.metrics.first_scheduled_time is not None \
                    and r.request_id not in order:
                order.append(r.request_id)
    assert order == [r.request_id for r in reqs], "FCFS violated"


def test_oversized_request_fails_cleanly():
    cfg = configs.get("mistral-small-24b")
    from repro.config import GPU_H100
    eng = LLMEngine(cfg, SimExecutor(cfg, GPU_H100), num_blocks=32,
                    block_size=16, max_model_len=128)
    r = Request(prompt_tokens=[1] * 1000,
                sampling=SamplingParams(max_new_tokens=4))
    eng.add_request(r, 0.0)
    eng.step(0.0)
    assert r.status.value == "failed"


def test_engine_metrics_snapshot():
    cfg = configs.get("mistral-small-24b")
    from repro.config import GPU_H100
    eng = LLMEngine(cfg, SimExecutor(cfg, GPU_H100), num_blocks=512,
                    block_size=16, max_model_len=2048)
    for i in range(3):
        eng.add_request(Request(prompt_tokens=[1] * 64,
                                sampling=SamplingParams(
                                    target_output_len=8, max_new_tokens=8)),
                        0.0)
    snap = eng.snapshot(1.0)
    assert snap["num_waiting"] == 3
    assert snap["queue_time"] == 1.0
    now = 0.0
    while eng.has_work():
        now += max(eng.step(now).elapsed, 1e-4)
    snap = eng.snapshot(now)
    assert snap["requests_finished_total"] == 3
    assert snap["tokens_generated_total"] >= 3 * 7
    assert snap["kv_utilization"] >= 0.0


@pytest.fixture
def host_spans():
    """The process's host-span recorder, started for one test."""
    from repro.core.tracing import HOST_SPANS
    HOST_SPANS.drain()
    HOST_SPANS.start()
    yield HOST_SPANS
    HOST_SPANS.stop()
    HOST_SPANS.drain()


def test_stamps_give_the_wait_behind_a_full_batch(host_spans):
    cfg = configs.get("mistral-small-24b")
    from repro.config import GPU_H100
    eng = LLMEngine(cfg, SimExecutor(cfg, GPU_H100), num_blocks=64,
                    block_size=16, max_num_seqs=1, max_prefill_tokens=256,
                    max_model_len=512)
    first, second = [Request(prompt_tokens=[i + 1] * 32,
                             sampling=SamplingParams(target_output_len=4,
                                                     max_new_tokens=4))
                     for i in range(2)]
    eng.add_request(first, 0.0)
    eng.add_request(second, 0.0)
    run_engine(eng, [])
    spans, stamps = host_spans.drain()
    at = {(s.request_id, s.event): s.t for s in stamps}
    assert sorted(at) == sorted((r.request_id, e) for r in (first, second)
                                for e in ("enqueue", "admit"))
    wait = {r.request_id: at[r.request_id, "admit"]
            - at[r.request_id, "enqueue"] for r in (first, second)}
    # the second waits for the one row through every call that served
    # the first, and was admitted in the call after the first finished
    steps = [s for s in spans if s.name == "engine.step"]
    before = [s for s in steps if s.end <= at[second.request_id, "admit"]]
    assert len(before) == 4             # prefill + 3 decodes of the first
    assert wait[second.request_id] >= sum(s.duration for s in before)
    assert wait[first.request_id] < wait[second.request_id]
    assert {s.attrs["replica"] for s in spans} == {None}   # no device
    sched = [s for s in spans if s.name == "engine.schedule"]
    assert [s.attrs["decode_rows"] for s in sched[:5]] == [0, 1, 1, 1, 0]
    assert all(s.parent in {t.span_id for t in steps} for s in sched)


def test_real_executor_reports_its_measured_time(dense_setup, rng,
                                                 host_spans):
    cfg, params = dense_setup
    ex = RealExecutor(cfg, params, num_blocks=64, block_size=16, hw=TPU_V5E,
                      max_model_len=256, max_slots=4, backend="ref")
    eng = LLMEngine(cfg, ex, num_blocks=64, block_size=16, max_num_seqs=4,
                    max_prefill_tokens=64, max_model_len=256)
    calls = []
    inner = ex.step

    def timed(prefills, decode):
        from repro.core.tracing import host_clock
        t0 = host_clock()
        out = inner(prefills, decode)
        calls.append((t0, host_clock(), out[2], prefills, decode))
        return out

    ex.step = timed
    reqs = [Request(prompt_tokens=list(rng.integers(1, cfg.vocab_size,
                                                    size=n)),
                    sampling=SamplingParams(temperature=0.0,
                                            max_new_tokens=5))
            for n in (9, 20)]
    run_engine(eng, reqs)
    spans, _ = host_spans.drain()
    assert all(r.status.value == "finished" for r in reqs)
    own = {}
    for s in spans:
        if s.name.startswith("executor."):
            own.setdefault(s.parent, []).append(s)
    steps = [s for s in spans if s.name == "engine.step"
             and s.span_id in own]
    assert len(steps) == len(calls)
    for (t0, t1, elapsed, _, _), step in zip(calls, steps):
        # the call's own host time: inside the wrapper's, over its spans
        assert 0 < elapsed <= t1 - t0
        assert elapsed >= sum(s.duration for s in own[step.span_id])
        assert {s.attrs["replica"] for s in own[step.span_id]} == {
            ex.device.id}
    names = [s.name for s in spans]
    assert names.count("executor.prefill") == 2
    assert names.count("executor.decode.dispatch") == \
        names.count("executor.decode.fetch") == 4
    assert eng.metrics.busy_time == pytest.approx(sum(c[2] for c in calls))
    # the roofline estimate stays SimExecutor's elapsed
    sim = SimExecutor(cfg, TPU_V5E)
    _, _, _, prefills, decode = calls[1]
    _, _, est = sim.step(prefills, decode)
    assert est == pytest.approx(sim.cost.mixed_time(
        0, 0, len(decode["slots"]), sum(p + 1 for p in decode["pos"])))


def test_real_executor_compiles_each_prompt_length_once(dense_setup, rng):
    """The prefill is one program per prompt length: a second prompt of a
    length served before runs it again, and is neither traced nor
    compiled anew."""
    cfg, params = dense_setup
    ex = RealExecutor(cfg, params, num_blocks=64, block_size=16, hw=TPU_V5E,
                      max_model_len=256, max_slots=4, backend="ref")
    eng = LLMEngine(cfg, ex, num_blocks=64, block_size=16, max_num_seqs=4,
                    max_prefill_tokens=64, max_model_len=256)
    reqs = [Request(prompt_tokens=list(rng.integers(1, cfg.vocab_size,
                                                    size=n)),
                    sampling=SamplingParams(temperature=0.0,
                                            max_new_tokens=3))
            for n in (9, 20, 9, 20, 9)]
    run_engine(eng, reqs)
    assert all(r.status.value == "finished" for r in reqs)
    assert ex._prefill_program._cache_size() == 2


def test_decode_step_traced_once_across_batch_sizes(dense_setup, rng,
                                                    monkeypatch):
    """The decode step is one jitted program per executor: batches of 1, 2
    and 4 live rows at many positions run it without tracing it again, and
    the greedy tokens stay the dense oracle's."""
    from repro.engine import paged_model
    cfg, params = dense_setup
    traces = []
    decode_step = paged_model.decode_step

    def spy(*args, **kw):
        traces.append(1)
        return decode_step(*args, **kw)

    monkeypatch.setattr(paged_model, "decode_step", spy)
    lens = (7, 13, 21, 30)
    new = (12, 9, 4, 4)
    prompts = [list(rng.integers(1, cfg.vocab_size, size=n)) for n in lens]
    oracle = [oracle_generate(cfg, params, p, k)
              for p, k in zip(prompts, new)]
    ex = RealExecutor(cfg, params, num_blocks=64, block_size=8, hw=TPU_V5E,
                      max_model_len=64, max_slots=4, backend="ref")
    eng = LLMEngine(cfg, ex, num_blocks=64, block_size=8, max_num_seqs=4,
                    max_prefill_tokens=128, max_model_len=64)
    rows = []
    inner = ex.step

    def counted(prefills, decode):
        if decode:
            rows.append(len(decode["slots"]))
        return inner(prefills, decode)

    ex.step = counted
    reqs = [Request(prompt_tokens=p,
                    sampling=SamplingParams(temperature=0.0,
                                            max_new_tokens=k))
            for p, k in zip(prompts, new)]
    # one row alone, then a second joins, then all four
    now = 0.0
    for batch, steps in ((reqs[:1], 3), (reqs[1:2], 3), (reqs[2:], 100)):
        for r in batch:
            eng.add_request(r, now)
        for _ in range(steps):
            if not eng.has_work():
                break
            now += max(eng.step(now).elapsed, 1e-4)
    assert not eng.has_work()
    assert {1, 2, 4} <= set(rows)
    assert len(traces) == 1
    assert ex._decode_program._cache_size() == 1
    for r, o in zip(reqs, oracle):
        assert r.status.value == "finished"
        assert r.output_tokens == o


def test_decode_donates_the_pool(dense_setup, rng):
    """Each decode call consumes the pool it was given; prefills written
    into the pool a decode returned, and decoded after, still give the
    dense oracle's tokens, so no donated pool is read again."""
    cfg, params = dense_setup
    prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
               for n in (10, 26)]
    oracle = [oracle_generate(cfg, params, p, 6) for p in prompts]
    ex = RealExecutor(cfg, params, num_blocks=32, block_size=8, hw=TPU_V5E,
                      max_model_len=64, max_slots=4, backend="ref")
    eng = LLMEngine(cfg, ex, num_blocks=32, block_size=8, max_num_seqs=4,
                    max_prefill_tokens=64, max_model_len=64)
    given = []
    program = ex._decode_program

    def held(params, tokens, pos, pool, block_tables):
        given.append(pool)
        return program(params, tokens, pos, pool, block_tables)

    ex._decode_program = held
    reqs = [Request(prompt_tokens=p,
                    sampling=SamplingParams(temperature=0.0,
                                            max_new_tokens=6))
            for p in prompts]
    # the second prompt is written into a pool that decodes have returned
    eng.add_request(reqs[0], 0.0)
    now = 0.0
    for _ in range(3):
        now += max(eng.step(now).elapsed, 1e-4)
    assert len(given) == 2 and not reqs[1].output_tokens
    run_engine(eng, reqs[1:])
    assert len(given) > 2
    assert all(x.is_deleted() for pool in given for x in pool.values())
    assert not any(x.is_deleted() for x in ex.pool.values())
    for r, o in zip(reqs, oracle):
        assert r.status.value == "finished"
        assert r.output_tokens == o
