"""Full-stack end-to-end: the paper's architecture with REAL model compute.

A reduced qwen3 model is served by a RealExecutor engine inside a simulated
Slurm job; requests flow client -> Web Gateway (auth, lookup, forward) ->
vLLM instance -> paged engine -> streamed tokens; outputs must equal the
dense-cache oracle exactly (greedy)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.config import TPU_V5E
from repro.core.controller import ClusterSpec, ControlPlane
from repro.engine.engine import LLMEngine
from repro.engine.executor import RealExecutor
from repro.engine.request import Request, SamplingParams
from repro.models import api


@pytest.mark.slow
def test_full_stack_real_compute_end_to_end():
    cfg = configs.get("qwen3-1.7b").reduced()
    params, _ = api.init_params(cfg, jax.random.key(5))

    def factory(c, tp, gpu):
        ex = RealExecutor(c, params, num_blocks=256, block_size=16,
                          hw=TPU_V5E, max_model_len=256, max_slots=8,
                          backend="ref")
        return LLMEngine(c, ex, num_blocks=256, block_size=16,
                         max_num_seqs=8, max_prefill_tokens=128,
                         max_model_len=256)

    spec = ClusterSpec(num_nodes=2, gpus_per_node=1)
    cp = ControlPlane(spec, engine_factory=factory)
    cp.add_tenant("uni", "sk-e2e")
    cp.add_model(cfg, instances=1, est_load_time=20.0)
    cp.run_until(60.0)
    assert cp.ready_endpoints(cfg.name)

    rng = np.random.default_rng(11)
    prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
               for n in (12, 33, 50)]

    # oracle
    def oracle(prompt, n_new):
        toks = jnp.asarray(prompt, jnp.int32)[None]
        logits, cache = api.prefill_fn(params, cfg, {"tokens": toks})
        cache = api.pad_cache(cfg, cache, len(prompt) + n_new + 8)
        out = [int(jnp.argmax(logits[0]))]
        for i in range(n_new - 1):
            logits, cache = api.decode_fn(
                params, cfg, jnp.asarray([out[-1]], jnp.int32), cache,
                jnp.asarray([len(prompt) + i], jnp.int32))
            out.append(int(jnp.argmax(logits[0])))
        return out

    expected = [oracle(p, 8) for p in prompts]

    streamed: dict[int, list] = {}
    reqs = []
    for p in prompts:
        r = Request(prompt_tokens=p,
                    sampling=SamplingParams(temperature=0.0,
                                            max_new_tokens=8))
        streamed[r.request_id] = []
        r.on_token = lambda req, tok, t, acc=streamed[r.request_id]: \
            acc.append(tok)
        status = cp.web_gateway.handle("sk-e2e", cfg.name, r)
        assert status == 200
        reqs.append(r)
    # the served steps advance the virtual clock by their measured host
    # time: serve until every request ends, against a deadline far past
    # any CPU's compute for three short requests, so a stalled request
    # still ends the loop (the periodic tasks reach it) and fails below
    cp.loop.run_while(
        lambda: not all(r.status.value in ("finished", "failed")
                        for r in reqs), max_t=cp.loop.now + 3600.0)

    for r, exp in zip(reqs, expected):
        assert r.status.value == "finished"
        assert r.output_tokens == exp, "served tokens != oracle"
        assert streamed[r.request_id] == exp, "streamed tokens != oracle"
    cp.db.check_invariants()
    # per-request metrics populated for the Table-1 pipeline
    for r in reqs:
        assert r.metrics.ttft is not None and r.metrics.ttft > 0
        assert r.metrics.e2el >= r.metrics.ttft
