"""The end-to-end numbers come from the host clock, not the model's
virtual time.

A stub executor that sleeps a known time per engine call stands in for the
model. Its step returns the roofline estimate as its elapsed time, as the
real executor does, and the estimate is patched from five milliseconds to
fifty seconds: the gap between tokens and the token rate follow the sleep
and do not move with the estimate.

The estimate still orders the program's events on its virtual clock: one
shorter than the gateway's forward delay (about 1.1 ms of virtual time)
lets the engine run calls before a request sent meanwhile arrives. Both
estimates here are longer, as the roofline's estimate of a served step
is (several ms at full width)."""
import time

import numpy as np
import pytest

from bench.tests import tiny


def _stub(sleep_s):
    from repro.engine.costmodel import RooflineCost

    class SleepyExecutor:
        needs_logits = True

        def __init__(self, cfg, params, *, hw, tp, max_slots, device, **kw):
            self.cfg, self.max_slots, self.device = cfg, max_slots, device
            self.cost = RooflineCost(cfg, hw, tp=tp)
            self.pool = {"k": np.zeros(1, np.float32)}
            self.rng = np.random.default_rng(0)

        def step(self, prefills, decode):
            new = sum(e - s for s, e in (p["chunk"] for p in prefills))
            ctx = sum(p["chunk"][1] for p in prefills)
            rows = len(decode["slots"]) if decode else 0
            total = sum(p + 1 for p in decode["pos"]) if decode else 0
            elapsed = self.cost.mixed_time(new, ctx, rows, total)
            time.sleep(sleep_s)
            v = self.cfg.vocab_size
            pre = [self.rng.normal(size=v) if p["is_last"] else None
                   for p in prefills]
            dec = self.rng.normal(size=(rows, v)) if decode else None
            return pre, dec, elapsed

    return SleepyExecutor


def _run(tmp_path, monkeypatch, sleep_s, estimate_s):
    from repro.engine import executor
    from repro.engine.costmodel import RooflineCost
    monkeypatch.setattr(executor, "RealExecutor", _stub(sleep_s))
    if estimate_s is not None:
        monkeypatch.setattr(RooflineCost, "mixed_time",
                            lambda self, *a: estimate_s)
    root = tiny.make_root(tmp_path / f"{sleep_s}-{estimate_s}")
    return tiny.run(root, seconds=2.0)["_summary"]


def test_numbers_follow_the_sleep_not_the_estimate(tmp_path, monkeypatch):
    fast = _run(tmp_path, monkeypatch, 0.05, 0.005)
    slow_estimate = _run(tmp_path, monkeypatch, 0.05, 50.0)
    slow = _run(tmp_path, monkeypatch, 0.10, None)
    for s in (fast, slow_estimate, slow):
        assert s["failed"] == 0 and s["itl_count"] > 10
    # one engine call per token of a request: the gap is the sleep plus
    # the host's own few milliseconds
    assert 50 <= fast["itl_p95_ms"] <= 65
    assert 50 <= slow_estimate["itl_p95_ms"] <= 65
    assert 100 <= slow["itl_p95_ms"] <= 120
    assert slow_estimate["output_tok_s"] == pytest.approx(
        fast["output_tok_s"], rel=0.15)
    assert fast["output_tok_s"] / slow["output_tok_s"] == pytest.approx(
        2.0, rel=0.15)
