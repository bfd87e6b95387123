"""The output check fails a broken served path.

Each test drives a whole run on the CPU (the harness's look for a chip
skipped) with the timed path broken underneath, and sees `correct` come
out false; the unbroken run on the same seed sees it true. The faults are
those a one-chip serving cell can have: a step that returns its state
unchanged (the decode step hands back the KV pool it was given), half of
the batch left out (the second half of the decode rows gets the first
row's logits), and a token altered where it is produced (every fifth
sampled token moved by one). The limit is the tiny configuration's
(tests/tiny.py); the served path's own widest gap there is about a tenth
of it.
"""
import numpy as np
import pytest

from bench.tests import tiny


def _assert_incorrect(line):
    gap = line["compared"]["max_logit_gap"]
    print("widest gap", gap)
    assert not line["correct"] and gap["value"] > gap["limit"], gap


@pytest.fixture
def root(tmp_path):
    return tiny.make_root(tmp_path)


def test_unbroken_path_is_correct(root):
    line = tiny.run(root)
    gap = line["compared"]["max_logit_gap"]
    assert line["correct"], gap
    assert gap["tokens"] > 0 and gap["value"] < tiny.LIMIT / 3


def test_step_that_returns_its_state_unchanged(root, monkeypatch):
    from repro.engine import paged_model
    inner = paged_model.decode_step

    def stale(params, cfg, tokens, pos, pool, block_tables, **kw):
        logits, _ = inner(params, cfg, tokens, pos, pool, block_tables, **kw)
        return logits, pool

    monkeypatch.setattr(paged_model, "decode_step", stale)
    _assert_incorrect(tiny.run(root))


def test_half_of_the_batch_left_out(root, monkeypatch):
    from repro.engine.executor import RealExecutor
    inner = RealExecutor._decode

    def half(self, dec):
        logits = inner(self, dec)
        n = len(logits)
        out = np.array(logits)
        out[(n + 1) // 2:] = out[0]
        return out

    monkeypatch.setattr(RealExecutor, "_decode", half)
    _assert_incorrect(tiny.run(root))


def test_token_altered_where_produced(root, monkeypatch):
    from repro.engine.engine import LLMEngine
    inner = LLMEngine._sample
    calls = [0]

    def altered(self, req, logits):
        tok = inner(self, req, logits)
        calls[0] += 1
        return (tok + 1) % self.cfg.vocab_size if calls[0] % 5 == 0 else tok

    monkeypatch.setattr(LLMEngine, "_sample", altered)
    _assert_incorrect(tiny.run(root))
