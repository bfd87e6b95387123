"""The output check's control fails it.

The control is the plain reference computed a step below the configured
bfloat16: every matrix rounded to int8, one scale per output channel
(bench/reference/dense_decoder.py, `control=True`). Read as a run's
tokens are read, the gap on the float32 reference of the token it puts
first at each position must exceed the configuration's limit. On the
chip, at the cells' own sizes, it reads 0.27-0.86 against limits of 0.18
and 0.2 (PERF.md). Here it runs at the tiny configuration of the other
tests, over 8 random sequences of 128 tokens (960 positions), where it
reads 0.02-0.045 against that configuration's limit of 0.02; the served
path there reads under a third of the limit (test_faults)."""
import jax
import numpy as np
import pytest

from bench import check, model
from bench.tests import tiny


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_control_fails_the_limit(tmp_path, seed):
    root = tiny.make_root(tmp_path)
    spec = tiny.tiny_spec()
    reference = model.reference_module(root, spec)
    weights = model.make_weights(spec, seed, jax.devices()[0])
    rng = np.random.default_rng(seed)
    v = spec["vocab_size"]
    seqs = [(rng.integers(1, v, 8).tolist(), rng.integers(1, v, 120).tolist())
            for _ in range(8)]
    got = check.gaps(reference, weights, spec, seqs, 128, control=True)
    served = max(float(g.max()) for g, _ in got)
    control = max(float(c.max()) for _, c in got)
    assert control > spec["check"]["max_logit_gap"], control
    # the random "served" tokens are no model's choice: far below the best
    assert served > 10 * spec["check"]["max_logit_gap"]
