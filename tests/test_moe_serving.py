"""The serving expert layer of the moe family (`models/moe.py`
`serve_experts`, `kernels/moe_experts`) on the CPU: dropless against a
plain per-token loop however skewed the routing, padding rows routed
nowhere, the Pallas kernel interpreted against its jnp oracle on ragged
groups, and a reduced qwen3-moe served by `RealExecutor` (prefill, then
paged decode) against the model's own full forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.config import TPU_V5E
from repro.engine import paged_model
from repro.engine.executor import RealExecutor
from repro.kernels.moe_experts import ops
from repro.kernels.moe_experts.kernel import moe_experts as pallas_experts
from repro.kernels.moe_experts.ref import moe_experts_oracle
from repro.models import api, moe

CFG = configs.get("qwen3-moe-30b-a3b").reduced()     # 8 experts, top-2


@pytest.fixture(scope="module")
def layer():
    params, _ = api.init_params(CFG, jax.random.key(11))
    return params, jax.tree.map(lambda v: v[0], params["layers"])["moe"]


def _loop(p, x):
    """Each token through its top-k experts, one at a time, in numpy."""
    xf = np.asarray(x, np.float64).reshape(-1, CFG.d_model)
    logits = xf @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    wg, wu, wd = (np.asarray(p[w], np.float64)
                  for w in ("w_gate", "w_up", "w_down"))
    out = np.zeros_like(xf)
    for i, row in enumerate(xf):
        top = np.argsort(-probs[i])[:CFG.num_experts_per_tok]
        w = probs[i][top] / probs[i][top].sum()
        for wj, e in zip(w, top):
            g = row @ wg[e]
            out[i] += wj * ((g / (1 + np.exp(-g)) * (row @ wu[e])) @ wd[e])
    return out


def _skewed(p, x):
    """Every token's largest router logit on expert 3: a positive first
    feature against a large router weight."""
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 1.0)
    return dict(p, router=p["router"].at[0, 3].set(50.0)), x


@pytest.mark.parametrize("skew", [False, True], ids=["spread", "skewed"])
@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_serving_block_is_the_per_token_loop(layer, skew, backend):
    """128 tokens: the old serving capacity (2 n k / E = 64 rows an
    expert) would drop half of the skewed expert's 128 rows."""
    _, p = layer
    x = jax.random.normal(jax.random.key(1), (2, 64, CFG.d_model)) * 0.3
    if skew:
        p, x = _skewed(p, x)
    y, groups = moe.serve_experts(p, CFG, x, backend=backend)
    want = _loop(p, x)
    np.testing.assert_allclose(np.asarray(y).reshape(want.shape), want,
                               rtol=2e-4, atol=2e-4)
    groups = np.asarray(groups)
    assert groups.sum() == 128 * CFG.num_experts_per_tok
    if skew:
        assert groups[3] == 128


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_padding_rows_route_nowhere(layer, backend):
    """A decode batch of 8 rows of which rows 0-2 and 5-6 are live: the
    padding rows add no row to any expert and come out 0, and the live
    rows come out as they do alone."""
    _, p = layer
    x = jax.random.normal(jax.random.key(2), (8, 1, CFG.d_model)) * 0.3
    live = np.array([1, 1, 1, 0, 0, 1, 1, 0], bool)
    y, groups = moe.serve_experts(p, CFG, x, backend=backend,
                                  routed=jnp.asarray(live))
    alone, alone_groups = moe.serve_experts(p, CFG, x[live],
                                            backend=backend)
    assert np.asarray(groups).tolist() == np.asarray(alone_groups).tolist()
    assert int(np.asarray(groups).sum()) == 5 * CFG.num_experts_per_tok
    np.testing.assert_array_equal(np.asarray(y[~live]), 0.0)
    np.testing.assert_allclose(np.asarray(y[live]), np.asarray(alone),
                               rtol=1e-6, atol=1e-6)


def test_paged_decode_routes_only_rows_past_position_0(layer):
    """The paged decode step's group sizes count the rows that carry a
    sequence (position > 0) and none of the rows that pad the batch."""
    params, _ = layer
    pool = paged_model.init_pool(CFG, 4, 8)
    pos = jnp.asarray([5, 0, 3, 0], jnp.int32)
    _, _, groups = paged_model.decode_step(
        params, CFG, jnp.asarray([7, 0, 9, 0], jnp.int32), pos, pool,
        jnp.asarray([[1], [0], [2], [0]], jnp.int32), backend="ref")
    groups = np.asarray(groups)
    assert groups.shape == (CFG.num_layers, CFG.num_experts)
    assert groups.sum(axis=1).tolist() == \
        [2 * CFG.num_experts_per_tok] * CFG.num_layers


@pytest.mark.parametrize("sizes, m, tm", [
    ([0, 3, 0, 17, 1, 0, 40, 2], 80, 16),      # empty groups between
    ([0, 0, 0, 0, 0, 33, 0, 0], 48, 16),       # one group, rows left over
    ([5, 0, 0, 0, 0, 0, 0, 0], 16, 16),        # one partial tile
    ([9, 14, 1, 1, 30, 0, 7, 2], 64, 16),      # groups across tiles
    ([0] * 8, 32, 16),                         # no row at all
    ([1] * 8, 300, 128),                       # ops' own tile, padded
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_interpreted_equals_the_oracle(sizes, m, tm, dtype, rng):
    """Layer 1 of weights stacked over 2 layers, as a layer scan gives
    them to the kernel, against the oracle on layer 1's own weights."""
    e, d, f = len(sizes), 64, 32
    x = jnp.asarray(rng.normal(size=(m, d)), dtype)
    wg, wu = (jnp.asarray(rng.normal(size=(2, e, d, f)) * d ** -0.5, dtype)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(2, e, f, d)) * f ** -0.5, dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    want = np.asarray(moe_experts_oracle(x, wg[1], wu[1], wd[1], gs))
    n = sum(sizes)
    np.testing.assert_array_equal(want[n:], 0.0)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    if m % tm == 0:
        got = pallas_experts(x, wg, wu, wd, gs, jnp.int32(1), tm=tm,
                             interpret=True)
        np.testing.assert_allclose(np.asarray(got)[:n], want[:n],
                                   rtol=tol, atol=tol)
    for backend in ("interpret", "ref"):
        got = ops.moe_experts(x, wg, wu, wd, gs, jnp.int32(1),
                              backend=backend)
        np.testing.assert_allclose(np.asarray(got), want, rtol=tol, atol=tol)
    got = ops.moe_experts(x, wg[1], wu[1], wd[1], gs, backend="interpret")
    np.testing.assert_allclose(np.asarray(got), want, rtol=tol, atol=tol)


def test_unknown_backend_is_refused():
    x = jnp.zeros((16, 8))
    w = jnp.zeros((2, 8, 8))
    with pytest.raises(ValueError, match="backend"):
        ops.moe_experts(x, w, w, w, jnp.zeros(2, jnp.int32), backend="gpu")


def test_executor_prefill_then_paged_decode_matches_full_forward(layer):
    """Two sequences of a reduced qwen3-moe (float32 weights, the `ref`
    kernels) prefilled and then decoded teacher-forced through the paged
    pool in a batch of 4 rows: each position's logits are the full
    forward's. Tolerance 1e-4: float32 throughout, only the order of the
    attention sums differs (paged against dense)."""
    params, _ = layer
    rng = np.random.default_rng(5)
    seqs = [rng.integers(1, CFG.vocab_size, size=n) for n in (21, 12)]
    prompt = (13, 6)
    bs = 8
    ex = RealExecutor(CFG, params, num_blocks=16, block_size=bs, hw=TPU_V5E,
                      max_model_len=32, max_slots=4, backend="ref")
    tables = [[1, 2, 3], [4, 5]]
    got = {}
    for i, (seq, n) in enumerate(zip(seqs, prompt)):
        pre, _, _ = ex.step([{"token_ids": list(seq[:n]), "is_last": True,
                              "block_table": tables[i][:-(-n // bs)],
                              "slot": i, "chunk": (0, n)}], None)
        got[i] = [pre[0]]
    for step in range(6):
        pos = [n + step for n in prompt]
        _, dec, _ = ex.step([], {
            "slots": [0, 1], "tokens": [int(s[p]) for s, p in zip(seqs, pos)],
            "pos": pos, "block_tables": [t[:p // bs + 1]
                                         for t, p in zip(tables, pos)]})
        for i in range(2):
            got[i].append(dec[i])
    for i, (seq, n) in enumerate(zip(seqs, prompt)):
        full, _ = moe.forward_train(params, CFG, jnp.asarray(seq)[None],
                                    remat=False, capacity_factor=None)
        want = np.asarray(full[0, n - 1:n + 6])
        np.testing.assert_allclose(np.stack(got[i]), want, rtol=1e-4,
                                   atol=1e-4)


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_float32_rows_over_bfloat16_weights_keep_float32(backend, rng):
    """Rows in float32 over experts in bfloat16, as the moe family serves:
    the kernel and the ragged dot give the float32 product of the rows and
    the stored weights, where rounding the rows to bfloat16 first would be
    off by about 1e-2."""
    sizes, d, f = [3, 0, 20, 9], 64, 32
    x = jnp.asarray(rng.normal(size=(32, d)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(4, d, f)) * d ** -0.5,
                          jnp.bfloat16) for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(4, f, d)) * f ** -0.5, jnp.bfloat16)
    gs = jnp.asarray(sizes, jnp.int32)
    want = np.asarray(moe_experts_oracle(x, *(w.astype(jnp.float32)
                                              for w in (wg, wu, wd)), gs))
    got = np.asarray(ops.moe_experts(x, wg, wu, wd, gs, backend=backend))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    rounded = np.asarray(ops.moe_experts(x.astype(jnp.bfloat16), wg, wu, wd,
                                         gs, backend=backend))
    assert np.abs(rounded - want).max() > 1e-3


def test_served_in_float32_over_bfloat16_weights(layer):
    """Prefill and the paged decode step of bfloat16 weights give float32
    logits equal to those of the same weights held in float32: no
    activation is rounded to the weights' type."""
    params, _ = layer
    bf16 = jax.tree.map(lambda v: v.astype(jnp.bfloat16), params)
    f32 = jax.tree.map(lambda v: v.astype(jnp.float32), bf16)
    toks = jnp.asarray([[3, 17, 5, 9, 11]], jnp.int32)
    (got, _), (want, _) = (moe.prefill(p, CFG, toks) for p in (bf16, f32))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    pool = paged_model.init_pool(CFG, 4, 8)
    args = (jnp.asarray([7, 9], jnp.int32), jnp.asarray([5, 3], jnp.int32),
            pool, jnp.asarray([[1], [2]], jnp.int32))
    (got, _, _), (want, _, _) = (paged_model.decode_step(
        p, CFG, *args, backend="ref") for p in (bf16, f32))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
