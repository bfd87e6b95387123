"""The output check: served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests that were served tokens in the window, drawn from the seed
and always holding the one served the most tokens, is run through the
reference: one float32 forward over each prompt with its served tokens.
For each served token the gap is the reference's best logit at that
position minus the reference's logit of the served token. The number
compared is the widest gap over the sample; it is 0 where the program
picked the reference's own greedy token everywhere.

The same comparison scores the control: at each position of the same
sequences, the token that the reference run in int8 puts first, read on
the float32 reference's logits.
"""
from __future__ import annotations

import numpy as np

MIN_TOKENS = 512        # served tokens compared: the sample grows to this
MAX_REQUESTS = 12       # ... or to this many requests


def sample(records: list, seed_words: list) -> list:
    """The records compared: the one served the most tokens, then others
    in an order drawn from the seed, until MIN_TOKENS served tokens or
    MAX_REQUESTS requests."""
    served = [r for r in records if r.tokens]
    if not served:
        return []
    longest = max(range(len(served)), key=lambda i: len(served[i].tokens))
    rng = np.random.default_rng([*seed_words, 0xC4EC])
    order = [longest] + [i for i in rng.permutation(len(served))
                         if i != longest]
    out, n = [], 0
    for i in order:
        if n >= MIN_TOKENS or len(out) >= MAX_REQUESTS:
            break
        out.append(served[i])
        n += len(served[i].tokens)
    return out


def _scores(ref, ctrl, tokens):
    """Per position p: the reference's best logit minus its logit of
    tokens[p + 1] (the served token), and minus its logit of the control's
    first choice."""
    import jax.numpy as jnp
    best = jnp.max(ref, axis=-1)
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    at_next = jnp.take_along_axis(ref, nxt[:, None], axis=-1)[:, 0]
    out = [best - at_next]
    if ctrl is not None:
        pick = jnp.argmax(ctrl, axis=-1)
        out.append(best - jnp.take_along_axis(ref, pick[:, None],
                                              axis=-1)[:, 0])
    return out


def gaps(reference, weights, spec: dict, seqs: list, pad_to: int,
         control: bool = False):
    """For each (prompt, served tokens): the gap of each served token and,
    with `control`, of each of the int8 reference's choices, at the same
    positions, as arrays. Sequences are padded to `pad_to` at the end, so
    one program serves all (attention is causal; padding is never
    read)."""
    import jax
    import jax.numpy as jnp
    score = jax.jit(_scores)
    out = []
    for prompt, served in seqs:
        toks = list(prompt) + list(served)
        n = len(toks)
        if n > pad_to:
            raise ValueError(f"sequence of {n} tokens > {pad_to}")
        x = jnp.asarray(toks + [0] * (pad_to - n), jnp.int32)
        ref = reference.logits(weights, spec, x)
        ctrl = reference.logits(weights, spec, x, control=True) \
            if control else None
        rows = slice(len(prompt) - 1, n - 1)
        out.append(tuple(np.asarray(g)[rows] for g in score(ref, ctrl, x)))
        del ref, ctrl
    return out


def run(reference, weights, spec: dict, records: list, seed_words: list,
        pad_to: int, limit: float) -> dict:
    """The check of one run: what was compared, the widest gap, its limit
    and whether it holds."""
    picked = sample(records, seed_words)
    seqs = [(r.prompt, r.tokens) for r in picked]
    widest = max((float(g[0].max()) for g in gaps(reference, weights, spec,
                                                   seqs, pad_to)),
                 default=None)
    return {"requests": len(picked),
            "tokens": sum(len(s) for _, s in seqs),
            "max_logit_gap": widest, "limit": limit,
            "correct": widest is not None and widest <= limit}
