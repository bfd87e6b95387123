"""The one traffic generator: reads a mix's data file and yields requests.

A mix file (bench/traffic/<mix>.json) holds:
- `loop`: "closed" or "open".
  - closed: `clients` clients all send at the window's start, and each
    sends its next request when its last one closes;
  - open: requests arrive at `rate` per second, the gaps between arrivals
    gamma-distributed with shape `arrival_shape` (1 is Poisson, under 1
    burstier). The gaps are drawn from `catalog_seed` (0 where the file
    gives none), so every seed sends at the same times;
- `pairs`: the catalog of (prompt length, output length) pairs, sampled
  once from the mix's distribution (described under `shapes`) and kept,
  each prompt + output at most `max_total`;
- `prefix` (optional): {"groups": g, "tokens": t, "sessions": bool}. The
  n-th request's prompt starts with the t tokens of shared prefix n mod g
  (every prompt is longer than t); with `sessions` it carries the session
  id of its group, for routers that keep a session on one replica.

The pairs are served in the order the file keeps them, over and over,
whatever the seed: in a closed loop the window ends part-way through the
catalog, and a seeded order would change which sizes fall inside it, so
the work of a run would depend on the seed. Every seed sends the same
sizes in the same order.

The seed draws each prompt's token ids (and the shared prefixes), uniform
over [1, vocab). Requests are greedy. A key or a value that this
generator does not honour is refused.
"""
from __future__ import annotations

import itertools
import json
from pathlib import Path
from typing import Iterator, NamedTuple, Optional

import numpy as np

KEYS = {"why", "loop", "clients", "rate", "arrival_shape", "max_total",
        "shapes", "catalog_seed", "pairs", "prefix"}
NEEDS = {"closed": {"clients"}, "open": {"rate", "arrival_shape"}}
PREFIX_KEYS = {"groups", "tokens", "sessions"}


class Request(NamedTuple):
    prompt: list
    output: int
    session: Optional[str] = None


def load(root: Path, name: str) -> dict:
    with open(Path(root) / "bench" / "traffic" / f"{name}.json") as f:
        mix = json.load(f)
    check(mix, name)
    return mix


def check(mix: dict, name: str = "mix"):
    """Refuse a mix that asks for what this generator does not do."""
    unknown = set(mix) - KEYS
    if unknown:
        raise ValueError(f"mix {name}: unknown keys {sorted(unknown)}")
    loop = mix.get("loop")
    if loop not in NEEDS:
        raise ValueError(f"mix {name}: loop {loop!r} is not one of "
                         f"{sorted(NEEDS)}")
    missing = NEEDS[loop] - set(mix)
    extra = (set().union(*NEEDS.values()) - NEEDS[loop]) & set(mix)
    if missing or extra:
        raise ValueError(f"mix {name}: a {loop} loop needs "
                         f"{sorted(NEEDS[loop])}, not {sorted(extra)}")
    if loop == "closed" and not (isinstance(mix["clients"], int)
                                 and mix["clients"] >= 1):
        raise ValueError(f"mix {name}: clients must be a whole number >= 1")
    if loop == "open" and not (mix["rate"] > 0 and mix["arrival_shape"] > 0):
        raise ValueError(f"mix {name}: rate and arrival_shape must be > 0")
    if not mix["pairs"] or any(p < 1 or o < 1 or p + o > mix["max_total"]
                               for p, o in mix["pairs"]):
        raise ValueError(f"mix {name}: no pairs, or a pair is empty or "
                         f"longer than max_total {mix['max_total']}")
    pre = mix.get("prefix")
    if pre is not None:
        if set(pre) - PREFIX_KEYS or not {"groups", "tokens"} <= set(pre):
            raise ValueError(f"mix {name}: prefix takes {sorted(PREFIX_KEYS)}"
                             f" (groups and tokens required)")
        if pre["groups"] < 1 or pre["tokens"] < 1 or any(
                p <= pre["tokens"] for p, _ in mix["pairs"]):
            raise ValueError(f"mix {name}: every prompt must be longer than "
                             f"its shared prefix of {pre['tokens']}")


def prompt_lengths(mix: dict) -> list:
    """Every prompt length the mix sends (what set-up warms up)."""
    return sorted({p for p, _ in mix["pairs"]})


def warm_up_requests(mix: dict, vocab: int) -> list:
    """One request of each prompt length, two output tokens each, on fresh
    prefixes; for a mix with shared prefixes the same again on one prefix
    that the first pass has already sent, so that the lengths a prefix
    hit computes are made too."""
    rng = np.random.default_rng(0)
    lens = prompt_lengths(mix)
    out = [Request(rng.integers(1, vocab, size=n).tolist(), 2)
           for n in lens]
    pre = mix.get("prefix")
    if pre is not None:
        head = out[0].prompt[:pre["tokens"]]
        out += [Request(head + rng.integers(1, vocab, size=n - len(head))
                        .tolist(), 2) for n in lens]
    return out


def requests(mix: dict, vocab: int,
             seed_words: list) -> Iterator[Request]:
    """Endless requests from `seed_words` (a seed as non-negative words)."""
    rng = np.random.default_rng([*seed_words, 0x7AFF1C])
    pre = mix.get("prefix")
    heads = [] if pre is None else [
        rng.integers(1, vocab, size=pre["tokens"]).tolist()
        for _ in range(pre["groups"])]
    for n, (p, o) in enumerate(itertools.cycle(mix["pairs"])):
        if pre is None:
            yield Request(rng.integers(1, vocab, size=p).tolist(), int(o))
        else:
            g = n % pre["groups"]
            own = rng.integers(1, vocab, size=p - pre["tokens"])
            yield Request(heads[g] + own.tolist(), int(o),
                          f"group-{g}" if pre.get("sessions") else None)


def arrivals(mix: dict) -> Iterator[float]:
    """Seconds from the window's start of each arrival of an open loop:
    the same for every seed."""
    rng = np.random.default_rng([mix.get("catalog_seed", 0), 0xA221])
    k, rate = float(mix["arrival_shape"]), float(mix["rate"])
    t = 0.0
    while True:
        yield t
        t += float(rng.gamma(k, 1.0 / (k * rate)))
