"""Median host time of the engine's own work in one engine call: the
program's span `engine.step` less the executor's spans inside it
(`executor.*`), over the window's calls that ran the executor. What is
left is scheduling, building the batch, sampling and handing the tokens to
the streams."""
import statistics
from collections import defaultdict

from bench import program_spans


def read(r):
    w = program_spans.window(r)
    if w is None:
        return None
    executor = defaultdict(float)          # engine.step span id -> s
    for s in w.spans:
        if s.name.startswith("executor."):
            executor[s.parent] += s.duration
    own = [s.duration - executor[s.span_id] for s in w.spans
           if s.name == "engine.step" and s.span_id in executor]
    return statistics.median(own) * 1e3 if own else None
