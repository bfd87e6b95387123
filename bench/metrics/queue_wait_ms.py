"""p95 over the requests enqueued at an engine in the window of the wait
until the engine admitted them: the program's stamps `admit` less
`enqueue`, on the host clock. A request not admitted by the window's end
enters at its age then (a lower bound), as in `ttft_p95_s`."""
from bench import program_spans
from bench.client import percentile


def read(r):
    w = program_spans.window(r)
    if w is None:
        return None
    enqueued, admitted = {}, {}
    for st in w.stamps:
        if st.event == "enqueue":
            enqueued.setdefault(st.request_id, st.t)
        elif st.event == "admit" and st.request_id in enqueued:
            admitted.setdefault(st.request_id, st.t)
    waits = [admitted.get(k, w.end) - t for k, t in enqueued.items()]
    return percentile(waits, 95) * 1e3 if waits else None
