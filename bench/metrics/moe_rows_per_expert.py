"""Rows that share each expert's weight read in the decode step: the
routed (token, expert) rows of the live decode rows, summed over layers,
over the (layer, expert) pairs given at least one of them, both summed over
the window's decode calls. Read from the attributes `moe_rows` and
`moe_active` of the program's span `executor.decode.fetch`; a program or a
model family that records neither gives nothing."""
from bench import program_spans


def decode_routing(r):
    """(routed rows, active (layer, expert) pairs), each summed over the
    window's decode calls that recorded them, or None."""
    w = program_spans.window(r)
    fetched = [s.attrs for s in w.spans
               if s.name == "executor.decode.fetch"
               and "moe_active" in s.attrs] if w else []
    if not fetched:
        return None
    return (sum(a["moe_rows"] for a in fetched),
            sum(a["moe_active"] for a in fetched))


def read(r):
    routed = decode_routing(r)
    if routed is None or not routed[1]:
        return None
    return routed[0] / routed[1]
