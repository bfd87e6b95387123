"""bench/trace.py on a synthetic profile with known intervals.

Times below are in microseconds from the window's start; the profile
holds them in picoseconds."""
import pytest
from jax.profiler import ProfileData

from bench import trace


def _plane(pid, name, lines):
    names, out = {}, []
    for lid, (lname, events) in enumerate(lines, 1):
        evs = []
        for ev, start, end in events:
            mid = names.setdefault(ev, len(names) + 1)
            evs.append(f"events {{ metadata_id: {mid} "
                       f"offset_ps: {start * 10**6} "
                       f"duration_ps: {(end - start) * 10**6} }}")
        out.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                   + " ".join(evs) + " }")
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}' for n, i in names.items())
    return (f'planes {{ id: {pid} name: "{name}" ' + " ".join(out)
            + " " + meta + " }")


HOST = _plane(1, "/host:CPU", [
    ("python3", [("bench.window", 0, 100),
                 ("bench.executor.decode", 0, 45),
                 ("PjitFunction(step)", 2, 8),
                 ("bench.engine.step", 45, 85),
                 ("backend_compile", 70, 95)]),
    ("other thread", [("bench.gateway", 0, 100)]),
])
DEVICE0 = _plane(2, "/device:TPU:0", [
    ("XLA Ops", [("fusion.1", 10, 30),
                 ("paged_attention.2", 20, 40),
                 ("while.1", 60, 75),           # holds the next op
                 ("paged_attention.5", 60, 70),
                 ("copy.1", 90, 110),          # clipped at the window's end
                 ("fusion.9", 120, 130)]),     # after the window
    ("XLA Modules", [("jit_step(123)", 0, 200)]),   # not an op line
])
DEVICE1 = _plane(3, "/device:TPU:1", [("XLA Ops", [("fusion.2", 0, 100)])])


def _profile(*planes):
    text = " ".join(planes)
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_busy_ops_kernel_and_gaps():
    s = trace.reduce(_profile(HOST, DEVICE0))
    assert s.devices == 1
    assert s.window_s == pytest.approx(100e-6)
    assert s.busy_s == pytest.approx(55e-6)     # [10,40] [60,75] [90,100]
    # self time, keyed by the enclosing program; where two ops overlap,
    # the overlap is the later one's
    assert s.op_seconds == pytest.approx({"jit_step:fusion": 10e-6,
                                          "jit_step:paged_attention": 30e-6,
                                          "jit_step:while": 5e-6,
                                          "jit_step:copy": 10e-6})
    assert s.kernel_seconds("paged_attention") == pytest.approx(30e-6)
    assert s.kernel_events("paged_attention") == 2
    gaps = dict(s.top_gaps())
    assert gaps == pytest.approx({
        "bench.executor.decode > PjitFunction(step)": 10e-6,   # [0,10]
        "bench.engine.step": 20e-6,                            # [40,60]
        "bench.engine.step > backend_compile": 15e-6})         # [75,90]
    assert s.top_ops(1) == [["jit_step:paged_attention",
                             pytest.approx(30e-6)]]


def test_busy_is_averaged_over_devices():
    s = trace.reduce(_profile(HOST, DEVICE0, DEVICE1))
    assert s.devices == 2
    assert s.busy_s == pytest.approx(77.5e-6)
    assert sum(g for _, g in s.idle_gaps) == pytest.approx(22.5e-6)


def test_a_chip_that_ran_nothing_counts_as_idle():
    s = trace.reduce(_profile(HOST, DEVICE0), chips=2)
    assert s.devices == 1
    assert s.busy_s == pytest.approx(27.5e-6)           # (55 + 0) / 2
    assert sum(g for _, g in s.idle_gaps) == pytest.approx(72.5e-6)


def test_no_device_plane_reads_no_busy_time():
    s = trace.reduce(_profile(HOST))
    assert s.devices == 0 and s.busy_s == 0.0 and s.op_seconds == {}


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(_profile(DEVICE0))


def test_hlo_text_names_are_reduced_to_the_op():
    assert trace.base_name(
        "%paged_attention.5 = bf16[8,8,2,128] custom-call(s32[8] %a)") \
        == "paged_attention"
    assert trace.base_name("copy-start.12") == "copy-start"


def test_union_merges_overlaps():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
