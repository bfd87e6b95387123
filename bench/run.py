#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload qwen3-1.7b.chat --seed 7 --seconds 30 --trace 0

Set-up (counted in `setup_s`, from process start): the weights made on the
device from the seed, the deployment (one replica on each chip of the
cell) applied and Ready, and a warm-up that serves every prompt length of
the cell's traffic on every replica. Then the window: the mix's clients
send, in a closed or an open loop (bench/traffic.py), for `--seconds` of
host time. With
`--trace 1` the window runs under the JAX profiler and the per-layer
metrics are printed instead of the end-to-end ones.

After the window the program's state is freed and the output check runs
(bench/check.py). Its numbers and limits are the last lines on standard
error and the last key of the result line, which is the last line on
standard output. Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

OUT = ".bench_out"            # under the checkout: compile cache, traces


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Reading:
    """What a per-layer metric reader (bench/metrics/<name>.py) gets."""
    steps: list
    gateway_s: list
    lowered: int
    window_s: float
    trace: Optional[object]
    shape: object
    peaks: Optional[object]
    chips: int
    kv_bytes: int
    q_bytes: int


def compile_cache(root: Path):
    """JAX's persistent cache at a fixed path inside the checkout, every
    program kept, so only a checkout's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(root / OUT / "jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_chip: bool = True,
             backend: Optional[str] = None, hw=None, peaks=None) -> dict:
    """One run; returns the result line's object and, under "_summary",
    "_records" and "_served", what the clients saw and what each replica
    served. Off the chip (tests) pass `require_chip=False`, a
    paged-attention `backend`, the roofline constants `hw` and, for the
    readers that count work against a chip's peaks, `peaks`."""
    import jax
    from bench import check, loader, model, traffic
    from bench.client import Clients, summarize
    from bench.harness import Plane, Probes, deployment, engine_factory
    from bench.peaks import peaks_for
    from repro.engine import factory as F

    bench = loader.benchmark(root)
    cell, cfg_entry, e2e, layer = loader.cell(bench, workload)
    spec = model.load(root / cfg_entry["file"])
    mix = traffic.load(root, cell["traffic"])
    dep = deployment(spec)
    devices = jax.devices()
    dev = devices[0]
    if require_chip and dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev.platform!r}")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"{workload} needs {cell['chips']} chips, "
                         f"JAX found {len(devices)}")
    used = devices[:cell["chips"]]           # one replica on each
    if require_chip:
        from repro.config import hardware_for
        hw, peaks = hardware_for(dev), peaks_for(dev.device_kind)
    words = model.seed_words(seed)
    probes = Probes(annotate=jax.profiler.TraceAnnotation if trace else None)
    jax.monitoring.register_event_listener(probes.on_event)
    jax.monitoring.register_event_duration_secs_listener(probes.on_event)

    cfg = model.model_config(spec, root)
    params = model.make_weights(spec, seed, dev, root)
    plane = Plane(cfg, engine_factory(cfg, params, used,
                                      max_num_seqs=dep["max_num_seqs"],
                                      num_blocks=dep["num_blocks"], hw=hw,
                                      backend=backend, probes=probes),
                  hw, probes, replicas=len(used),
                  routing_policy=dep.get("routing_policy"))
    del params
    kv_bytes = plane.engines[0].executor.pool["k"].dtype.itemsize
    q_bytes = jax.numpy.dtype(spec["torch_dtype"]).itemsize
    plane.warm_up(traffic.warm_up_requests(mix, spec["vocab_size"]),
                  dep["max_num_seqs"] * len(used))
    clients = Clients(
        plane.client, cfg.name,
        traffic.requests(mix, spec["vocab_size"], words),
        clients=mix.get("clients", 0),
        arrivals=traffic.arrivals(mix) if mix["loop"] == "open" else None,
        annotate=probes.annotate)
    setup_s = time.perf_counter() - t_start
    log(f"{workload}: set-up {setup_s:.2f} s; window {seconds} s")

    trace_dir = root / OUT / "trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    probes.reset()
    probes.on = True
    t0 = time.perf_counter()
    with probes.span("bench.window"):
        clients.start(t0, t0 + seconds)
        plane.drive(clients, t0 + seconds)
        t1 = time.perf_counter()
    probes.on = False
    jax.monitoring.unregister_event_listener(probes.on_event)
    jax.monitoring.unregister_event_duration_listener(probes.on_event)
    if trace:
        jax.profiler.stop_trace()
    summary = summarize(clients.records, t0, t1)
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in used)
    records, served = clients.records, plane.served()
    clients.client = None
    plane.close()
    del plane, clients
    gc.collect()               # the plane's cycles hold the params and pool
    log(f"{workload}: window {summary}; JAX events in it "
        f"{dict(probes.events)}; (device, requests finished) of each "
        f"replica {served}")

    summ = None
    if trace:
        from bench import trace as tr
        summ = tr.reduce(tr.load(str(trace_dir)), chips=len(used))
        shutil.rmtree(trace_dir, ignore_errors=True)

    t_check = time.perf_counter()
    weights = model.make_weights(spec, seed, dev, root)
    result = check.run(model.reference_module(root, spec), weights, spec,
                       records, words, F.MAX_MODEL_LEN,
                       float(spec["check"]["max_logit_gap"]))
    del weights
    log(f"{workload}: output check {time.perf_counter() - t_check:.2f} s")

    if trace:
        reading = Reading(steps=probes.steps, gateway_s=probes.gateway_s,
                          lowered=probes.lowered, window_s=t1 - t0,
                          trace=summ, shape=model.shape(spec, root),
                          peaks=peaks,
                          chips=cell["chips"], kv_bytes=kv_bytes,
                          q_bytes=q_bytes)
        metrics = {}
        for m in layer:
            reader = loader.load_module(root / "bench" / "metrics"
                                        / f"{m['name']}.py")
            v = reader.read(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(summary, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in e2e if values.get(m["name"]) is not None}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    line = {"correct": result["correct"],
            "attempted": summary["attempted"], "failed": summary["failed"],
            "metrics": metrics, "device": device}
    if summ is not None:
        device.update(busy_s=summ.busy_s, window_s=summ.window_s)
        line["breakdown"] = {"device_ops": summ.top_ops(),
                             "idle_gaps": summ.top_gaps()}
    line["compared"] = {"max_logit_gap": {
        "value": result["max_logit_gap"], "limit": result["limit"],
        "tokens": result["tokens"], "requests": result["requests"]}}
    line["_summary"], line["_records"], line["_served"] = (summary, records,
                                                           served)
    return line


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    compile_cache(ROOT)
    line = run_cell(ROOT, args.workload, args.seed, args.seconds,
                    bool(args.trace), t_start=T_START)
    for k in ("_summary", "_records", "_served"):
        line.pop(k)
    c = line["compared"]["max_logit_gap"]
    log(f"compared: max_logit_gap {c['value']!r} limit {c['limit']!r} "
        f"over {c['tokens']} served tokens of {c['requests']} requests; "
        f"correct {line['correct']}")
    print(json.dumps(line), flush=True)
    sys.stderr.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
