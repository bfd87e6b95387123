"""Median host time of one request inside the gateway's entry call
(`WebGateway.api_handle`: auth, tenancy, tracing, routing and the forward),
wrapped by the benchmark. The engine never steps inside it."""
import statistics


def read(r):
    return statistics.median(r.gateway_s) * 1e3 if r.gateway_s else None
