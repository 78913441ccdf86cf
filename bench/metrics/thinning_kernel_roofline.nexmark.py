"""The fused decision+update kernel's share of its roofline in the NEXmark
cell, %, as ``thinning_kernel_roofline.ingest`` computes it, with each call
priced at its lanes: a micro-batch of ``batch`` bids runs as ``batch``
times the lanes per bid (``SinkStats.fanout_lanes`` over the bids)."""
from bench import roofline, trace_reduce

KERNEL = "thinning_rmw"


def read(rec):
    secs, calls = trace_reduce.kernel_seconds(rec.trace, KERNEL)
    lanes = rec.window.sink_stats.get("fanout_lanes")
    if calls == 0 or secs <= 0 or not lanes or rec.window.events == 0:
        return None
    eng = rec.config["engine"]
    per_event = round(lanes / rec.window.events)
    ops, nbytes = roofline.thinning_rmw_cost(
        int(eng["batch"]) * per_event, len(eng["windows_s"]), eng["policy"])
    return roofline.roofline_share(calls * ops, calls * nbytes, secs,
                                   rec.device_kind)
