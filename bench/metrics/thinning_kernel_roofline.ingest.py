"""The fused decision+update kernel's share of its roofline, %: the least
time the chip could take for the operations and bytes its calls need, over
the time its calls took in the trace."""
from bench import roofline, trace_reduce

KERNEL = "thinning_rmw"


def read(rec):
    secs, calls = trace_reduce.kernel_seconds(rec.trace, KERNEL)
    if calls == 0 or secs <= 0:
        return None
    eng = rec.config["engine"]
    ops, nbytes = roofline.thinning_rmw_cost(
        int(eng["batch"]), len(eng["windows_s"]), eng["policy"])
    return roofline.roofline_share(calls * ops, calls * nbytes, secs,
                                   rec.device_kind)
