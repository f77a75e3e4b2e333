"""Kernels: the selective-scan forward kernel's share of its roofline.
Memory-bound: the least bytes its calls must move
(`arith_hybrid.scan_min_bytes`: x, dt, B, C, A, D in, y out) over the HBM
peak, over the forward kernel's device time in the trace.  Under full
remat the forward runs twice a mamba layer a step; each call is counted.
The kernel does a fixed amount of vector work an element, so it can sit
under the memory roof without a fault; it cannot read over 100 %."""
from benchmark import arith_hybrid, scan_faces
from benchmark.layer_lib import peak

NAME, UNIT, SOURCE = "selective_scan_roofline.hybrid", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-hybrid-d8"]
KERNEL = scan_faces.FORWARD


def read(spans, trace, counters, cell):
    if trace is None:
        return None
    k = trace.op_time(KERNEL)
    if k["count"] <= 0 or k["seconds"] <= 0:
        return None
    tr = counters["train"]
    per_call = arith_hybrid.scan_min_bytes(
        tr["batch_rows"] / counters["chips"], tr["sequence_length"],
        counters["model"])
    least_s = k["count"] * per_call / peak(counters, "hbm_bytes_per_s")
    return 100.0 * least_s / k["seconds"]
