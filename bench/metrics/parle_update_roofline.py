"""The Parle update kernels' share of their HBM roofline
(kernels/parle_update.py, inner Eq. 8a-8b and sync Eq. 8c-8d): the bytes
their calls in the traced window must move, from the operand shapes
(bench/flops.py), over the HBM peak, divided by the summed device time of
their events.  Nothing to read where the kernels did not run."""
from bench import flops

KERNELS = ("parle_update_leaf", "parle_sync_leaf")


def read(art):
    if art.get("kind") != "train" or not art["args"].use_kernel:
        return None
    op_time = art["trace"]["op_time"]
    t = sum(v for k, v in op_time.items() if any(n in k for n in KERNELS))
    if t <= 0:
        return None
    n, L = art["args"].replicas, art["args"].L
    sizes = flops.param_sizes(art["config"])
    per_round = sum(L * flops.parle_inner_bytes(s, n)
                    + flops.parle_sync_bytes(s, n) for s in sizes)
    hbm = flops.peaks(art["devices"][0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * art["rounds"] * per_round / hbm / t
