"""A kernel's share of its roofline: the least time the chip's peaks
(``peaks.json``) allow for the work the cell's shapes *require* of it in
one update (``flops/kernels/<work>.py``: the same count whatever
implements the kernel), over the device self time an update spends in
the operations that carry the kernel's name (``%<kernel>.N`` in the
trace, ``trace_scopes.by_scope(...)["ops"]``):

    100 x max(flops / bf16 peak, bytes / HBM bandwidth) / measured

Work the kernel adds of its own (padding, a recomputed product, a second
run under remat) lengthens the measured time and not the required work,
so it lowers the share. Nothing without a trace, and nothing where no
operation carries the name: a cell whose policy fell back to XLA shows
it by the metric's absence, never by a 0."""

import re

import cells
import flops
import trace_scopes


def kernel_seconds(scopes: dict, kernel: str) -> float:
    """Self seconds per execution of the operations named ``kernel`` or
    ``kernel.N`` (an event's name is its whole HLO line)."""
    name = re.compile(rf"^%?{re.escape(kernel)}(\.\d+)?$")
    return sum(
        s for op, (s, _meta) in scopes["ops"].items()
        if name.match(op.split(" = ")[0].strip())
    )


def least_seconds(work: dict, device_kind: str) -> float:
    peak = flops.peak(device_kind)
    return max(
        work["flops"] / peak["bf16_flops_per_s"],
        work["bytes"] / peak["hbm_bytes_per_s"],
    )


def read(obs, kernel, work, which):
    scopes = trace_scopes.this_run(obs)
    if not scopes:
        return None
    seconds = kernel_seconds(scopes, kernel) / obs["window"]["chunk"]
    if not seconds:
        return None
    m = obs["model"]
    required = cells.load_module("flops/kernels", work).required(
        m["kwargs"], m["input_shape"], m["batch_per_chip"], m["precision"],
        which,
    )
    return 100.0 * least_seconds(required, obs["device"]["kind"]) / seconds
