"""A part's share of its roofline where the part is XLA's own fusions
and not one named kernel: ``trace_kernel_roofline_share``'s quotient over
a scope's device self time.

    100 x max(flops / bf16 peak, bytes / HBM bandwidth) / measured

``measured`` is the self time an update spends in the operations traced
under ``scope`` (``trace_scopes.seconds_of``: forward, backward and, under
remat, the recomputed forward), ``flops`` and ``bytes`` what
``flops/kernels/<work>.py`` says the cell's shapes *require* of the part.
Never clipped: a reading over 100 % is a wrong count. Nothing without a
trace, and nothing where no operation carries the scope (a program from
before the scope was put in)."""

import cells
import trace_scopes


def read(obs, scope, work, which):
    scopes = trace_scopes.this_run(obs)
    if not scopes:
        return None
    seconds = trace_scopes.seconds_of(scopes, [scope]) / obs["window"]["chunk"]
    if not seconds:
        return None
    m = obs["model"]
    required = cells.load_module("flops/kernels", work).required(
        m["kwargs"], m["input_shape"], m["batch_per_chip"], m["precision"],
        which,
    )
    least = cells.load_module(
        "readers", "trace_kernel_roofline_share"
    ).least_seconds(required, obs["device"]["kind"])
    return 100.0 * least / seconds
