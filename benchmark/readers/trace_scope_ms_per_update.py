"""Device self time per optimizer update of one part of the step, from
the trace's own operation metadata (``trace_scopes``): the operations
inside whole executions of the step program whose name stack meets any
of ``include`` and none of ``exclude`` (parts of ``trace_scopes.PARTS``
or scope names), over the updates per dispatch. Nothing without a trace,
and nothing where no operation carries the name (a program from before
the scopes were put in)."""

import trace_scopes


def read(obs, include, exclude=()):
    scopes = trace_scopes.this_run(obs)
    if not scopes:
        return None
    seconds = trace_scopes.seconds_of(scopes, include, exclude)
    return 1e3 * seconds / obs["window"]["chunk"] if seconds else None
