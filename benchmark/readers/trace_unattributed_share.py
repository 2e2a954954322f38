"""Share of the step program's device self time that belongs to none
of its named parts (decode, reshard, forward, backward, optimizer):
100 * rest / all, over the whole executions the traced slice holds."""

import trace_scopes


def read(obs):
    scopes = trace_scopes.this_run(obs)
    if not scopes or not scopes["seconds"]:
        return None
    return 100.0 * trace_scopes.seconds_of(
        scopes, [trace_scopes.REST]
    ) / sum(scopes["seconds"].values())
