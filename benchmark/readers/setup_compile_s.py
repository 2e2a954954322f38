"""Seconds of set-up spent making the state and getting the cell's
programs ready: the harness's clock around state creation, the
reference-check dispatch and the warm-up dispatches (trace, compile or
cache load, first executions), plus the program's own
``train.compile_ms`` where the AOT path reports it."""


def read(obs):
    return obs["setup"].get("compile_s")
