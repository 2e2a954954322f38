"""Device busy time per optimizer update, from the executions of the
step program that the traced slice holds whole: the union of device
operations inside one execution of the program that took most of the
slice (the fused step), over the updates per dispatch. Nothing where the
slice holds no whole execution."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace.get("modules"):
        return None
    step = max(
        trace["modules"].values(),
        key=lambda m: m["executions"] * m["seconds_per_execution"],
    )
    return 1e3 * step["busy_s_per_execution"] / obs["window"]["chunk"]
