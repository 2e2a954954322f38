"""Share of the traced slice in which a collective ran on a device and
no compute operation did, the worst device."""


def read(obs):
    trace = obs.get("trace")
    if not trace or obs["window"]["chips"] < 2:
        return None
    return 100.0 * max(
        d["collective_exposed_s"] for d in trace["devices"]
    ) / trace["window_s"]
