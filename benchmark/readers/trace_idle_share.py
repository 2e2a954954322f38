"""Device idle share from the profiler trace of the steady slice:
1 - union of device-operation intervals / window, the worst device."""


def read(obs):
    if not obs.get("trace"):
        return None
    return 100.0 * max(d["idle_share"] for d in obs["trace"]["devices"])
