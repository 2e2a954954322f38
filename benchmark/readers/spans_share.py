"""Share of the measured window the host spent inside any of several of
the program's spans that never overlap (one thread opens them one after
another): 100 * sum over the spans / window. A span the registry never
opened in the window adds 0, as in ``span_share``."""


def read(obs, spans):
    seen = obs["spans"]
    if not seen:
        return None
    return 100.0 * sum(
        seen.get(s, {}).get("total_s", 0.0) for s in spans
    ) / obs["window"]["seconds"]
