"""Share of the measured window the host spent inside one of the
program's spans: 100 * sum(span) / window. A span the registry never
opened in the window reads 0 (spans are created on first use)."""


def read(obs, span):
    spans = obs["spans"]
    if not spans:
        return None
    return 100.0 * spans.get(span, {}).get("total_s", 0.0) / obs["window"]["seconds"]
