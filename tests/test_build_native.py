"""The native loader says which implementation it handed out."""

import pytest

from blendjax._native import build
from blendjax.utils.metrics import metrics

LOADERS = {
    "tiledelta": build.load_tile_delta,
    "palettize": build.load_palettize,
    "tiledelta_palidx": build.load_tile_delta_palidx,
    "render_frame": build.load_render_frame,
}


def _count(name):
    return metrics.report()["counters"].get(name, 0)


@pytest.fixture
def fresh_cache(monkeypatch):
    """Each test resolves the entry points anew (the process-wide cache
    is put back afterwards)."""
    monkeypatch.delenv("BLENDJAX_NO_NATIVE", raising=False)
    monkeypatch.setattr(build, "_CACHE", {})


@pytest.mark.parametrize("name", sorted(LOADERS))
def test_status_names_a_native_entry_point(fresh_cache, name):
    loaded = _count("native.loaded")
    fn = LOADERS[name]()
    if fn is None:
        pytest.skip("no g++ here: the fallback case covers this box")
    assert build.native_status() == {name: True}
    assert LOADERS[name]() is fn  # resolved once per process
    assert _count("native.loaded") == loaded + 1


def test_a_failed_build_is_a_counted_fallback(fresh_cache, monkeypatch):
    """g++ missing or failing still yields a working (Python) producer —
    but ``native_status`` and the ``native.fallbacks`` counter (which
    rides producer telemetry) say so, not only a log line."""
    monkeypatch.setattr(build, "_build", lambda src, tag: None)
    fallbacks = _count("native.fallbacks")
    assert build.load_render_frame() is None
    assert build.load_tile_delta() is None
    assert build.native_status() == {"render_frame": False, "tiledelta": False}
    assert _count("native.fallbacks") == fallbacks + 2


def test_no_native_env_forces_python_without_building(fresh_cache, monkeypatch):
    monkeypatch.setenv("BLENDJAX_NO_NATIVE", "1")
    monkeypatch.setattr(
        build, "_build", lambda *a: pytest.fail("built despite the switch")
    )
    assert all(load() is None for load in LOADERS.values())
    assert build.native_status() == {}
