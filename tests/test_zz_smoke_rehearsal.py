"""``chip_smoke.py``'s phases, rehearsed where there is no chip.

The first two rehearsals of the ``on-chip-measurement`` guide (§2): the
phase functions run end to end on the CPU backend at a tiny size — real
producer and env children, the real pipeline and drivers, Pallas paths
replaced by what the CPU takes — and the four-chip phase on four of the
suite's virtual devices. Wrong paths, arguments, control flow, meshes
and sharding rules show here and cost no chip time; the third rehearsal
is tests/test_tpu_compile.py. The four-chip rehearsal is ``slow``: make
it before a four-chip call
(``python -m pytest -m slow tests/test_zz_smoke_rehearsal.py``).

The file is named to run last: these are the dearest of the default
tests (real child processes, several compiles), and the tier-1 command
runs under a time limit.
"""

import pytest

import chip_smoke

TINY = chip_smoke.Sizes(
    shape=(64, 64), batch=4, chunk=2, tile_capacity="8", producers=2,
    cnn_steps=4, former_steps=2,
    former=dict(patch=8, dim=32, depth=1, num_heads=4, num_outputs=16),
    flash_shape=(1, 128, 2, 64), attn_shape=(1, 200, 2, 64), rl_steps=4, mesh_batches=4, mesh_chunk=2,
)


@pytest.mark.parametrize(
    "phase",
    [
        chip_smoke.phase_kernels,
        chip_smoke.phase_headline,
        chip_smoke.phase_rl,
        pytest.param(chip_smoke.phase_four_chips, marks=pytest.mark.slow),
    ],
    ids=lambda p: p.__name__,
)
def test_phase_rehearses_on_cpu(phase):
    out = phase(TINY, 0)
    assert out["phase"] in phase.__name__
