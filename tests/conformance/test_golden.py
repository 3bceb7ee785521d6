"""Golden-trajectory regression cells.

Each (layout, codec) cell in ``GOLDEN_CELLS`` is frozen as a compressed
.npz under tests/golden/.  A normal run recomputes the cell with the
current engines and demands BIT-EXACT agreement with the fixture, so a
refactor is always diffed against pre-refactor numerics rather than just
against itself.  Fixtures are only ever rewritten deliberately:

    PYTHONPATH=src python -m pytest tests/conformance/test_golden.py \
        --update-golden

and the regenerated .npz files are reviewed like any other diff.

The fixtures are bound to the pinned jax (requirements-dev.txt): they hold
its random streams.  JAX 0.5 flipped ``jax_threefry_partitionable`` to
True, which changes every ``jax.random`` draw, and so every element of
the jax-0.4.37 fixtures.  Recomputed with the old value set, all 9 cells
matched those fixtures bit for bit in the flat state and residual, and to
<= 1.5e-5 in the per-step loss (an XLA:CPU reduction-order difference);
so the drift was the random streams, not the algorithm, and the fixtures
were regenerated under jax 0.9.0.
"""

import os

import numpy as np
import pytest

from _equiv import (GOLDEN_CELLS, compute_golden, golden_path, load_golden,
                    write_golden)


@pytest.mark.parametrize("layout,codec", GOLDEN_CELLS,
                         ids=[f"{l}-{c}" for l, c in GOLDEN_CELLS])
def test_golden_cell(layout, codec, update_golden):
    if update_golden:
        path = write_golden(layout, codec)
        assert os.path.exists(path)
        return
    path = golden_path(layout, codec)
    assert os.path.exists(path), (
        f"missing golden fixture {path}; regenerate with "
        "pytest --update-golden and commit the .npz")
    want = load_golden(layout, codec)
    got = compute_golden(layout, codec)
    np.testing.assert_array_equal(
        got["meta"], want["meta"],
        err_msg=f"{layout}/{codec}: cell geometry drifted — the fixture "
                "was generated for a different (n, T, H, seed)")
    assert set(got) == set(want), (
        f"{layout}/{codec}: fixture arrays {sorted(want)} != computed "
        f"{sorted(got)} (EF residual presence changed?)")
    for name in ("flat", "loss", "step", "residual"):
        if name not in want:
            continue
        np.testing.assert_array_equal(
            got[name], want[name],
            err_msg=f"{layout}/{codec}: '{name}' drifted from the frozen "
                    "trajectory (bit-exactness is the contract; rerun "
                    "with --update-golden only for an intended numerics "
                    "change)")


def test_golden_dir_has_no_strays():
    """Every .npz under tests/golden/ corresponds to a declared cell —
    renamed or abandoned fixtures would otherwise pass silently forever."""
    golden_dir = os.path.dirname(golden_path("flat", "none"))
    have = {f for f in os.listdir(golden_dir) if f.endswith(".npz")}
    want = {os.path.basename(golden_path(l, c)) for l, c in GOLDEN_CELLS}
    assert have == want, (f"stray fixtures: {sorted(have - want)}; "
                          f"missing: {sorted(want - have)}")
