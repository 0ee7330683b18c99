import os
import sys

import numpy as np
import pytest

# tests see the default single CPU device (the dry-run, and only the
# dry-run, forces 512 host devices in its own process).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402  (after the path insert, before any repro import)

# ---------------------------------------------------------------------------
# Strict numerics for the whole suite (ISSUE 9).
#
# Rank promotion is where silent-broadcast bugs live: an (m,) array
# meeting an (m, 1) array quietly produces (m, m) and every downstream
# reduction still "works".  The bitwise-parity contract makes those
# especially nasty — the numbers stay plausible while the reduction
# geometry changes — so the suite runs with promotion as a hard error.
#
# jax_debug_nans re-runs de-optimized on every NaN producer; it is
# opt-in (REPRO_DEBUG_NANS=1) because it disables the jit caching the
# recompile-guard tests count on.
# ---------------------------------------------------------------------------
jax.config.update("jax_numpy_rank_promotion", "raise")
if os.environ.get("REPRO_DEBUG_NANS") == "1":
    jax.config.update("jax_debug_nans", True)

# ---------------------------------------------------------------------------
# THE backend-parity tolerance, pinned in kernels/ops.py so
# that chip_smoke.py holds the chip to the same pair.
#
# One pinned pair for every pallas-vs-reference comparison in the
# suite — predictions, distances, divergences, fused rounds.  Tests
# must not carry private tolerances for parity checks: loosening THIS
# number is a reviewed decision, not a local tweak.
# ---------------------------------------------------------------------------
from repro.kernels.ops import PARITY_ATOL, PARITY_RTOL  # noqa: E402


def assert_backend_parity(got, want, label: str = ""):
    """Assert a pallas-backend value matches its reference-backend
    counterpart within the pinned parity tolerance."""
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want),
        rtol=PARITY_RTOL, atol=PARITY_ATOL, err_msg=label)


@pytest.fixture
def backend_parity():
    """Fixture handing tests the pinned parity assertion helper."""
    return assert_backend_parity
