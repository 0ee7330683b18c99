"""The system under test: the scan engine, ``repro.core.engine.run``.

One experiment is one ``engine.run(substrate, protocol, X, Y)`` over
the stream's T rounds and m learners, on one chip or with the learner
axis sharded over ``make_learner_mesh(chips)``.  It returns when the
numpy result is assembled, which waits for the device.  The substrate
comes from the configuration's own system module (``sv``, ``rff``).
"""
from __future__ import annotations

import os
import sys

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")


def import_program():
    """Put the program's ``src`` on the path (it is not installed)."""
    if SRC not in sys.path:
        sys.path.insert(0, SRC)


class System:
    def __init__(self, substrate, cfg: dict, protocol: dict):
        import_program()
        from repro.core import engine
        from repro.core.protocol import ProtocolConfig
        from repro.launch.mesh import make_learner_mesh

        self._engine = engine
        self.substrate = substrate
        self.pcfg = ProtocolConfig(**protocol)
        self.mesh = make_learner_mesh(cfg["chips"]) if cfg["chips"] > 1 else None

    def run(self, X: np.ndarray, Y: np.ndarray):
        """One experiment: the call the window times."""
        return self._engine.run(self.substrate, self.pcfg, X, Y, mesh=self.mesh)

    @staticmethod
    def series(res) -> dict:
        """Per-round (T,) series of one result: summed loss, summed errors,
        bytes, sync flags, and the compression error of each sync (0
        where none, or where the substrate compresses nothing)."""
        T = len(res.cumulative_loss)
        sync = np.zeros(T, bool)
        sync[res.sync_rounds] = True
        eps = np.zeros(T)
        if len(res.eps_history):
            eps[res.sync_rounds] = res.eps_history
        return {"loss": np.diff(res.cumulative_loss, prepend=0.0),
                "err": np.diff(res.cumulative_errors, prepend=0.0),
                "bytes": np.diff(res.cumulative_bytes, prepend=0),
                "sync": sync, "eps": eps}

    def hlo_text(self, X: np.ndarray, Y: np.ndarray) -> str:
        """Optimized HLO of the program ``run`` dispatches.  Where the
        engine no longer exposes it this raises, so that a traced run
        stops rather than leave the kernel rooflines silent."""
        import jax.numpy as jnp

        eng = self._engine
        axes = None if self.mesh is None else eng.learner_axes_of(self.mesh)
        fn = eng._jitted(self.substrate, self.pcfg.kind, False, False, False,
                         "coordinator", self.mesh, axes, False)
        lowered = fn.lower(eng.params_of(self.pcfg), jnp.asarray(X), jnp.asarray(Y))
        return lowered.compile().as_text()
