"""RFF configurations: ``RFFSubstrate`` (hinge-loss SGD over D random
Fourier features, one fused ``rff_step`` kernel per round under
``backend="pallas"``) in the scan engine."""
from __future__ import annotations

from chipbench.systems.engine import System, import_program


def build(cfg: dict, protocol: dict) -> System:
    import_program()
    from repro.core.rff import RFFSpec
    from repro.core.substrate import RFFSubstrate

    spec = RFFSpec(dim=cfg["dim"], num_features=cfg["num_features"],
                   gamma=cfg["gamma"], seed=cfg["rff_seed"])
    sub = RFFSubstrate(spec=spec, eta=cfg["eta"], lam=cfg["lam"],
                       loss=cfg["loss"], backend=cfg["backend"])
    return System(sub, cfg, protocol)
