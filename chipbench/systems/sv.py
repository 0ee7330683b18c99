"""SV configurations: ``SVSubstrate`` (a budgeted support-vector
expansion per learner, the fused ``sv_predict`` and ``quadform``
kernels under ``backend="pallas"``) in the scan engine."""
from __future__ import annotations

from chipbench.systems.engine import System, import_program


def build(cfg: dict, protocol: dict) -> System:
    import_program()
    from repro.core.learners import LearnerConfig
    from repro.core.rkhs import KernelSpec
    from repro.core.substrate import SVSubstrate

    lcfg = LearnerConfig(algo="kernel_sgd", loss=cfg["loss"], eta=cfg["eta"],
                         lam=cfg["lam"], budget=cfg["budget"],
                         evict=cfg["eviction"],
                         kernel=KernelSpec(cfg["kernel"], gamma=cfg["gamma"]),
                         dim=cfg["dim"])
    sub = SVSubstrate(lcfg=lcfg, compress_method=cfg["compression"],
                      backend=cfg["backend"])
    return System(sub, cfg, protocol)
