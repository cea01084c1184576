"""What every deployment shares: the server, its weights, the verdict."""

from __future__ import annotations

import numpy as np

from bench.spec import sub_seed

MODEL_ID = "model"
# Time a warm-up request may take, compilation included.
WARM_TIMEOUT_S = 600.0


class Deployment:
    """One configuration served from one process.

    ``server`` is a ``GnnServeEngine``, or an ``EngineRouter`` over one
    replica per chip when ``replicas`` > 1; ``engines`` lists the engines
    either way.  Engine options other than those the configuration fixes
    stay at the program's defaults.
    """

    def __init__(self, cfg: dict, seed: int, replicas: int):
        import jax

        from repro.serving import EngineRouter, GnnServeEngine

        opts = dict(cfg["engine"])
        if replicas > 1:
            self.server = EngineRouter(replicas, **opts)
            self.engines = list(self.server.replicas)
        else:
            self.server = GnnServeEngine(**opts)
            self.engines = [self.server]
        self.slots = int(opts["slots"])
        self.replicas = replicas
        self.model = self.arch.program_model(cfg)
        self.params = self.arch.init_params(
            cfg, jax.random.PRNGKey(sub_seed(seed, "weights")))
        self._host_params = None

    def register(self, **kwargs) -> None:
        from repro.serving import EngineRouter

        if isinstance(self.server, EngineRouter):
            kwargs["hot"] = True  # every replica serves the model
        self.server.register(MODEL_ID, self.model, self.params, **kwargs)

    def host_params(self) -> dict:
        """The weights as float64 numpy, for the reference (read from the
        device once, and kept when the program's state is freed)."""
        import jax

        if self._host_params is None:
            self._host_params = jax.tree.map(
                lambda x: np.asarray(x, np.float64),
                jax.device_get(self.params))
        return self._host_params

    def trace_count(self) -> int:
        return sum(e.pool.trace_count for e in self.engines)

    def free(self) -> None:
        """Drop the program's state: engines, executors, device weights."""
        self.host_params()
        self.server = None
        self.engines = []
        self.params = None


def verdict(cfg: dict, gaps: list) -> dict:
    """The compared number (widest gap), its limit, and the answers over it."""
    limit = float(cfg["check"]["max_error"])
    worst = max(gaps) if gaps else float("inf")
    return {"max_error": worst, "limit": limit, "checked": len(gaps),
            "wrong": [i for i, g in enumerate(gaps) if not g <= limit]}
