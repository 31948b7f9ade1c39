"""fedml_tpu — a TPU-native federated / distributed ML framework.

Public surface mirrors the reference FedML (``python/fedml/__init__.py``):

    import fedml_tpu as fedml
    args = fedml.init()
    device = fedml.device.get_device(args)
    dataset, output_dim = fedml.data.load(args)
    model = fedml.model.create(args, output_dim)
    fedml.FedMLRunner(args, device, dataset, model).run()

or the one-liners ``run_simulation()`` / ``run_cross_silo_server()`` /
``run_cross_silo_client()``. The compute plane is jax/XLA/pjit/pallas; the
WAN message plane lives in ``core.distributed``.
"""

from __future__ import annotations

import logging
import os
import random
from typing import Any, Dict, Optional

import numpy as np

__version__ = "0.1.0"

from . import constants  # noqa: E402
from .arguments import Arguments, default_config, load_arguments  # noqa: E402
from .constants import (  # noqa: E402
    FEDML_TRAINING_PLATFORM_CROSS_DEVICE,
    FEDML_TRAINING_PLATFORM_CROSS_SILO,
    FEDML_TRAINING_PLATFORM_SIMULATION,
)
from .runner import FedMLRunner  # noqa: E402
from . import device  # noqa: E402


from . import data  # noqa: E402  (fedml.data.load lives on the subpackage)


class _ModelNS:
    @staticmethod
    def create(args, output_dim=None, seed=None):
        from .models.model_hub import create as _create

        return _create(args, output_dim, seed)


model = _ModelNS()


def _seed_everything(seed: int) -> None:
    random.seed(seed)
    np.random.seed(seed)
    os.environ.setdefault("PYTHONHASHSEED", str(seed))


def init(args: Optional[Any] = None, override: Optional[Dict[str, Any]] = None) -> Any:
    """Parse config, seed RNGs, init middleware singletons and mlops.

    Reference: ``python/fedml/__init__.py:64`` (init) — env-version fetch and
    per-platform arg mangling are dropped; middleware init mirrors
    ``_init_*`` + mlops hookup at ``__init__.py:156``.
    """
    if args is None:
        args = load_arguments(override=override)
    elif override:
        for k, v in override.items():
            setattr(args, k, v)

    # multi-host slices must attach BEFORE the first JAX backend touch
    # (jax.distributed cannot initialize later); no-op when single-process
    from .parallel.multihost import init_distributed

    _pid = getattr(args, "process_id", None)
    init_distributed(
        coordinator_address=getattr(args, "coordinator_address", None),
        num_processes=int(getattr(args, "num_processes", 0)) or None,
        process_id=int(_pid) if _pid is not None else None,
    )

    from .utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    logging.basicConfig(
        level=logging.INFO, format="[fedml_tpu] %(asctime)s %(levelname)s %(name)s: %(message)s"
    )
    _seed_everything(int(getattr(args, "random_seed", 0)))

    from .core.dp.fedml_differential_privacy import FedMLDifferentialPrivacy
    from .core.fhe.fhe_agg import FedMLFHE
    from .core.security.fedml_attacker import FedMLAttacker
    from .core.security.fedml_defender import FedMLDefender

    FedMLAttacker.get_instance().init(args)
    FedMLDefender.get_instance().init(args)
    FedMLDifferentialPrivacy.get_instance().init(args)
    FedMLFHE.get_instance().init(args)

    from .mlops import MLOpsRuntime

    MLOpsRuntime.get_instance().init(args)
    return args


def run_simulation(backend: str = constants.FEDML_SIMULATION_TYPE_SP, args: Optional[Any] = None):
    """One-line simulation entry (reference: launch_simulation.py:9)."""
    args = args or default_config(FEDML_TRAINING_PLATFORM_SIMULATION, backend=backend)
    args.training_type = FEDML_TRAINING_PLATFORM_SIMULATION
    args.backend = backend
    args = init(args)
    dev = device.get_device(args)
    dataset, output_dim = data.load(args)
    mdl = model.create(args, output_dim)
    runner = FedMLRunner(args, dev, dataset, mdl)
    return runner.run()


def _run_platform(training_type: str, role: str, args: Optional[Any] = None):
    """Shared launch body for the role-based platforms (cross-silo/cloud)."""
    args = args or load_arguments(training_type=training_type)
    args.training_type = training_type
    args.role = role
    args = init(args)
    dev = device.get_device(args)
    dataset, output_dim = data.load(args)
    mdl = model.create(args, output_dim)
    return FedMLRunner(args, dev, dataset, mdl).run()


def _run_cross_silo(role: str, args: Optional[Any] = None):
    return _run_platform(FEDML_TRAINING_PLATFORM_CROSS_SILO, role, args)


def run_cross_silo_server(args: Optional[Any] = None):
    """Reference: launch_cross_silo_horizontal.py."""
    return _run_cross_silo("server", args)


def run_cross_silo_client(args: Optional[Any] = None):
    return _run_cross_silo("client", args)


def _run_cross_cloud(role: str, args: Optional[Any] = None):
    """Reference: launch_cross_cloud.py:8 — Cheetah entry."""
    return _run_platform(constants.FEDML_TRAINING_PLATFORM_CROSS_CLOUD, role, args)


def run_cross_cloud_server(args: Optional[Any] = None):
    return _run_cross_cloud("server", args)


def run_cross_cloud_client(args: Optional[Any] = None):
    return _run_cross_cloud("client", args)


def run_hierarchical_cross_silo_server(args: Optional[Any] = None):
    """Reference: launch_cross_silo_hi.py — same managers, hierarchical scenario."""
    if args is not None:
        args.scenario = "hierarchical"
    return _run_cross_silo("server", args)


def run_hierarchical_cross_silo_client(args: Optional[Any] = None):
    if args is not None:
        args.scenario = "hierarchical"
    return _run_cross_silo("client", args)


__all__ = [
    "init",
    "run_simulation",
    "run_cross_silo_server",
    "run_cross_silo_client",
    "run_cross_cloud_server",
    "run_cross_cloud_client",
    "run_hierarchical_cross_silo_server",
    "run_hierarchical_cross_silo_client",
    "FedMLRunner",
    "Arguments",
    "load_arguments",
    "default_config",
    "device",
    "data",
    "model",
    "constants",
]
