"""fedml_tpu — a TPU-native federated & distributed ML framework.

Brand-new design with the capabilities of the reference FL platform
(see /root/repo/SURVEY.md): FL simulation where an entire round is one jitted
SPMD program over a named ``client`` mesh axis; cross-silo/cross-device FL
with a message-driven FSM at the WAN boundary; pluggable trust/privacy
(defenses, DP, secure aggregation); an LLM fine-tuning path on XLA FSDP with
Pallas attention; data/model zoos; federated analytics; observability.

Public API parity (reference ``python/fedml/__init__.py:67+``):

    import fedml_tpu as fedml
    args = fedml.init()
    device = fedml.device.get_device(args)
    dataset, output_dim = fedml.data.load(args)
    model = fedml.model.create(args, output_dim)
    fedml.FedMLRunner(args, device, dataset, model).run()

or the one-liner ``fedml_tpu.run_simulation(backend="tpu")``.
"""

from __future__ import annotations

import logging
import os
import random
from typing import Any, Optional

import numpy as np

from .arguments import Arguments, add_args, load_arguments
from .runner import FedMLRunner
from . import constants
from .core import mlops, obs

__version__ = "0.1.0"


def _place_compile_cache() -> None:
    """The one place the persistent XLA compilation cache is given a
    directory. ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself, so
    nothing is set in code. Otherwise the cache lives at
    ``<checkout>/.jax_cache`` — the directory is part of the cache key, so
    it is fixed by the package's location and never built from a platform
    string, pid, time or temp dir. A CPU-primary process gets no default:
    XLA:CPU executables are built for the compiling host's CPU features,
    and a cache that travels with the checkout could load code another
    machine cannot run (the tests place a per-session one through the
    variable, ``tests/conftest.py``). Touches ``jax.config`` only; no
    backend is initialised here."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu":
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache"))


_place_compile_cache()

_logger_configured = False


def _setup_logging() -> None:
    global _logger_configured
    if not _logger_configured:
        logging.basicConfig(
            level=logging.INFO,
            format="[fedml_tpu] %(asctime)s %(levelname)s %(name)s: %(message)s")
        # orbax/absl emit INFO for every checkpoint IO op — far too chatty
        logging.getLogger("absl").setLevel(logging.WARNING)
        _logger_configured = True


def init(args: Optional[Arguments] = None, **overrides: Any) -> Arguments:
    """Parse config + seed RNGs (reference ``__init__.py:67,103-108``).

    With no ``args``, reads ``--cf <yaml>`` from the CLI if present; keyword
    overrides always win (convenient for tests/notebooks).
    """
    _setup_logging()
    if args is None:
        cli = add_args()
        merged = dict(rank=cli.rank, role=cli.role, run_id=cli.run_id)
        merged.update(overrides)  # explicit overrides beat CLI bootstrap
        args = load_arguments(cli.yaml_config_file, **merged)
    else:
        for k, v in overrides.items():
            setattr(args, k, v)
    # the knobs first, so that the span below already obeys obs_tracing
    obs.configure(args)
    with obs.span("setup.init", root=True):
        seed = int(getattr(args, "random_seed", 0))
        random.seed(seed)
        np.random.seed(seed)
        mlops.init(args)
    return args


def run_simulation(backend: str = "tpu", args: Optional[Arguments] = None,
                   **overrides: Any) -> Any:
    """One-call simulation entrypoint (reference ``launch_simulation.py:9``)."""
    from . import data as data_mod
    from . import model as model_mod

    args = init(args, backend=backend, **overrides)
    args.training_type = constants.FEDML_TRAINING_PLATFORM_SIMULATION
    fed, output_dim = data_mod.load(args)
    bundle = model_mod.create(args, output_dim)
    runner = FedMLRunner(args, dataset=fed, model=bundle)
    result = runner.run()
    save_path = getattr(args, "save_model_path", None)
    if save_path and isinstance(result, dict) and "params" in result:
        from .serving import save_model
        save_model(result["params"], os.path.expanduser(str(save_path)))
    return result


def run_cross_silo_server(args: Optional[Arguments] = None, **overrides: Any):
    args = init(args, **overrides)
    args.training_type = constants.FEDML_TRAINING_PLATFORM_CROSS_SILO
    args.role = "server"
    from . import data as data_mod
    from . import model as model_mod
    fed, output_dim = data_mod.load(args)
    bundle = model_mod.create(args, output_dim)
    return FedMLRunner(args, dataset=fed, model=bundle).run()


def run_cross_silo_client(args: Optional[Arguments] = None, **overrides: Any):
    args = init(args, **overrides)
    args.training_type = constants.FEDML_TRAINING_PLATFORM_CROSS_SILO
    args.role = "client"
    from . import data as data_mod
    from . import model as model_mod
    fed, output_dim = data_mod.load(args)
    bundle = model_mod.create(args, output_dim)
    return FedMLRunner(args, dataset=fed, model=bundle).run()
