"""Subprocess replica entrypoint: ``python -m fedml_tpu.serving.replica_main
<spec.json>`` builds a :class:`CheckpointPredictor` from a model artifact
and serves it over HTTP until killed.

This is the process-isolation analogue of the reference's container
deployment (``model_scheduler/device_model_deployment.py:61-333``: one
docker container per replica): a replica crash — up to ``kill -9`` — takes
down this process only, never the gateway or its siblings; the replica
controller's health check replaces the corpse. No container runtime exists
in this environment, so the isolation boundary is the OS process.

Spec schema (JSON):
  ``args``        flat config dict (model/dataset fields the bundle needs)
  ``params_path`` msgpack model artifact (``serving.save_model``)
  ``output_dim``  classifier width
  ``port_file``   where to write the bound port (the parent polls it)
  ``platform``    jax platform for the replica (default "cpu" — serving
                  replicas must not fight the trainer for the chip)
"""

from __future__ import annotations

import json
import os
import sys


def main() -> None:
    spec_path = sys.argv[1]
    with open(spec_path) as f:
        spec = json.load(f)
    # the SPEC decides the platform — an inherited JAX_PLATFORMS=tpu from
    # the trainer process must not make every replica fight it for the
    # chip (the whole point of platform='cpu' isolation)
    os.environ["JAX_PLATFORMS"] = spec.get("platform", "cpu")
    import jax
    jax.config.update("jax_platforms",
                      os.environ["JAX_PLATFORMS"].split(",")[0])

    from types import SimpleNamespace
    from . import CheckpointPredictor, FedMLInferenceRunner

    args = SimpleNamespace(**spec["args"])
    # a replica is a first-class observability citizen: its own JSONL
    # sink (run_<id>.jsonl, distinguished by pid-suffixed run_id so
    # sibling replicas never interleave one file), the obs knobs from
    # the spec's flat config, and — in batch mode — the engine's flight
    # recorder dumping on SIGTERM (the platform's shutdown signal)
    from fedml_tpu.core import mlops
    args.run_id = f"{getattr(args, 'run_id', '0')}_replica{os.getpid()}"
    mlops.init(args)
    # serving chaos in a SUBPROCESS replica is allowed to be lethal:
    # crash-at-request-N exits this process for real (the gateway's
    # health-aware failover + the set's health check are what recover)
    from fedml_tpu.core.chaos import ServingChaosInjector
    chaos = ServingChaosInjector.from_args(args, hard_crash=True)
    if spec.get("kind") == "causal_lm":
        # LLM template replica: chat route mounted, artifact + bundle
        # rebuilt from the spec's flat config
        from .llm_template import CausalLMPredictor, ChatCompletionRunner
        predictor = CausalLMPredictor.from_artifact(
            args, spec["params_path"])
        runner = ChatCompletionRunner(predictor, chaos=chaos)
        if predictor.engine is not None:
            from fedml_tpu.core.obs import flight as obs_flight
            obs_flight.install_signal_dump(
                predictor.engine.flight, predictor.engine._flight_path)
    else:
        predictor = CheckpointPredictor.from_files(
            args, spec["params_path"], int(spec["output_dim"]))
        runner = FedMLInferenceRunner(predictor, chaos=chaos)
    port = runner.start()
    # graceful SIGTERM drain (the drain-before-kill scale-down path):
    # stop accepting, let the engine finish/flush, then exit 0 — so a
    # scale-down victim's in-flight work resolves instead of dying
    # mid-stream. SIGKILL remains the crash path chaos exercises.
    import signal
    import threading
    stop_evt = threading.Event()

    def _graceful(_sig, _frm):
        stop_evt.set()

    signal.signal(signal.SIGTERM, _graceful)
    port_file = spec.get("port_file")
    if port_file:
        tmp = f"{port_file}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            f.write(str(port))
        os.replace(tmp, port_file)
    # serve until terminated; the server thread keeps running while the
    # main thread waits on the shutdown signal
    while not stop_evt.wait(0.5):
        if not runner._thread.is_alive():
            return
    close = getattr(predictor, "close", None)
    if callable(close):
        try:
            close()   # engine stop: drains the loop + flushes metrics
        except Exception:
            pass
    runner.stop()


if __name__ == "__main__":
    main()
