"""Train + deploy-preparation drivers.

`CoreWorkflow.runTrain` semantics
(`/root/reference/core/src/main/scala/io/prediction/workflow/CoreWorkflow.scala:42-94`)
without Spark: one Python process drives the TPU mesh.  Lifecycle parity:
insert EngineInstance (INIT) -> train -> persist models -> COMPLETED;
failures mark the record and re-raise.  ``prepare_deploy`` mirrors
`Engine.prepareDeploy` (`controller/Engine.scala:173-243`) including the
compat retrain path for non-persisted models.
"""

from __future__ import annotations

import json
import logging
import uuid
from typing import Any, Optional

from ..controller.base import TrainingInterrupted, WorkflowContext
from ..controller.engine import Engine, EngineParams
from ..controller.params import params_to_json
from ..obs import phase_span
from ..storage.event import format_time, now_utc
from ..storage.metadata import EngineInstance
from .model_io import NotPersisted, load_models, save_models
from .params import WorkflowParams

logger = logging.getLogger(__name__)

__all__ = ["run_train", "prepare_deploy", "new_instance_id"]


def new_instance_id() -> str:
    return uuid.uuid4().hex[:16]


def _await_chief_terminal_status(
    md, instance_id: str, timeout: float = 1800.0
) -> None:
    """Non-chief wait for the chief's terminal instance status via the
    shared metadata store (the coordination plane every multi-host
    deployment already shares — the role HBase/ES played for the
    reference).  Raises if the chief recorded a failure or never wrote a
    terminal row (chief died before/inside its chief-only writes)."""
    import time as _time

    deadline = _time.time() + timeout
    while True:
        rec = md.engine_instance_get(instance_id)
        status = rec.status if rec is not None else "MISSING"
        if status == "COMPLETED":
            return
        if status in ("FAILED", "INTERRUPTED"):
            raise RuntimeError(
                f"training {status.lower()} on the chief process "
                f"(instance {instance_id})"
            )
        if _time.time() > deadline:
            raise TimeoutError(
                f"chief process never recorded a terminal status for "
                f"instance {instance_id} (last seen: {status}) within "
                f"{timeout}s"
            )
        _time.sleep(0.05)


def _shared_instance_id() -> str:
    """One instance id for the whole (possibly multi-process) run: chief
    draws it, everyone else receives it via collective broadcast."""
    import jax

    iid = new_instance_id()
    if jax.process_count() > 1:
        import numpy as np
        from jax.experimental import multihost_utils

        buf = np.frombuffer(iid.encode("ascii"), dtype=np.uint8)
        buf = np.asarray(multihost_utils.broadcast_one_to_all(buf))
        iid = buf.tobytes().decode("ascii")
    return iid


def _params_json(engine_params: EngineParams) -> dict[str, str]:
    return {
        "data_source_params": json.dumps(
            {engine_params.data_source[0]: params_to_json(engine_params.data_source[1])}
        ),
        "preparator_params": json.dumps(
            {engine_params.preparator[0]: params_to_json(engine_params.preparator[1])}
        ),
        "algorithms_params": json.dumps(
            [{n: params_to_json(p)} for n, p in engine_params.algorithms]
        ),
        "serving_params": json.dumps(
            {engine_params.serving[0]: params_to_json(engine_params.serving[1])}
        ),
    }


def run_train(
    engine: Engine,
    engine_params: EngineParams,
    ctx: Optional[WorkflowContext] = None,
    workflow_params: Optional[WorkflowParams] = None,
    engine_id: str = "default",
    engine_version: str = "1",
    engine_variant: str = "engine.json",
    engine_factory: str = "",
) -> str:
    """Run training end-to-end; returns the engine instance id.

    Multi-host: all processes run the same training program (SPMD — the
    collectives inside require it); one instance id is broadcast from the
    chief, and only the chief writes the instance/model metadata rows (the
    reference's single Spark driver owns those writes; here every process
    is a "driver", so writes are explicitly gated).
    """
    import os
    import time

    import jax

    from ..obs import get_tracer, tower, xray
    from ..parallel.mesh import describe_devices

    # compile/device observability for the whole training run: every
    # half-iteration compile books into pio_jit_compiles_total{fn} and
    # the device sampler keeps the memory gauges live while we train
    xray.install()
    xray.start_sampler()

    ctx = ctx or WorkflowContext(mode="Training")
    wp = workflow_params or WorkflowParams()
    device = describe_devices()
    md = ctx.storage.get_metadata()
    chief = jax.process_index() == 0
    if jax.process_count() > 1:
        # stamp worker identity into span journals (pio-tower: a
        # cluster run's journals merge and grep by worker)
        get_tracer().set_process_index(jax.process_index())

    instance_id = _shared_instance_id()
    # pio-tower run session: chief writes the persistent run manifest;
    # every worker publishes registry snapshots into the coordination
    # dir (PIO_TPU_COORD_DIR — the multihost harness's rendezvous dir)
    # and the chief merges them into its /metrics and the manifest
    from ..engines import engine_label_of

    session = tower.TowerSession(
        instance_id,
        kind="train",
        meta={
            "engineId": engine_id,
            # pio-forge: the registered spec name rides every train
            # manifest so runlog list/diff can group runs by engine
            "engine": engine_label_of(engine, fallback=engine_id),
            "engineVariant": engine_variant,
            "batch": wp.batch,
            "nDevices": ctx.n_devices,
            # what jax reports, so a manifest says which device its
            # numbers came from
            "platform": device["platform"],
            "deviceKind": device["kind"],
        },
        worker=jax.process_index(),
        n_workers=jax.process_count(),
        coord_dir=os.environ.get("PIO_TPU_COORD_DIR"),
    ).start()
    ei = EngineInstance(
        id=instance_id,
        status="INIT",
        start_time=format_time(now_utc()),
        end_time="",
        engine_id=engine_id,
        engine_version=engine_version,
        engine_variant=engine_variant,
        engine_factory=engine_factory,
        batch=wp.batch,
        mesh_conf={"n_devices": ctx.n_devices},
        **_params_json(engine_params),
    )
    if chief:
        md.engine_instance_insert(ei)

    completed = False
    try:
        ei.status = "TRAINING"
        if chief:
            md.engine_instance_update(ei)
        # keep the trained instances: persistence hooks may rely on state
        # the algorithm built during train
        t_run = time.perf_counter()
        with phase_span("train.run", attrs={"instance": instance_id}):
            algos, models = engine.train_components(ctx, engine_params, wp)
        session.note_train_run(time.perf_counter() - t_run)
        if wp.save_model:
            names = [n for n, _ in engine_params.algorithms]
            with phase_span("train.save_models",
                            attrs={"instance": instance_id}):
                save_models(
                    ctx, instance_id, list(zip(names, algos, models))
                )
        ei.status = "COMPLETED"
        ei.end_time = format_time(now_utc())
        if chief:
            md.engine_instance_update(ei)
        completed = True
        session.finalize(
            "completed", compileCache=xray.compile_cache_summary()
        )
        logger.info("training finished: instance %s", instance_id)
        return instance_id
    except TrainingInterrupted as e:
        ei.status = "INTERRUPTED"
        ei.end_time = format_time(now_utc())
        if chief:
            md.engine_instance_update(ei)
        session.finalize("interrupted", error=str(e))
        raise
    except Exception as e:
        ei.status = "FAILED"
        ei.end_time = format_time(now_utc())
        if chief:
            md.engine_instance_update(ei)
        # a ConvergenceError was already finalized as "aborted" by the
        # watchdog (finalize is idempotent); anything else is "failed"
        session.finalize_error(e)
        raise
    finally:
        if jax.process_count() > 1 and not chief and completed:
            # Outcome agreement rides the SHARED METADATA STORE, not a
            # collective: a collective here could pair out of order with
            # one inside a failing peer's training step and hang.  The
            # chief's terminal status row is the verdict — non-chiefs
            # that finished their SPMD part wait for it (it also orders
            # the chief's COMPLETED row and model files before any
            # process returns or deploys).  Failures INSIDE the SPMD
            # phase are symmetric (every process raises) and skip this;
            # a chief that dies without writing any terminal status is
            # caught by the timeout.
            _await_chief_terminal_status(
                md, instance_id, timeout=wp.chief_wait_timeout_s
            )


def prepare_deploy(
    engine: Engine,
    engine_params: EngineParams,
    instance_id: str,
    ctx: Optional[WorkflowContext] = None,
) -> list[Any]:
    """Load persisted models for serving; retrain any NotPersisted model
    (reference `Engine.prepareDeploy` / `:186-208`)."""
    _, models, _ = prepare_deploy_components(
        engine, engine_params, instance_id, ctx
    )
    return models


def prepare_deploy_components(
    engine: Engine,
    engine_params: EngineParams,
    instance_id: str,
    ctx: Optional[WorkflowContext] = None,
) -> tuple[list[Any], list[Any], Any]:
    """Like :func:`prepare_deploy`, but returns the serving-ready component
    instances too: ``(algorithms, models, serving)``.  Algorithms get the
    serving context attached (``_ctx``) so predict-time event-store reads
    (e.g. the ecommerce template) resolve the same storage the deployment
    uses — the reference reaches this via the Storage global."""
    ctx = ctx or WorkflowContext(mode="Serving")
    algos = engine._algorithms(engine_params)
    for a in algos:
        a._ctx = ctx
    names = [n for n, _ in engine_params.algorithms]
    models = load_models(ctx, instance_id, list(zip(names, algos)))
    missing = [i for i, m in enumerate(models) if isinstance(m, NotPersisted)]
    if missing:
        logger.warning(
            "models %s of instance %s were not persisted; retraining those",
            missing, instance_id,
        )
        _, retrained = engine.train_components(
            ctx, engine_params, WorkflowParams(save_model=False),
            algo_indices=missing,
        )
        for i, model in zip(missing, retrained):
            models[i] = model
    serving = engine._serving(engine_params)
    return algos, models, serving
