"""The system under test, built from the benchmark's configuration and
fitted tables: the program's ``PlacementRuntime`` over a ``DecisionEngine``
and a ``TwinBackend``, served with ``serve_stream(..., array_backend="jax")``.

This is the only module of the benchmark that imports the program. The
benchmark hands it the deployment (app parameters, configs, fleet, pricing,
policy) and the fitted tables as the program's own model objects, and takes
back the served records, the program's counters (``engine.jax_stats``,
``stream_stats``, the core's ``compile_stats``) and its kernel names.

``TimedTwin`` wraps the twin's ``execute_many``: at that call a chunk's
decisions are on the host, so the time it is entered is when the chunk's
decisions reached the host, and the time it returns is when the chunk's
executed outcomes are complete. It records one entry per chunk.
"""

from __future__ import annotations

import time

import numpy as np


class Chunk:
    """What the benchmark records of one chunk at the twin boundary."""

    __slots__ = ("t_in", "t_out", "n", "stats")

    def __init__(self, t_in: float, t_out: float, n: int, stats: dict):
        self.t_in, self.t_out, self.n, self.stats = t_in, t_out, n, stats


def build(cfg: dict, tables: dict, seed: int, on_chunk=None):
    """``(runtime, backend)`` of the deployment; ``on_chunk(chunk)`` is
    called after each chunk's outcomes are complete."""
    import jax
    from repro.core.apps import AppSpec, AWSTwin
    from repro.core.decision import (DecisionEngine, MinCostPolicy,
                                     MinLatencyPolicy)
    from repro.core.fit import FittedModels, build_fleet_predictor
    from repro.core.gbrt import GBRT, GBRTConfig
    from repro.core.perf_models import NormalModel, RidgeModel
    from repro.core.pricing import LambdaPricing
    from repro.core.runtime import PlacementRuntime, TwinBackend

    class TimedTwin(TwinBackend):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.engine = None
            self.chunks: list[Chunk] = []

        def execute_many(self, tasks, targets):
            t_in = time.perf_counter()
            stats = dict(getattr(self.engine, "jax_stats", None) or {})
            with jax.profiler.TraceAnnotation("chipbench.twin"):
                out = super().execute_many(tasks, targets)
            c = Chunk(t_in, time.perf_counter(), len(tasks), stats)
            self.chunks.append(c)
            if on_chunk is not None:
                on_chunk(c)
            return out

    g = tables["gbrt"]
    gbrt = GBRT(config=GBRTConfig(n_trees=g["n_trees"],
                                  max_depth=g["max_depth"],
                                  learning_rate=g["learning_rate"]),
                base=g["base"],
                features=np.asarray(g["features"], np.int32),
                thresholds=np.asarray(g["thresholds"], np.float64),
                leaves=np.asarray(g["leaves"], np.float64))
    models = FittedModels(
        upld=RidgeModel(theta=np.asarray(tables["upld"], np.float64)),
        comp_cloud=gbrt,
        start_warm=NormalModel(mean=tables["start_warm"]),
        start_cold=NormalModel(mean=tables["start_cold"]),
        store_cloud=NormalModel(mean=tables["store_cloud"]),
        comp_edge=RidgeModel(theta=np.asarray(tables["edge_comp"],
                                              np.float64)),
        iotup=NormalModel(mean=tables["iotup"]),
        store_edge=NormalModel(mean=tables["store_edge"]),
        cloud_comp_std_frac=0.0, edge_comp_std_frac=0.0)
    pr = cfg["pricing"]
    pricing = LambdaPricing(gb_second_rate=pr["gb_second_rate"],
                            quantum_ms=pr["quantum_ms"])
    fleet = {k: float(v) for k, v in cfg["edge_fleet"].items()}
    pred = build_fleet_predictor(models, dict(fleet),
                                 configs=tuple(cfg["memory_configs_mb"]),
                                 pricing=pricing,
                                 t_idl_ms=cfg["predicted_t_idl_ms"])
    pol = cfg["policy"]
    if pol["kind"] == "min_latency":
        policy = MinLatencyPolicy(c_max=pol["c_max"], alpha=pol["alpha"])
    elif pol["kind"] == "min_cost":
        policy = MinCostPolicy(deadline_ms=pol["deadline_ms"])
    else:
        raise ValueError(f"unknown policy {pol['kind']!r}")
    engine = DecisionEngine(predictor=pred, policy=policy)
    twin = AWSTwin(spec=AppSpec(**cfg["app_spec"]), seed=seed)
    backend = TimedTwin(twin, seed=seed, pricing=pricing,
                        edge_names=tuple(fleet), edge_speed=fleet)
    backend.engine = engine
    return PlacementRuntime(engine, backend), backend


def task_chunk(idx0: int, arrival_ms, size, nbytes):
    from repro.core.workload import TaskChunk

    n = len(arrival_ms)
    return TaskChunk(idx=np.arange(idx0, idx0 + n, dtype=np.int64),
                     arrival_ms=np.asarray(arrival_ms, np.float64),
                     size=np.asarray(size, np.float64),
                     bytes=np.asarray(nbytes, np.float64))


def serve(runtime, chunks, chunk_rows: int):
    """The timed path: the device-resident stream over ``chunks``."""
    return runtime.serve_stream(chunks, chunk_size=chunk_rows,
                                array_backend="jax")


def compile_stats(runtime) -> dict:
    """The core's jit-cache sizes (a retrace grows them)."""
    from repro.core import jax_core

    core = jax_core.core_for(runtime.engine)
    return core.compile_stats() if core is not None else {}


def records(result) -> dict:
    """The served records as plain arrays, in task order."""
    r = result.records
    order = np.argsort(r.task_idx, kind="stable")
    names = list(r.target_names)
    return {"task_idx": np.asarray(r.task_idx)[order],
            "target": [names[c] for c in np.asarray(r.target_codes)[order]],
            "pred_latency": np.asarray(r.predicted_latency_ms)[order],
            "pred_cost": np.asarray(r.predicted_cost)[order],
            "pred_cold": np.asarray(r.predicted_cold, bool)[order],
            "feasible": np.asarray(r.feasible, bool)[order],
            "actual_latency": np.asarray(r.actual_latency_ms)[order],
            "actual_cost": np.asarray(r.actual_cost)[order],
            "actual_cold": np.asarray(r.actual_cold, bool)[order]}


def numpy_rate(cfg: dict, tables: dict, seed: int, chunks, chunk_rows: int,
               warm: int, max_tasks: int, max_s: float):
    """Tasks per second of the program's host path (``array_backend=
    "numpy"``, GBRT on the host tree walk), the single-threaded baseline
    the device path is meant to beat: it serves ``chunks`` from the start,
    untimed through the first ``warm``, and is timed from there over the
    chunks that follow until ``max_tasks`` tasks or ``max_s`` seconds are
    done. Returns ``(tasks/s, tasks, seconds)``."""
    from repro.core import predictor as predictor_mod

    runtime, backend = build(cfg, tables, seed)
    done = backend.chunks

    def feed():
        for k, c in enumerate(chunks):
            if k > warm and len(done) > warm and (
                    sum(d.n for d in done[warm:]) >= max_tasks
                    or done[-1].t_out - done[warm - 1].t_out >= max_s):
                return
            yield c

    was = predictor_mod.GBRT_KERNEL_MODE
    predictor_mod.GBRT_KERNEL_MODE = "off"
    try:
        runtime.serve_stream(feed(), chunk_size=chunk_rows,
                             array_backend="numpy")
    finally:
        predictor_mod.GBRT_KERNEL_MODE = was
    n = sum(d.n for d in done[warm:])
    dt = done[-1].t_out - done[warm - 1].t_out
    return n / dt, n, dt
