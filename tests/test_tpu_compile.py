"""Compile the placement service's device programs for a described TPU v5e.

No chip is attached: ``jax.experimental.topologies`` describes one and the
TPU compiler refuses here what it would refuse on the chip — block shapes
off the (8, 128) tiling, too much fast memory, a kernel Mosaic cannot lower.
Interpret mode, which the other tests use on the CPU, checks none of that.

Three compiles at the paper's deployment scale (19 Lambda memory configs,
GBRT of 150 trees × depth 3, 65,536-row chunks):

- the blocked multi-config GBRT kernel, with its temp memory bounded by a
  small multiple of its output (every width-1 lane row would pad to 128);
- the per-model GBRT kernel at the predictor's route width;
- the core's jitted place step on its TPU (two-float) branch.

The topology is described inside a module fixture, never at import time.
"""

from __future__ import annotations

import os

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

C, TREES, DEPTH, ROWS = 19, 150, 3, 65536


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return spec


def _ensemble_specs(spec, n_cfg):
    n_int, n_leaf = 2 ** DEPTH - 1, 2 ** DEPTH
    from repro.kernels.gbrt_predict.kernel import CFG_WIDTH

    return (spec((n_cfg, TREES * n_int), jnp.int32),
            spec((n_cfg, TREES * n_int), jnp.float32),
            spec((n_cfg, TREES * n_int), jnp.float32),
            spec((n_cfg, TREES * n_leaf), jnp.float32),
            spec((n_cfg, TREES * n_leaf), jnp.float32),
            spec((n_cfg, CFG_WIDTH), jnp.float32))


def test_gbrt_multi_compiles_lane_dense(one_chip):
    from repro.kernels.gbrt_predict.kernel import gbrt_predict_multi

    spec = _spec(one_chip)
    compiled = jax.jit(
        lambda *a: gbrt_predict_multi(*a, depth=DEPTH, interpret=False)
    ).lower(spec((2, ROWS), jnp.float32),
            *_ensemble_specs(spec, C)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    out_bytes = 2 * C * ROWS * 4
    assert mem.temp_size_in_bytes <= 2 * out_bytes, mem


def test_gbrt_blocked_compiles(one_chip):
    from repro.core.predictor import GBRT_KERNEL_MIN_BATCH
    from repro.kernels.gbrt_predict.kernel import gbrt_predict_blocked

    spec = _spec(one_chip)
    compiled = jax.jit(
        lambda *a: gbrt_predict_blocked(*a, depth=DEPTH, interpret=False)
    ).lower(spec((2, 2, GBRT_KERNEL_MIN_BATCH), jnp.float32),
            *_ensemble_specs(spec, 1)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_core_place_step_compiles_on_tpu_branch(one_chip, monkeypatch):
    from repro.core import jax_core
    from repro.core.apps import MEMORY_CONFIGS_MB
    from repro.core.decision import DecisionEngine, MinLatencyPolicy
    from repro.core.fit import build_fleet_predictor, fit_app

    monkeypatch.setattr(jax_core, "platform", lambda: "tpu")
    _, models = fit_app("IR", seed=0, n_inputs=40, configs=MEMORY_CONFIGS_MB)
    fleet = {"edge0": 1.0, "edge1": 1.0, "edge2": 0.6}
    engine = DecisionEngine(
        predictor=build_fleet_predictor(models, fleet,
                                        configs=MEMORY_CONFIGS_MB),
        policy=MinLatencyPolicy(c_max=5e-6, alpha=0.05))
    core = jax_core.core_for(engine)
    assert core is not None and core.A.df and not core.seq

    spec = _spec(one_chip)
    nd, cap = len(fleet), 64

    def pair(shape):
        return (spec(shape, jnp.float32), spec(shape, jnp.float32))

    P = {k: pair((ROWS, C)) for k in
         ("LATW", "LATC", "OCCW", "OCCC", "COMPC", "COSTC")}
    P["RANKC"] = spec((ROWS, C), jnp.int32)
    P.update({k: pair((ROWS, nd)) for k in ("ECOMP", "ELAT", "ECOST")})
    P.update(nows=pair((ROWS,)), valid=spec((ROWS,), jnp.bool_),
             nom_fixed=spec((ROWS,), jnp.int32), c_max=pair(()),
             alpha=pair(()), deadline=pair(()))
    S = {"busy0": pair((C, cap)), "last0": pair((C, cap)),
         "cnt0": spec((C,), jnp.int32), "h0": pair((nd,)), "s0": pair(())}
    compiled = core._place.lower(P, S).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30, mem
