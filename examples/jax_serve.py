"""Device-resident placement: the jit-compiled JAX predict→place pipeline.

Serves the same bursty stream three ways — the numpy columnar oracle,
``array_backend="jax_interpret"`` (the bit-parity audit mode), and compiled
``array_backend="jax"`` — and verifies the parity contract on the spot:
every mode must make the oracle's decisions; on float64 (the CPU) interpret
mode also matches every record column bit for bit; compiled floats agree to
``FLOAT_RTOL``, which both branches meet — float64 on the CPU, two-float
(~2**-48 relative) on the TPU, where plain f32 (~6e-8) would not.

Then demonstrates persistent residency: a 3-chunk resident stream places
every chunk with the CIL pools / surplus bank / edge horizons held
device-side (one host materialization total, at stream end), matches the
oracle's decisions, and — rerun same-shape on the same engine — reuses
every jit cache entry (no retrace).

    PYTHONPATH=src python examples/jax_serve.py
"""

import time

import numpy as np

from repro import compile_cache
from repro.core import jax_core
from repro.core.decision import DecisionEngine, MinLatencyPolicy
from repro.core.fit import build_fleet_predictor, fit_app
from repro.core.runtime import PlacementRuntime, TwinBackend
from repro.core.workload import BurstyWorkload

N_TASKS = 2_000
CHUNK = 512
CONFIGS = (1280, 1536, 1792)
FLEET = {"edge0": 1.0, "edge1": 1.0, "edge2": 0.6}
C_MAX = 6e-6            # $/task budget (Alg. 1)
ALPHA = 0.05
FLOAT_RTOL = 1e-9       # compiled-vs-oracle float agreement (see docstring)

compile_cache.configure()
print("fitting IR component models (twin ground truth)...")
twin, models = fit_app("IR", seed=0, n_inputs=120, configs=CONFIGS)
tasks = BurstyWorkload(rate_per_s=4.0, size_sampler=twin.sample_input,
                       burst_multiplier=8.0, mean_quiet_s=10.0,
                       mean_burst_s=6.0, seed=31).generate(N_TASKS)


def runtime():
    pred = build_fleet_predictor(models, dict(FLEET), configs=CONFIGS)
    eng = DecisionEngine(predictor=pred,
                         policy=MinLatencyPolicy(c_max=C_MAX, alpha=ALPHA))
    backend = TwinBackend(twin, seed=11, edge_names=tuple(FLEET),
                          edge_speed=FLEET)
    return PlacementRuntime(eng, backend)


def serve(backend):
    rt = runtime()
    t0 = time.perf_counter()
    res = rt.serve_stream(tasks, chunk_size=CHUNK, array_backend=backend)
    dt = time.perf_counter() - t0
    return res, dt, rt.engine


print(f"serving {N_TASKS} bursty tasks, chunk={CHUNK}, 3-device fleet...")
ref, t_np, _ = serve("numpy")
interp, t_it, eng_it = serve("jax_interpret")
comp, t_jx, eng_jx = serve("jax")

COLS = ("predicted_latency_ms", "predicted_cost", "actual_latency_ms",
        "actual_cost", "allowed_cost", "completion_ms", "queue_wait_ms",
        "exec_ms", "predicted_cold", "actual_cold", "feasible")

float64 = jax_core.platform() != "tpu"
bit_equal = (list(ref.records.targets) == list(interp.records.targets)
             and all(np.array_equal(getattr(ref.records, c),
                                    getattr(interp.records, c))
                     for c in COLS))
dec_equal = (list(ref.records.targets) == list(comp.records.targets)
             == list(interp.records.targets))
close = all(np.allclose(getattr(ref.records, c).astype(float),
                        getattr(comp.records, c).astype(float),
                        rtol=FLOAT_RTOL)
            for c in COLS)
assert dec_equal, "every jax mode must make the oracle's decisions"
assert bit_equal or not float64, \
    "float64 interpret mode must be bit-identical to the numpy oracle"
assert close, f"compiled floats must agree to rtol={FLOAT_RTOL}"

core = jax_core.core_for(eng_jx)
print(f"\nnumpy oracle          : {t_np:.2f} s")
print(f"jax_interpret (audit) : {t_it:.2f} s  bit-identical: {bit_equal}")
print(f"jax (compiled)        : {t_jx:.2f} s  decision-identical: "
      f"{dec_equal}  floats close: {close}")
print(f"fixed-point passes    : {eng_jx.jax_stats['passes']} "
      f"(last chunk, rows={eng_jx.jax_stats['rows']})")
print(f"jit cache entries     : {core.compile_stats()}")
print(f"avg latency           : {ref.avg_actual_latency_ms:.1f} ms   "
      f"total cost: ${ref.total_actual_cost:.6f}")

# --- persistent residency (3-chunk resident stream) -------------------------
# Stream state stays device-side across chunks: no host commit at chunk
# boundaries, one materialization at stream end. A same-shape continuation
# stream on the same engine (arrivals keep moving forward — replaying past
# arrivals would cold-start into ever-larger pools) must reuse every jit
# cache entry.
demo = BurstyWorkload(rate_per_s=4.0, size_sampler=twin.sample_input,
                      burst_multiplier=8.0, mean_quiet_s=10.0,
                      mean_burst_s=6.0, seed=32).generate(6 * CHUNK)
rt_ref, rt_res = runtime(), runtime()
ref_r = rt_ref.serve_stream(demo[:3 * CHUNK], chunk_size=CHUNK)
res_r = rt_res.serve_stream(demo[:3 * CHUNK], chunk_size=CHUNK,
                            array_backend="jax")
r = rt_res.stream_stats["residency"]
assert list(ref_r.records.targets) == list(res_r.records.targets), \
    "resident stream diverged from the numpy oracle"
assert r["enabled"] and r["resident_chunks"] == 3
assert r["chunk_commits"] == 0 and r["state_syncs"] == 1

core_r = jax_core.core_for(rt_res.engine)
stats0 = core_r.compile_stats()
rt_res.serve_stream(demo[3 * CHUNK:], chunk_size=CHUNK, array_backend="jax")
no_retrace = core_r.compile_stats() == stats0
assert no_retrace, "same-shape continuation stream retraced"
print(f"resident stream       : 3/3 chunks device-resident, "
      f"{r['state_syncs']} host sync (stream end), "
      f"{r['chunk_commits']} chunk commits, prefetched {r['prefetched']}, "
      f"no-retrace continuation: {no_retrace}")

print("\nTimes above are host wall-clock. No speed of the device path has "
      "been measured\non a chip yet; on the CPU the compiled path loses to "
      "numpy (XLA scan overhead).")
