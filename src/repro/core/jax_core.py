"""Device-resident placement core: the jit-compiled JAX predict→place pass.

The columnar decision core (``repro.core.decision``) is pure numpy: one
vectorized predict pass, then speculate-and-repair over the three sequential
recurrences. This module ports that hot per-chunk pipeline to JAX so a whole
chunk runs device-resident under ``jax.jit`` — selected per engine with
``DecisionEngine(array_backend="jax")`` or per stream with
``serve_stream(..., array_backend="jax")``. The numpy path stays the
correctness oracle.

Structure of one chunk (with stream residency — see below — even chunk
boundaries stop being host↔device sync points):

1. **Predict** — ridge upload / edge-compute models, normal-model scalars and
   Lambda pricing as jnp expressions; the GBRT compute model as a device-side
   gather over the serving step tables (``predictor.const1_serving_table``,
   padded to one ``(n_configs, B)`` matrix), or through the
   ``repro.kernels.gbrt_predict`` Pallas kernel on TPU / ``GBRT_KERNEL_MODE
   == "force"``.
2. **Place** — a fixed-point driver replaces the host speculate-and-repair
   loop: a ``lax.while_loop`` carries the speculated policy-view codes
   (``-1`` = "no state effects yet", the frozen-state guess), and each
   iteration replays ALL THREE sequential recurrences from the block-start
   state under the current guess — the surplus bank and FIFO busy horizons
   as ``lax.scan`` left folds (or max-plus ``lax.associative_scan`` / prefix
   forms in ``assoc`` mode, see ``recurrence.maxplus_combine``), the CIL
   warm/cold event walk as a ``lax.scan`` over fixed-capacity container
   pools. By the same induction the numpy repair loop relies on, the exact
   prefix grows by ≥ 1 row per iteration, so the fixed point
   (``pass(g) == g``) IS the true sequential trajectory and is reached in
   ≤ R+1 passes. A chunk runs as ``PLACE_BLOCK``-row blocks in order (see
   ``_build_place``): where decisions feed back, a pass repairs few rows.
3. **Commit or stay resident** — decision outputs are sliced to the chunk on
   host either way. Without stream residency (standalone ``place_many``),
   CIL pools, edge horizons and the surplus bank are written back exactly
   like the numpy accept step (including the final ``reap`` at the last
   arrival). Under ``serve_stream`` the engine carries a
   ``_device_residency`` flag and the committed state instead STAYS ON
   DEVICE as a ``DeviceStreamState``: consecutive in-order chunks seed the
   next fixed point straight from the previous chunk's final state arrays
   (buffer-donated into the jitted step, so steady chunks reuse the same
   device buffers), and the host CIL/queues/policy are materialized only on
   demand — at stream end, on any fallback exit (hedged/custom policy swap,
   out-of-order arrivals, ``record_decisions``, a ``columnar=False`` chunk),
   or when an external consumer calls ``sync_engine``. Deferring the reap to
   materialization time is exact: the keep predicate is monotone in the reap
   time and dead containers are never warm-reusable, so the one deferred
   reap drops exactly the records the per-chunk reaps would have (order
   preserved — slot order is list order in both). ``stage_chunk`` +
   ``runtime._Prefetcher`` double-buffer the NEXT chunk's task arrays
   onto the device (``jax.device_put`` on a transfer thread) while the
   current chunk is placed (backlogged source) or executed (caught-up
   source), and the GBRT compute column launches ONE
   blocked multi-config Pallas kernel (``gbrt_predict_multi``) instead of a
   launch per cloud config.

Arithmetic: the numpy oracle decides in float64. On the CPU the core runs
float64 under ``jax.enable_x64``. The TPU has no float64, so there every
decision-relevant value is a two-float pair (``repro.kernels.dfloat``, ~48
bits): predicted latencies and costs, the surplus bank, and times. Times
are also rebased per chunk: the device holds ``t - base`` with ``base`` the
chunk's first arrival, kept exactly on the host, so no absolute time of a
long stream (1e8+ ms) reaches the device. Costs are gathered from per-config
tables the host computes with the oracle's own float64 formula, on both
branches, beside each cost's rank among them (``cost_ranks``): Lambda billing
makes exact ties in real arithmetic that float64 breaks by one ulp, which
two-float cannot resolve, so one cost is compared with another by rank. Which
branch runs is decided by ``platform()``, the core's one read of the device.

Parity contract (mirrors the Pallas kernel tests):

- ``array_backend="jax_interpret"`` — float64 op-by-op execution
  (``jax.disable_jit``): BIT-IDENTICAL per record to the numpy path. XLA's
  compiled CPU pipeline contracts ``a + b*c`` into FMAs and reassociates
  constant chains, so the compiled path cannot promise last-ULP equality —
  interpret mode is the oracle, exactly like ``interpret=True`` Pallas.
- ``array_backend="jax"`` — jit-compiled: decision-equality (identical
  ``target_codes``) with tolerance-level float agreement: about 1e-12
  relative on the CPU, 2**-48 relative (plus the representation of rebased
  times) on the TPU's two-float branch.

Fallback rules (all BEFORE any balancer/RNG state is consumed, so a fallback
chunk is indistinguishable from a numpy chunk): hedged/custom policies,
non-columnar balancers, quantile prediction, ``record_decisions``, custom
target/model/pricing types, and out-of-order arrivals all take the existing
numpy path. These are semantic refusals (``CoreIneligible``); anything else
that stops the core from building — JAX missing, a compile error, a device
the kernel cannot run on — raises. Chunks are padded to power-of-two rows
(pad rows carry code ``-1`` and no effects) so streaming tails never retrace
the jit cache.
"""

from __future__ import annotations

import contextlib
import threading
import weakref
from dataclasses import dataclass, field

import numpy as np

from repro.core.cil import ContainerInfoList, ContainerRecord
from repro.kernels import dfloat
from repro.core.perf_models import NormalModel, RidgeModel, ScaledModel
from repro.core.predictor import (
    EdgeTarget,
    LambdaTarget,
    Predictor,
    const1_serving_table,
    model_keyed_cache,
)
from repro.core.pricing import EdgePricing, LambdaPricing
from repro.core.workload import task_arrays

# "seq"   — sequential lax.scan left folds (bit-exact association vs numpy);
# "assoc" — max-plus associative_scan / cumsum forms (reassociated float sums:
#           decision-equality contract only);
# "auto"  — per-backend pick from the bench section 9 measurement (see
#           ``resolve_scan_mode``).
SCAN_MODE = "auto"
# Winners for SCAN_MODE="auto" (bench_runtime section 9's assoc-vs-seq
# timing; backends not listed default to "assoc"). XLA:CPU executes the
# short sequential scan faster than the log-depth max-plus associative form
# at serving chunk sizes — and seq is also the bit-exact association, so CPU
# keeps it. The TPU default ("assoc") has not been timed on a chip yet.
_AUTO_SCAN = {"cpu": "seq"}

POOL_MIN_CAP = 8        # starting CIL container-pool capacity (doubles on demand)
# ... and at least one slot per this many padded chunk rows: a chunk's cold
# starts take slots before the reap that frees them, so the width a stream
# needs grows with its chunks. FD MinCost's 16,384-row chunks need up to 128
# over a simulated day; starting there keeps the regrow (a new place program)
# out of a long stream.
POOL_ROWS_PER_SLOT = 128
PAD_MIN = 8             # minimum padded chunk rows
PLACE_BLOCK = 32        # rows per compiled fixed point (``_build_place``)
# the place step's per-chunk scalars (every other input is per row), and
# the state it carries from block to block: (seed key, final key)
_SCALAR_INPUTS = ("c_max", "alpha", "deadline")
_STATE_OUTPUTS = (("busy0", "busyF"), ("last0", "lastF"), ("cnt0", "cntF"),
                  ("h0", "h_fin"), ("s0", "s_fin"))
MAX_BACKENDS = ("numpy", "jax", "jax_interpret")


def resolve_scan_mode(backend: str) -> str:
    """Effective scan mode for a jax backend under the current ``SCAN_MODE``.

    ``"auto"`` resolves through the measured ``_AUTO_SCAN`` table (bench
    section 9 re-derives it and asserts agreement on accelerators)."""
    if SCAN_MODE != "auto":
        return SCAN_MODE
    return _AUTO_SCAN.get(backend, "assoc")


class CoreIneligible(Exception):
    """This engine's policy/targets/models are outside the jax core's replica."""


def _modules():
    import jax
    import jax.numpy as jnp
    from jax import lax

    return jax, jnp, lax


def platform() -> str:
    """The platform the core builds for (``jax.default_backend()``).

    The core's one read of the device: it picks the arithmetic (float64 on
    the CPU, two-float on the TPU), the scan mode and the GBRT route. Tests
    steer the TPU branch on a CPU host by monkeypatching this function."""
    return _modules()[0].default_backend()


# ------------------------------------------------------------ arithmetic
class _F64:
    """Float64 arithmetic (CPU, under x64): plain arrays, the very operations
    of the numpy oracle, so interpret mode stays bit-identical."""

    df = False
    zero, inf, ninf = 0.0, np.inf, -np.inf

    def __init__(self, jnp):
        self.jnp = jnp

    @staticmethod
    def add(a, b):
        return a + b

    @staticmethod
    def sub(a, b):
        return a - b

    @staticmethod
    def mul(a, b):
        return a * b

    @staticmethod
    def lt(a, b):
        return a < b

    @staticmethod
    def le(a, b):
        return a <= b

    @staticmethod
    def eq(a, b):
        return a == b

    def maximum(self, a, b):
        return self.jnp.maximum(a, b)

    def where(self, c, a, b):
        return self.jnp.where(c, a, b)

    @staticmethod
    def reduce_min(x, axis):
        return x.min(axis=axis)

    def round(self, x):
        return self.jnp.round(x)

    def argmin(self, x, axis):
        return self.jnp.argmin(x, axis=axis)

    def argmax(self, x, axis):
        return self.jnp.argmax(x, axis=axis)

    def cumsum(self, x):
        return self.jnp.cumsum(x)

    def full(self, shape, v):
        return self.jnp.full(shape, v, self.jnp.float64)

    @staticmethod
    def const(v):
        return float(v)

    def dev(self, x, base: float = 0.0):
        return self.jnp.asarray(np.asarray(x, np.float64))

    @staticmethod
    def host(v) -> np.ndarray:
        return np.asarray(v, np.float64)


class _DF32:
    """Two-float arithmetic (TPU): values are ``(hi, lo)`` f32 pairs; see
    ``repro.kernels.dfloat``. ``dev`` rebases times by ``base`` on the host
    in float64 before the split."""

    df = True
    zero, inf, ninf = (0.0, 0.0), (np.inf, 0.0), (-np.inf, 0.0)
    add = staticmethod(dfloat.add)
    sub = staticmethod(dfloat.sub)
    mul = staticmethod(dfloat.mul)
    lt = staticmethod(dfloat.lt)
    le = staticmethod(dfloat.le)
    eq = staticmethod(dfloat.eq)
    maximum = staticmethod(dfloat.maximum)
    where = staticmethod(dfloat.where)
    reduce_min = staticmethod(dfloat.reduce_min)
    round = staticmethod(dfloat.round_half_even)
    argmin = staticmethod(dfloat.argmin)
    argmax = staticmethod(dfloat.argmax)

    def __init__(self, jnp, lax):
        self.jnp, self.lax = jnp, lax

    def cumsum(self, x):
        return self.lax.associative_scan(dfloat.add, x)

    def full(self, shape, v):
        jnp = self.jnp
        return (jnp.full(shape, v, jnp.float32),
                jnp.zeros(shape, jnp.float32))

    @staticmethod
    def const(v):
        hi, lo = dfloat.split(v)
        return np.float32(hi), np.float32(lo)

    def dev(self, x, base: float = 0.0):
        hi, lo = dfloat.split(np.asarray(x, np.float64) - base)
        return self.jnp.asarray(hi), self.jnp.asarray(lo)

    @staticmethod
    def host(v) -> np.ndarray:
        return dfloat.join(*v)


def cost_ranks(cost) -> np.ndarray:
    """Dense int32 rank of each float64 cost among all of them and the free
    edge's 0.0 (which ranks 0): equal ranks iff equal costs, and the ranks
    in the order of the costs."""
    _, inv = np.unique(np.append(np.ravel(cost), 0.0), return_inverse=True)
    return inv[:-1].reshape(np.shape(cost)).astype(np.int32)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


# Device-resident table/operand hosting, keyed on model identity + the
# arithmetic (float64 or two-float) with the _CONST1_TABLES weakref idiom — rebuilding a core (e.g.
# a hedged-policy swap and back) re-hosts NOTHING, and the per-chunk path
# does zero host-side operand prep (see ``model_keyed_cache``).
_DEVICE_TABLES: dict[tuple, dict] = {}
_DEVICE_TABLES_LOCK = threading.Lock()


@dataclass
class DeviceStreamState:
    """Cross-chunk device residency for one ``serve_stream`` run.

    Holds the sequential placement state ON DEVICE between consecutive
    in-order chunks: fixed-capacity CIL container pools (``busy``/``last``
    at ``cap`` slots per cloud config plus per-config ``cnt``), per-device
    edge FIFO horizons ``h``, and the Alg. 1 surplus bank ``s`` (two-float
    pairs on the TPU branch, where times are relative to ``base``). Host-side
    bookkeeping rides along: ``t_last`` (last committed arrival — validates
    in-order re-entry), ``cnt_max`` (pool-growth bound without materializing
    pools), ``chunks`` (resident chunks absorbed), and ``rng_draws`` (RNG
    stream offset — balancer draws consumed while resident; the host
    Generators advance identically, this records the offset). Strong refs
    to the CIL / policy / queues objects pin the state to the exact host
    structures it shadows — any object swap invalidates residency.
    """

    busy: object = None          # (n_cloud, cap) device array
    last: object = None          # (n_cloud, cap) device array
    cnt: object = None           # (n_cloud,) device array
    h: object = None             # (n_dev,) device array (edge fleets only)
    s: object = None             # scalar device array (MinLatency only)
    cap: int = 0
    base: float = 0.0            # host time origin of the device times
    t_last: float = -np.inf
    cnt_max: int = 0
    chunks: int = 0
    rng_draws: int = 0
    cil: object = None
    policy: object = None
    queues: object = field(default=None)


# --------------------------------------------------------------------- spec
@dataclass
class _CloudSpec:
    name: str
    memory_mb: float
    up_theta: tuple[float, float]
    start_warm: float          # max(mean, 0) — precomputed like the batch path
    start_cold: float
    store: float
    quantum: float
    gb: float
    rate: float
    breaks: np.ndarray
    vals: np.ndarray


@dataclass
class _EdgeSpec:
    name: str
    theta: tuple[float, float]
    scale: float
    iot: float
    store: float


def _ridge2(model) -> tuple[float, float]:
    if type(model) is not RidgeModel or model.theta.shape != (2,):
        raise CoreIneligible("non-affine upload/edge model")
    return float(model.theta[0]), float(model.theta[1])


def _normal_mean(model) -> float:
    if type(model) is not NormalModel:
        raise CoreIneligible("non-normal component model")
    return max(model.predict(), 0.0)


def _extract_cloud(tgt) -> _CloudSpec:
    if type(tgt) is not LambdaTarget:
        raise CoreIneligible(f"cloud target {tgt!r} is not a LambdaTarget")
    if type(tgt.pricing) is not LambdaPricing \
            or tgt.pricing.include_request_charge:
        raise CoreIneligible("non-Lambda or request-charge pricing")
    model = tgt.comp_model
    if not (hasattr(model, "const1_table") and hasattr(model, "thresholds")):
        raise CoreIneligible("cloud comp model is not a GBRT")
    breaks, vals = const1_serving_table(model, float(tgt.memory_mb))
    return _CloudSpec(
        name=tgt.name, memory_mb=float(tgt.memory_mb),
        up_theta=_ridge2(tgt.upld_model),
        start_warm=_normal_mean(tgt.start_warm),
        start_cold=_normal_mean(tgt.start_cold),
        store=_normal_mean(tgt.store_model),
        quantum=float(tgt.pricing.quantum_ms),
        gb=tgt.memory_mb / 1024.0,
        rate=float(tgt.pricing.gb_second_rate),
        breaks=np.asarray(breaks, np.float64),
        vals=np.asarray(vals, np.float64))


def _extract_edge(dev) -> _EdgeSpec:
    if type(dev) is not EdgeTarget:
        raise CoreIneligible(f"edge device {dev!r} is not an EdgeTarget")
    if type(dev.pricing) is not EdgePricing:
        raise CoreIneligible("edge pricing is not EdgePricing")
    model = dev.comp_model
    scale = 1.0
    if type(model) is ScaledModel:
        scale = float(model.scale)
        model = model.base
    t0, t1 = _ridge2(model)
    return _EdgeSpec(name=dev.name, theta=(t0, t1), scale=scale,
                     iot=_normal_mean(dev.iotup_model),
                     store=_normal_mean(dev.store_model))


def _engine_key(engine) -> tuple:
    """Cheap identity key for the per-engine core cache. Model swaps (online
    refit) change ids; ``valid_for`` weakref-guards against id recycling."""
    from repro.core import predictor as predictor_mod

    pred = engine.predictor
    ids = [id(pred), id(engine.policy), type(engine.policy),
           type(engine.balancer), pred.quantile,
           predictor_mod.GBRT_KERNEL_MODE, SCAN_MODE, platform()]
    for tgt in pred.cloud_targets:
        ids.append((id(tgt), id(tgt.comp_model), id(tgt.upld_model),
                    id(tgt.start_warm), id(tgt.start_cold),
                    id(tgt.store_model)))
    for dev in (pred.edge_fleet or ()):
        ids.append((id(dev), id(dev.comp_model), id(dev.iotup_model),
                    id(dev.store_model)))
    return tuple(ids)


# --------------------------------------------------------------------- core
class JaxPlacementCore:
    """One engine's compiled predict→place pipeline.

    Built lazily per engine (``core_for``), revalidated per chunk against the
    captured model identities — a refit-by-swap misses the cache and triggers
    a rebuild, exactly like the serving step-table cache.
    """

    def __init__(self, engine):
        self.jax, self.jnp, self.lax = _modules()
        if not engine._columnar_eligible():
            raise CoreIneligible("engine is not columnar-eligible")
        pred: Predictor = engine.predictor
        if pred.quantile is not None:
            raise CoreIneligible("quantile prediction is host-side only")
        self.cloud = [_extract_cloud(t) for t in pred.cloud_targets]
        self._kernel_models = [t.comp_model for t in pred.cloud_targets]
        self.edges = [_extract_edge(d) for d in (pred.edge_fleet or ())]
        self.n_cloud = len(self.cloud)
        self.n_dev = len(self.edges)
        self.has_edge = self.n_dev > 0
        self.T = self.n_cloud + (1 if self.has_edge else 0)
        self.edge_col = self.T - 1 if self.has_edge else -1
        self.t_idl = float(pred.cil.t_idl_ms)

        from repro.core import predictor as predictor_mod
        from repro.core.decision import (
            LeastPredictedWaitBalancer,
            MinLatencyPolicy,
        )

        self.is_minlat = type(engine.policy) is MinLatencyPolicy
        self.lpw = (self.n_dev > 1
                    and type(engine.balancer) is LeastPredictedWaitBalancer)
        mode = predictor_mod.GBRT_KERNEL_MODE
        plat = platform()
        tpu = plat == "tpu"
        # the TPU has no float64: decide in two-float there (module docstring)
        self.A = _DF32(self.jnp, self.lax) if tpu else _F64(self.jnp)
        if any(not c.quantum.is_integer() for c in self.cloud):
            raise CoreIneligible("billing needs integral quanta")
        self.use_gbrt_kernel = bool(self.n_cloud) and (
            mode == "force" or (tpu and mode == "auto"))
        # interpret mode follows the device the kernel really runs on: off on
        # a TPU, on for the CPU, an error on any other accelerator
        self.kernel_interpret = None
        if self.use_gbrt_kernel:
            from repro.kernels import interpret_mode

            self.kernel_interpret = interpret_mode()
        self.seq = resolve_scan_mode(plat) == "seq"
        self.key = _engine_key(engine)
        self._targets = list(pred.cloud_targets) + list(pred.edge_fleet or ())
        self._refs = [weakref.ref(o) for o in (
            [pred, engine.policy]
            + [t for t in pred.cloud_targets]
            + [t.comp_model for t in pred.cloud_targets]
            + [d for d in (pred.edge_fleet or ())])]
        self._cap_hint = POOL_MIN_CAP
        with self._scope():
            self._tables = self._device_tables()
            self._state_fn = self._build_state()
            self._choose_fn = self._build_choose()
            self._finalize_fn = self._build_finalize()
            self._predict = self.jax.jit(self._build_predict())
            # S (the sequential-state seed) is donated: resident streams
            # feed chunk k's final state arrays straight back in as chunk
            # k+1's seed, reusing the same device buffers — steady chunks
            # allocate nothing for state.
            self._place = self.jax.jit(self._build_place(),
                                       donate_argnums=(1,))
            # interpret-mode hosts the fixed point itself on these pieces
            self._state = self.jax.jit(self._state_fn)
            self._choose = self.jax.jit(self._choose_fn)
            self._finalize = self.jax.jit(self._finalize_fn)
            self._compact = (self.jax.jit(self._build_compact())
                             if self.n_cloud else None)
            self._shift = self.jax.jit(self._build_shift())
        self.last_stats: dict | None = None
        # ---- stream residency (serve_stream only; see module docstring) ----
        self._resident: DeviceStreamState | None = None
        self.state_syncs = 0      # host materializations of resident state
        self.fallback_syncs = 0   # ... of which were forced by a fallback
        self.resident_chunks = 0  # chunks absorbed without a host sync
        self.chunk_commits = 0    # legacy per-chunk host commits
        self.resident_regrows = 0  # donated-seed restore+retry events
        self.d2h_reads = 0        # device arrays place_chunk read back
        self.cost_rank_splits = 0  # rows whose cost tie a rank split

    # ------------------------------------------------------------ lifecycle
    def _scope(self):
        if self.A.df:
            return contextlib.nullcontext()
        return self.jax.enable_x64(True)

    def valid_for(self, engine) -> bool:
        return (self.key == _engine_key(engine)
                and all(r() is not None for r in self._refs))

    def compile_stats(self) -> dict:
        """jit-cache sizes of every program — the no-retrace probe."""
        fns = {"predict": self._predict, "place": self._place,
               "state": self._state, "choose": self._choose,
               "finalize": self._finalize, "compact": self._compact,
               "shift": self._shift}
        return {k: f._cache_size() if f is not None else 0
                for k, f in fns.items()}

    def _read(self, x) -> np.ndarray:
        """One device array read back to the host (``d2h_reads``)."""
        self.d2h_reads += 1
        return np.asarray(x)

    def _read_f(self, x) -> np.ndarray:
        """A float value read back: a two-float pair is two arrays."""
        self.d2h_reads += 2 if self.A.df else 1
        return self.A.host(x)

    def _base(self, nows_np) -> float:
        """Host time origin of one chunk's device times: its first arrival
        on the two-float branch (times stay small there), 0 on float64."""
        return float(nows_np[0]) if self.A.df and len(nows_np) else 0.0

    # ------------------------------------------------------- device operands
    def _device_tables(self) -> dict:
        key = (tuple(id(t) for t in self._targets), self.A.df)
        return model_keyed_cache(
            _DEVICE_TABLES, _DEVICE_TABLES_LOCK, key, self._targets,
            self._build_device_tables)

    def _build_device_tables(self) -> dict:
        dev = self.A.dev
        t: dict = {}
        if self.n_cloud:
            bmax = max(1, max(c.breaks.shape[0] for c in self.cloud))
            BR = np.full((self.n_cloud, bmax), np.inf)
            VL = np.zeros((self.n_cloud, bmax + 1))
            for i, c in enumerate(self.cloud):
                nb = c.breaks.shape[0]
                BR[i, :nb] = c.breaks
                VL[i, :nb + 1] = c.vals
                VL[i, nb + 1:] = c.vals[-1]
            t["BR"] = dev(BR)
            t["VL"] = dev(VL)
            for k, attr in (("SW", "start_warm"), ("SC", "start_cold"),
                            ("ST", "store")):
                t[k] = dev(np.array([getattr(c, attr) for c in self.cloud]))
            t["UP0"] = dev(np.array([c.up_theta[0] for c in self.cloud]))
            t["UP1"] = dev(np.array([c.up_theta[1] for c in self.cloud]))
            t["QI"], t["BILL"] = self._billing_tables(VL)
        if self.has_edge:
            t["ET0"] = dev(np.array([e.theta[0] for e in self.edges]))
            t["ET1"] = dev(np.array([e.theta[1] for e in self.edges]))
            t["ESC"] = dev(np.array([e.scale for e in self.edges]))
            t["EIO"] = dev(np.array([e.iot for e in self.edges]))
            t["EST"] = dev(np.array([e.store for e in self.edges]))
        return t

    def _billing_tables(self, VL):
        """Billing by billed-quantum count ``k``: the int32 quanta, and one
        stacked table ``BILL[:, config, k]`` of the cost (one float64 row,
        or the two rows of its two-float split) over its ``cost_ranks``
        rank, held in the same float type (exact below 2**24 distinct costs;
        a 15-minute Lambda limit bills 9,000 quanta per config). Costs are
        computed on the host with ``LambdaPricing.cost_batch``'s float64
        formula, ``((k*q / 1000) * gb) * rate``, so a cost on the device is
        the oracle's cost, and the rank orders costs as the oracle does.
        Compute times are bounded by the serving step tables (the GBRT's
        range), which bounds ``k``."""
        q = np.array([c.quantum for c in self.cloud])
        top = np.maximum(np.round(np.abs(VL).max(axis=1)), 1.0)
        K = int(np.ceil(top / q).max()) + 2
        k = np.arange(K, dtype=np.float64)
        cost = np.stack([((k * c.quantum / 1000.0) * c.gb) * c.rate
                         for c in self.cloud])
        rows = list(dfloat.split(cost)) if self.A.df else [cost]
        bill = np.stack(rows + [cost_ranks(cost)]).astype(
            np.float32 if self.A.df else np.float64)
        return (self.jnp.asarray(q.astype(np.int32)),
                self.jnp.asarray(bill))

    def _gbrt_kernel_operands(self):
        """Stacked multi-config Pallas operands for the ONE blocked
        ``gbrt_predict_multi`` launch (cached per model-identity tuple in
        ``ops.multi_kernel_operands`` — zero per-chunk / per-core-build host
        prep)."""
        from repro.kernels.gbrt_predict.ops import multi_kernel_operands

        return multi_kernel_operands(self._kernel_models,
                                     [c.memory_mb for c in self.cloud])

    # ----------------------------------------------------------- predict jit
    def _build_predict(self):
        jax, jnp, A = self.jax, self.jnp, self.A
        t = self._tables
        nc, nd = self.n_cloud, self.n_dev
        use_kernel = self.use_gbrt_kernel
        kernel_ops = None
        if use_kernel:
            from repro.kernels.gbrt_predict.kernel import gbrt_predict_multi

            interpret = self.kernel_interpret
            *kernel_ops, depth = self._gbrt_kernel_operands()

        def col(x):
            return jax.tree.map(lambda a: a[:, None], x)

        def row(x):
            return jax.tree.map(lambda a: a[None, :], x)

        def gbrt_kernel(sizes):
            # ONE blocked launch over the padded (configs, trees, …) operand
            # stack — grid (C, row-blocks) — instead of a pallas_call per
            # cloud config; two-float in and out (see the kernel docstring)
            if A.df:
                sh, sl = sizes
            else:
                sh = sizes.astype(jnp.float32)
                sl = (sizes - sh.astype(sizes.dtype)).astype(jnp.float32)
            ch, cl = gbrt_predict_multi(jnp.stack([sh, sl]), *kernel_ops,
                                        depth=depth, interpret=interpret)
            if A.df:
                return ch.T, cl.T
            return ch.T.astype(sizes.dtype) + cl.T.astype(sizes.dtype)

        def gbrt_table(sizes):
            if not A.df:
                return jax.vmap(
                    lambda b, v: v[jnp.searchsorted(b, sizes, side="left")]
                )(t["BR"], t["VL"]).T
            # searchsorted(side="left") == count of breaks below the size
            k = A.lt(jax.tree.map(lambda a: a[None], t["BR"]),
                     jax.tree.map(lambda a: a[:, None, None], sizes)
                     ).sum(axis=-1)
            cfg = jnp.arange(nc)[None, :]
            return jax.tree.map(lambda v: v[cfg, k], t["VL"])

        def billed(compc):
            # np.round, then up to the quantum, in exact integers; then one
            # gather of the host's (cost, rank) table
            m = jnp.maximum(A.round(compc), 1.0).astype(jnp.int32)
            q = t["QI"][None, :]
            k = jnp.clip((m + q - 1) // q, 0, t["BILL"].shape[2] - 1)
            g = t["BILL"][:, jnp.arange(nc)[None, :], k]
            cost = (g[0], g[1]) if A.df else g[0]
            return cost, g[-1].astype(jnp.int32)

        def predict(sizes, nbytes):
            out = {}
            if nc:
                comp = gbrt_kernel(sizes) if use_kernel else gbrt_table(sizes)
                compc = A.maximum(comp, A.zero)
                upld = A.maximum(
                    A.add(row(t["UP0"]), A.mul(col(nbytes), row(t["UP1"]))),
                    A.zero)
                # associate exactly like sum(warm.values()) / occupancy_ms:
                # ((upld + start) + comp) (+ store)
                occ_w = A.add(A.add(upld, row(t["SW"])), compc)
                occ_c = A.add(A.add(upld, row(t["SC"])), compc)
                out["LATW"] = A.add(occ_w, row(t["ST"]))
                out["LATC"] = A.add(occ_c, row(t["ST"]))
                out["OCCW"] = occ_w
                out["OCCC"] = occ_c
                out["COMPC"] = compc
                out["COSTC"], out["RANKC"] = billed(compc)
            if nd:
                ec = A.maximum(
                    A.mul(A.add(row(t["ET0"]),
                                A.mul(col(sizes), row(t["ET1"]))),
                          row(t["ESC"])),
                    A.zero)
                out["ECOMP"] = ec
                out["ELAT"] = A.add(A.add(ec, row(t["EIO"])), row(t["EST"]))
            return out

        return predict

    # ----------------------------------------------------------- place parts
    # The per-chunk pass is split in three so interpret mode can keep the one
    # FMA-prone operation out of XLA: ``state`` (the three recurrences, the
    # CIL event walk and the policy-view matrices — additions, compares and
    # gathers only, which compiled XLA executes bit-exactly in sequential
    # order) → ``allowed = c_max + α·s_before`` (the ONLY multiply on the
    # place side; XLA CPU contracts mul+add chains into FMAs regardless of
    # optimization barriers, so interpret mode computes it op-by-op under
    # ``jax.disable_jit``) → ``choose`` (masked lexicographic argmins: exact
    # compares and min-reductions). Compiled mode composes all three inside
    # one jitted ``lax.while_loop`` fixed-point driver under the
    # decision-equality contract; interpret mode hosts the same fixed point
    # in Python over the jitted pieces and stays bit-exact. Every float goes
    # through ``self.A`` (plain float64 arrays or two-float pairs); gathers
    # and slices apply to each array of a value with ``jax.tree.map``.
    def _build_state(self):
        jax, jnp, lax, A = self.jax, self.jnp, self.lax, self.A
        tm = jax.tree.map
        nc, nd, T = self.n_cloud, self.n_dev, self.T
        edge_col, has_edge = self.edge_col, self.has_edge
        is_minlat, lpw, seq = self.is_minlat, self.lpw, self.seq
        t_idl = A.const(self.t_idl)
        from repro.core.recurrence import maxplus_combine

        def state_fn(guess, P):
            """One full state replay of the chunk under speculated codes
            ``guess`` (policy-view; -1 = no state effects yet — the
            frozen-state guess)."""
            nows = P["nows"]
            R = guess.shape[0]
            rr = jnp.arange(R)
            is_edge_g = (guess == edge_col) if has_edge \
                else jnp.zeros(R, dtype=bool)
            is_cloud_g = (guess >= 0) & ~is_edge_g

            # --- edge busy horizons / nominations / induced waits ----------
            nom = ew = HB = h_fin = None
            if has_edge:
                ECOMP = P["ECOMP"]
                if lpw:
                    # winner feeds back into the next argmin: sequential only
                    def estep(h, xs):
                        now, ec, ie = xs
                        w = A.maximum(A.sub(h, now), A.zero)
                        d = A.argmin(w, 0)          # first-min == fleet order
                        hd = tm(lambda a: a[d], h)
                        upd = A.add(A.maximum(hd, now),
                                    tm(lambda a: a[d], ec))
                        h2 = tm(lambda a, u: a.at[d].set(
                            jnp.where(ie, u, a[d])), h, upd)
                        return h2, (h, d)

                    h_fin, (HB, nom) = lax.scan(
                        estep, P["h0"], (nows, ECOMP, is_edge_g))
                else:
                    nom = P["nom_fixed"]
                    pushm = is_edge_g[:, None] \
                        & (nom[:, None] == jnp.arange(nd)[None, :])
                    if seq:
                        def estep(h, xs):
                            now, ec, pm = xs
                            return A.where(
                                pm, A.add(A.maximum(h, now), ec), h), h

                        h_fin, HB = lax.scan(
                            estep, P["h0"], (nows, ECOMP, pushm))
                    else:
                        # exclusive max-plus scan: h_i = max(h0 + A_i, B_i)
                        a = A.where(pushm, ECOMP, A.zero)
                        b = A.where(pushm, A.add(tm(lambda x: x[:, None],
                                                    nows), ECOMP), A.ninf)
                        Ac, Bc = lax.associative_scan(
                            lambda x, y: maxplus_combine(x, y, A.maximum,
                                                         A.add),
                            (a, b), axis=0)

                        def shifted(x, fill):
                            return tm(lambda v, f: jnp.concatenate(
                                [f, v[:-1]], axis=0), x, A.full((1, nd), fill))

                        HB = A.maximum(
                            A.add(tm(lambda x: x[None, :], P["h0"]),
                                  shifted(Ac, 0.0)),
                            shifted(Bc, -np.inf))
                        h_fin = A.maximum(
                            A.add(P["h0"], tm(lambda x: x[-1], Ac)),
                            tm(lambda x: x[-1], Bc))
                waits = A.maximum(A.sub(HB, tm(lambda x: x[:, None], nows)),
                                  A.zero)
                if nom is None:
                    nom = P["nom_fixed"]
                ew = tm(lambda x: x[rr, nom], waits)

            # --- CIL pools: one scan, per-config cold flags + dispatches ---
            overflow = jnp.asarray(False)
            if nc:
                cap = jax.tree.leaves(P["busy0"])[0].shape[1]
                cidx = jnp.clip(guess, 0, nc - 1)

                def cstep(carry, xs):
                    busy, last, cnt = carry
                    now, ci, isc, occw, occc = xs
                    idle = A.le(busy, now) & A.le(now, A.add(last, t_idl))
                    cold_row = ~idle.any(axis=1)        # per-config, pre-row
                    idle_c = idle[ci]
                    # MRU reuse: first-max == the walk's strict > update
                    j_warm = A.argmax(
                        A.where(idle_c, tm(lambda x: x[ci], last), A.ninf), 0)
                    is_cold = ~idle_c.any()
                    j = jnp.where(is_cold, cnt[ci], j_warm)
                    ovf = isc & is_cold & (j >= cap)
                    jc = jnp.minimum(j, cap - 1)
                    occ = A.where(is_cold, tm(lambda x: x[ci], occc),
                                  tm(lambda x: x[ci], occw))
                    completion = A.add(now, occ)
                    do = isc & ~ovf

                    def put(x, v):
                        return x.at[ci, jc].set(jnp.where(do, v, x[ci, jc]))

                    busy = tm(put, busy, completion)
                    last = tm(put, last, completion)
                    cnt = cnt.at[ci].add(
                        jnp.where(do & is_cold, 1, 0))
                    return (busy, last, cnt), (cold_row, ovf)

                (busyF, lastF, cntF), (COLD, OVF) = lax.scan(
                    cstep, (P["busy0"], P["last0"], P["cnt0"]),
                    (nows, cidx, is_cloud_g, P["OCCW"], P["OCCC"]))
                overflow = OVF.any()
            else:
                busyF = lastF = cntF = None
                COLD = jnp.zeros((R, 0), dtype=bool)

            # --- (R, T) policy-view matrices -------------------------------
            cols_lat, cols_cost, cols_comp, cols_rank = [], [], [], []
            if nc:
                cols_lat.append(A.where(COLD, P["LATC"], P["LATW"]))
                cols_cost.append(P["COSTC"])
                cols_comp.append(P["COMPC"])
                cols_rank.append(P["RANKC"])
            if has_edge:
                def pick(x):
                    return tm(lambda v: v[rr, nom][:, None], x)

                cols_lat.append(
                    tm(lambda v: v[:, None], A.add(
                        ew, tm(lambda v: v[rr, nom], P["ELAT"]))))
                cols_cost.append(pick(P["ECOST"]))
                cols_comp.append(pick(P["ECOMP"]))
                cols_rank.append(jnp.zeros((R, 1), jnp.int32))  # free

            def cat(cols):
                return tm(lambda *v: jnp.concatenate(v, axis=1), *cols)

            LAT, COST, COMP = cat(cols_lat), cat(cols_cost), cat(cols_comp)
            RANK = jnp.concatenate(cols_rank, axis=1)

            # --- surplus bank (the third recurrence; MinLatency only) ------
            s_before = s_fin = None
            if is_minlat:
                safe_g = jnp.clip(guess, 0, T - 1)
                delta = A.where(
                    guess >= 0,
                    A.sub(P["c_max"], tm(lambda v: v[rr, safe_g], COST)),
                    A.zero)
                if seq:
                    def sstep(s, d):
                        return A.add(s, d), s

                    s_fin, s_before = lax.scan(sstep, P["s0"], delta)
                else:
                    incl = A.cumsum(delta)
                    s_before = A.add(P["s0"], tm(
                        lambda v, z: jnp.concatenate([z, v[:-1]]),
                        incl, A.full(1, 0.0)))
                    s_fin = A.add(P["s0"], tm(lambda v: v[-1], incl))
            return {"nom": nom, "ew": ew, "LAT": LAT, "COST": COST,
                    "RANK": RANK, "COMP": COMP, "COLD": COLD,
                    "s_before": s_before, "s_fin": s_fin, "h_fin": h_fin,
                    "busyF": busyF, "lastF": lastF, "cntF": cntF,
                    "overflow": overflow}

        return state_fn

    def _build_choose(self):
        jax, jnp, A = self.jax, self.jnp, self.A
        T, edge_col, has_edge = self.T, self.edge_col, self.has_edge
        is_minlat = self.is_minlat

        def col(x):
            return jax.tree.map(lambda v: v[:, None], x)

        def cheapest(keep, COST, RANK):
            """The ``keep`` columns of least cost, by rank (float64's order
            of the costs), and the rows where the arithmetic's own cost
            compare tied a column the rank left out."""
            r = jnp.where(keep, RANK, jnp.iinfo(jnp.int32).max)
            final = keep & (RANK == r.min(axis=1)[:, None])
            cmin = A.reduce_min(A.where(keep, COST, A.inf), 1)
            split = (keep & ~final & A.eq(COST, col(cmin))).any(axis=1)
            return final, split

        def choose_fn(LAT, COST, RANK, allowed, deadline, valid):
            """Codes, feasibility, and the rows whose least cost the rank
            split (``cost_rank_splits``); only costs against the budget
            compare in the arithmetic."""
            R = valid.shape[0]
            if is_minlat:
                feas = A.le(COST, col(allowed))
                none_f = ~feas.any(axis=1)
                if has_edge:
                    onehot = (jnp.arange(T) == edge_col)[None, :]
                    feas = jnp.where(none_f[:, None], onehot, feas)
                else:
                    feas = feas | none_f[:, None]
                l1 = A.where(feas, LAT, A.inf)
                lmin = A.reduce_min(l1, 1)
                tie = feas & A.eq(LAT, col(lmin))
                final, split = cheapest(tie, COST, RANK)
                code = final.argmax(axis=1).astype(jnp.int32)
                feas_out = jnp.ones(R, dtype=bool)
            else:  # MinCostPolicy (edge column guaranteed by eligibility)
                feas = A.le(LAT, deadline)
                any_f = feas.any(axis=1)
                tie, split = cheapest(feas, COST, RANK)
                l2 = A.where(tie, LAT, A.inf)
                lmin = A.reduce_min(l2, 1)
                final = tie & A.eq(LAT, col(lmin))
                code = final.argmax(axis=1).astype(jnp.int32)
                code = jnp.where(any_f, code, edge_col)
                feas_out = any_f
            return jnp.where(valid, code, -1), feas_out, split & valid

        return choose_fn

    def _build_finalize(self):
        jax, jnp, A = self.jax, self.jnp, self.A
        tm = jax.tree.map
        nc, T = self.n_cloud, self.T
        edge_col, has_edge = self.edge_col, self.has_edge
        is_minlat = self.is_minlat

        def finalize(st, code, feas, allowed, P):
            """Chosen-row gathers + committed-state bundle for one chunk."""
            R = code.shape[0]
            rr = jnp.arange(R)
            safe = jnp.clip(code, 0, T - 1)

            def chosen(x):
                return tm(lambda v: v[rr, safe], x)

            res = {"code": code, "overflow": st["overflow"],
                   "lat": chosen(st["LAT"]), "cost": chosen(st["COST"]),
                   "comp": chosen(st["COMP"]), "allowed": allowed,
                   "feas": feas}
            if is_minlat:
                res["s_fin"] = st["s_fin"]
            cold = (st["COLD"][rr, jnp.clip(code, 0, nc - 1)] if nc
                    else jnp.zeros(R, dtype=bool))
            if has_edge:
                is_edge_ch = code == edge_col
                res["cold"] = jnp.where(is_edge_ch, False, cold)
                res["wait"] = A.where(is_edge_ch, st["ew"], A.zero)
                res["nom"] = st["nom"]
                res["gcode"] = jnp.where(is_edge_ch, nc + st["nom"], code)
                res["h_fin"] = st["h_fin"]
            else:
                res["cold"] = cold
                res["wait"] = A.full(R, 0.0)
                res["gcode"] = code
            if nc:
                res["busyF"], res["lastF"], res["cntF"] = \
                    st["busyF"], st["lastF"], st["cntF"]
                # scalar pool-growth bound for the NEXT resident chunk —
                # fetched with the decision outputs, so residency never
                # materializes the pools just to size them
                res["cnt_max"] = st["cntF"].max()
            return res

        return finalize

    def _build_compact(self):
        """Device-side stable pool compaction == the deferred reap, run ON
        DEVICE so long resident streams never sync to host just to shrink
        pools. Exact by the same two properties the deferred host reap rests
        on: the keep predicate is monotone in the reap time (a record the
        per-arrival walk dropped earlier is still dropped at ``t_last``) and
        dead records are never warm-reusable (the idle check can never pass
        again), so compaction keeps exactly the records the host list would
        hold — in the same relative (list) order, preserving MRU first-max
        tie-breaks."""
        jax, jnp, A = self.jax, self.jnp, self.A
        tm = jax.tree.map
        nc, t_idl = self.n_cloud, A.const(self.t_idl)

        def compact(busy, last, cnt, t_last):
            cap = jax.tree.leaves(busy)[0].shape[1]
            slots = jnp.arange(cap)
            in_use = slots[None, :] < cnt[:, None]
            keep = in_use & (A.lt(t_last, busy)
                             | A.le(t_last, A.add(last, t_idl)))
            # stable scatter: kept slot -> its rank; dropped -> the spill
            # column (sliced off below)
            d = jnp.where(keep, jnp.cumsum(keep, axis=1) - 1, cap)
            rows = jnp.arange(nc)[:, None]

            def scatter(x, fill):
                return tm(lambda v, f: f.at[rows, d].set(v)[:, :cap],
                          x, A.full((nc, cap + 1), fill))

            return (scatter(busy, np.inf), scatter(last, -np.inf),
                    keep.sum(axis=1).astype(cnt.dtype))

        return compact

    def _build_shift(self):
        """Move resident times to a new chunk base: ``t += old - new``
        (two-float only; ``±inf`` sentinels stay put)."""
        A = self.A

        def shift(S, delta):
            out = dict(S)
            for k in ("busy0", "last0", "h0"):
                if k in out:
                    out[k] = A.add(out[k], delta)
            return out

        return shift

    def _build_place(self):
        jax, jnp, lax, A = self.jax, self.jnp, self.lax, self.A
        is_minlat = self.is_minlat
        state_fn = self._state_fn
        choose_fn = self._choose_fn
        finalize = self._finalize_fn

        def step(guess, P):
            st = state_fn(guess, P)
            if is_minlat:
                allowed = A.add(P["c_max"], A.mul(P["alpha"],
                                                  st["s_before"]))
            else:
                allowed = A.full(guess.shape[0], np.inf)
            code, feas, split = choose_fn(st["LAT"], st["COST"], st["RANK"],
                                          allowed, P["deadline"], P["valid"])
            return st, code, feas, allowed, split

        def fixed_point(P):
            R = P["valid"].shape[0]
            g0 = jnp.full(R, -1, dtype=jnp.int32)
            g1 = step(g0, P)[1]

            def cond(c):
                gp, g, i = c
                return jnp.any(gp != g) & (i < R + 2)

            def body(c):
                _, g, i = c
                return g, step(g, P)[1], i + 1

            _, gF, iters = lax.while_loop(cond, body, (g0, g1, jnp.int32(1)))
            st, code, feas, allowed, split = step(gF, P)  # code == gF
            res = finalize(st, code, feas, allowed, P)
            res["iters"] = iters
            res["splits"] = split.sum(dtype=jnp.int32)
            res["converged"] = ~jnp.any(code != gF)
            return res

        def place(P, S):
            # S carries the sequential-state seed (CIL pools, edge horizons,
            # surplus) split out so the jit can DONATE its buffers — resident
            # streams thread chunk k's final arrays in as chunk k+1's seed
            # with zero steady-state allocation. Callers must treat S as
            # consumed (place_chunk keeps a tiny device-side backup for the
            # overflow retry).
            #
            # The chunk is decided in PLACE_BLOCK-row blocks, in order, each
            # a fixed point seeded with the previous block's final state: the
            # fixed point is the exact sequential trajectory at any blocking,
            # and a pass repairs only a few rows when decisions feed back
            # (a binding surplus bank, warm-container chains), so passes
            # grow with the rows a fixed point spans.
            R = P["valid"].shape[0]
            B = min(R, PLACE_BLOCK)
            const = {k: P[k] for k in _SCALAR_INPUTS}
            rows = {k: v for k, v in P.items() if k not in _SCALAR_INPUTS}
            blocks = jax.tree.map(
                lambda v: v.reshape((R // B, B) + v.shape[1:]), rows)

            def block(S, Pb):
                res = fixed_point({**const, **Pb, **S})
                S = dict(S)
                for seed, final in _STATE_OUTPUTS:
                    if final in res:
                        S[seed] = res.pop(final)
                res.pop("cnt_max", None)
                return S, res

            S, out = lax.scan(block, S, blocks)
            res = {k: jax.tree.map(
                lambda v: v.reshape((R,) + v.shape[2:]), v)
                for k, v in out.items()
                if k not in ("overflow", "iters", "splits", "converged")}
            res["overflow"] = out["overflow"].any()
            # one read for both counts: passes, rows the rank split
            res["counts"] = jnp.stack([out["iters"].sum(dtype=jnp.int32),
                                       out["splits"].sum(dtype=jnp.int32)])
            res["converged"] = out["converged"].all()
            for seed, final in _STATE_OUTPUTS:
                if seed in S:
                    res[final] = S[seed]
            if self.n_cloud:
                res["cnt_max"] = S["cnt0"].max()
            return res

        return place

    def _run_interpret(self, P, R: int) -> dict:
        """Host-driven fixed point over the jitted FMA-free pieces: bit-exact
        (the α·s_before multiply runs op-by-op) at compiled-scan speed."""
        jax, jnp, A = self.jax, self.jnp, self.A
        g = jnp.asarray(np.full(R, -1, np.int32))
        g_np = np.asarray(g)
        st = code = feas = allowed = split = None
        iters = 0
        converged = False
        for _ in range(R + 2):
            st = self._state(g, P)
            if self.is_minlat:
                with jax.disable_jit():
                    allowed = A.add(P["c_max"],
                                    A.mul(P["alpha"], st["s_before"]))
            else:
                allowed = A.full(R, np.inf)
            code, feas, split = self._choose(st["LAT"], st["COST"],
                                             st["RANK"], allowed,
                                             P["deadline"], P["valid"])
            iters += 1
            c_np = np.asarray(code)
            if np.array_equal(c_np, g_np):
                converged = True
                break
            g, g_np = code, c_np
        res = dict(self._finalize(st, code, feas, allowed, P))
        # the converging (verification) pass isn't an iteration, matching the
        # compiled driver's count
        res["counts"] = np.array([max(iters - 1, 1), int(split.sum())])
        res["converged"] = converged
        return res

    # ------------------------------------------------------------ residency
    def _stage(self, host, R: int) -> tuple:
        """Padded device task columns ``(sizes, nbytes, nows, valid)`` of one
        chunk; times relative to the chunk's base (see ``_base``)."""
        _, nows_np, sizes_np, nbytes_np = host
        n = nows_np.shape[0]
        pad = R - n
        A = self.A
        return (A.dev(np.pad(sizes_np, (0, pad), mode="edge")),
                A.dev(np.pad(nbytes_np, (0, pad), mode="edge")),
                A.dev(np.pad(nows_np, (0, pad), mode="edge"),
                      self._base(nows_np)),
                self.jnp.asarray(np.arange(R) < n))

    def stage_chunk(self, tasks) -> dict:
        """Host prep + device upload for one chunk — engine-state-free, so
        ``runtime._Prefetcher`` can run it on the transfer thread while
        the loop works on the previous chunk (the x64
        scope is thread-local and re-entered here). The bundle reaches
        ``place_chunk`` via ``engine._jax_staged``."""
        host = task_arrays(tasks)
        n = len(tasks)
        with self._scope():
            dev = self._stage(host, max(PAD_MIN, _next_pow2(n)))
            dev = self.jax.device_put(dev)
        return {"host": host, "dev": dev, "n": n}

    def sync_host(self, reason: str = "external") -> bool:
        """Materialize resident device state into the host CIL / queues /
        policy and drop residency. Idempotent — ``False`` when nothing is
        resident. These calls (stream end, fallback exits, ``sync_engine``)
        are the ONLY host↔device state sync points of a resident stream."""
        rs = self._resident
        if rs is None:
            return False
        self._resident = None
        A = self.A
        if self.is_minlat and rs.s is not None:
            rs.policy.surplus = float(A.host(rs.s))
        if self.has_edge and rs.h is not None:
            h = A.host(rs.h) + rs.base
            for d, e in enumerate(self.edges):
                rs.queues[e.name].horizon_ms = float(h[d])
        if self.n_cloud and rs.busy is not None:
            self._commit_pools(rs.cil, A.host(rs.busy) + rs.base,
                               A.host(rs.last) + rs.base, np.asarray(rs.cnt),
                               rs.t_last)
        self.state_syncs += 1
        if reason == "fallback":
            self.fallback_syncs += 1
        return True

    def _commit_pools(self, cil, busyF, lastF, cntF, t_last):
        """The numpy accept step's pool writeback, with the reap at
        ``t_last`` == the per-arrival walk's end state (monotone keep
        predicate + dead records never warm-reused, see module docstring)."""
        for ci, c in enumerate(self.cloud):
            k = int(cntF[ci])
            b, l = busyF[ci, :k], lastF[ci, :k]
            keep = (t_last < b) | (t_last <= l + self.t_idl)
            recs = [ContainerRecord(c.name, float(bb), float(ll))
                    for bb, ll, kp in zip(b, l, keep) if kp]
            if recs:
                cil.containers[c.name] = recs
            else:
                cil.containers.pop(c.name, None)

    def _seed_state(self, rs, pools, cap, edge_queues, dev_names, policy,
                    base):
        """The (donated) sequential-state seed ``S`` — from resident device
        arrays when a valid ``DeviceStreamState`` is held (growing pool
        width device-side when ``cap`` outgrew it), else from host state
        rebased to ``base``."""
        jax, jnp, A = self.jax, self.jnp, self.A
        S: dict = {}
        if rs is not None and self.n_cloud:
            busy, last = rs.busy, rs.last
            have = int(jax.tree.leaves(busy)[0].shape[1])
            if cap > have:
                grow = ((0, 0), (0, cap - have))
                busy = jax.tree.map(lambda v: jnp.pad(
                    v, grow, constant_values=np.inf), busy)
                last = jax.tree.map(lambda v: jnp.pad(
                    v, grow, constant_values=-np.inf), last)
            S["busy0"], S["last0"], S["cnt0"] = busy, last, rs.cnt
        elif self.n_cloud:
            busy0 = np.full((self.n_cloud, cap), np.inf)
            last0 = np.full((self.n_cloud, cap), -np.inf)
            cnt0 = np.zeros(self.n_cloud, dtype=np.int32)
            for ci, recs in enumerate(pools):
                for j, rec in enumerate(recs):
                    busy0[ci, j] = rec.busy_until
                    last0[ci, j] = rec.last_completion
                cnt0[ci] = len(recs)
            S["busy0"] = A.dev(busy0, base)
            S["last0"] = A.dev(last0, base)
            S["cnt0"] = jnp.asarray(cnt0)
        else:
            S["busy0"] = A.full((0, cap), 0.0)
            S["last0"] = A.full((0, cap), 0.0)
            S["cnt0"] = jnp.zeros(0, dtype=jnp.int32)
        if self.has_edge:
            S["h0"] = rs.h if rs is not None else A.dev(np.array(
                [edge_queues[nm].horizon_ms for nm in dev_names]), base)
        if self.is_minlat:
            # np scalar, not python float: a strongly-typed aval, so host-
            # and resident-seeded calls share one jit trace per pool shape
            S["s0"] = rs.s if rs is not None \
                else A.dev(np.float64(policy.surplus))
        return S

    def _rebase(self, rs, base: float) -> None:
        """Carry resident times over to this chunk's base (two-float)."""
        if rs.base == base:
            return
        S = {"busy0": rs.busy, "last0": rs.last, "h0": rs.h}
        S = self._shift({k: v for k, v in S.items() if v is not None},
                        self.A.const(rs.base - base))
        rs.busy = S.get("busy0")
        rs.last = S.get("last0")
        rs.h = S.get("h0")
        rs.base = base

    # ----------------------------------------------------------- chunk entry
    def place_chunk(self, engine, tasks, edge_queues, interpret: bool):
        """Run one chunk device-resident; returns a ``DecisionBatch`` with
        committed host state (or, under ``serve_stream`` residency, state
        left ON DEVICE), or ``None`` to fall back — in which case any
        resident state is synced first so the host walk sees canonical
        state and no balancer/RNG state is consumed."""
        from repro.core.decision import (
            DecisionBatch,
            RandomBalancer,
            RoundRobinBalancer,
        )

        jax, jnp, A = self.jax, self.jnp, self.A
        n = len(tasks)
        spans = engine.__dict__.get("_spans")   # serve_stream's recorder
        staged = engine.__dict__.pop("_jax_staged", None)
        if staged is not None and staged[0] is not tasks:
            staged = None       # stale prefetch for some other chunk
        if staged is not None:
            host = staged[1]["host"]
        else:
            host = task_arrays(tasks)
        task_idx, nows_np = host[0], host[1]
        if not self.has_edge and self.is_minlat and not self.cloud:
            self.sync_host("fallback")
            return None  # nothing to choose from — let the walk raise
        if n > 1 and not bool(np.all(np.diff(nows_np) >= 0.0)):
            self.sync_host("fallback")
            return None  # out-of-order arrivals: host walk replays reaps

        residency = bool(engine.__dict__.get("_device_residency", False))
        if not residency:
            # an out-of-stream place_many while state is resident: the
            # legacy per-chunk path needs canonical host state first
            self.sync_host("external")
        cil: ContainerInfoList = engine.predictor.cil
        policy = engine.policy
        rs = self._resident
        if rs is not None and (
                rs.cil is not cil or rs.policy is not policy
                or rs.queues is not edge_queues
                or (n and float(nows_np[0]) < rs.t_last)):
            # host-structure swap or a cross-chunk out-of-order arrival:
            # the resident state no longer shadows this stream — sync, then
            # re-enter residency from host state below
            self.sync_host("fallback")
            rs = None

        # Everything below may consume balancer state — no fallback past here.
        nom_fixed = None
        draws = 0
        if self.has_edge and not self.lpw:
            if self.n_dev == 1:
                nom_fixed = np.zeros(n, dtype=np.int64)
            else:
                bal = engine.balancer
                if type(bal) is RoundRobinBalancer:
                    nom_fixed = (bal._i + np.arange(n, dtype=np.int64)) \
                        % self.n_dev
                    bal._i += n
                elif type(bal) is RandomBalancer:
                    nom_fixed = bal.rng.integers(
                        self.n_dev, size=n).astype(np.int64)
                    draws = n

        R = max(PAD_MIN, _next_pow2(n))
        pad = R - n
        base = self._base(nows_np)
        cloud_names = [c.name for c in self.cloud]
        dev_names = [e.name for e in self.edges]
        pools = [cil.containers.get(nm, []) for nm in cloud_names]
        if rs is not None:
            max_existing = int(rs.cnt_max)
            cap = rs.cap
        else:
            max_existing = max((len(p) for p in pools), default=0)
            cap = _next_pow2(max(self._cap_hint, POOL_MIN_CAP))
        cap = max(cap, R // POOL_ROWS_PER_SLOT)

        with self._scope():
            if rs is not None:
                self._rebase(rs, base)
            if staged is not None:
                sizes, nbytes, nows_d, valid_d = staged[1]["dev"]
            else:
                sizes, nbytes, nows_d, valid_d = self._stage(host, R)
            if interpret:
                # op-by-op: the predict pass is where the FMA-prone
                # multiplies live (ridge, pricing); eager execution keeps
                # every op individually rounded, bit-identical to numpy
                with jax.disable_jit():
                    P = dict(self._predict(sizes, nbytes))
            else:
                P = dict(self._predict(sizes, nbytes))
            P["nows"] = nows_d
            P["valid"] = valid_d
            if self.has_edge:
                P["ECOST"] = A.full((R, self.n_dev), 0.0)
                if nom_fixed is not None:
                    P["nom_fixed"] = jnp.asarray(np.pad(
                        nom_fixed, (0, pad)).astype(np.int32))
                else:
                    P["nom_fixed"] = jnp.zeros(R, dtype=jnp.int32)
            if self.is_minlat:
                P["c_max"] = A.const(policy.c_max)
                P["alpha"] = A.const(policy.alpha)
                P["deadline"] = A.const(0.0)
            else:
                P["c_max"] = A.const(0.0)
                P["alpha"] = A.const(0.0)
                P["deadline"] = A.const(policy.deadline_ms)
            res = None
            compacted = rs is None   # host seeds arrive freshly reaped
            if spans is not None:
                spans.switch("place")
            while True:
                if cap < max_existing + 1:
                    cap = _next_pow2(max_existing + 1)
                S = self._seed_state(rs, pools, cap, edge_queues, dev_names,
                                     policy, base)
                if interpret:
                    res = self._run_interpret({**P, **S}, R)
                else:
                    # the jit DONATES S; a resident seed must survive an
                    # overflow retry, so keep a (tiny) device-side copy
                    backup = (jax.tree.map(jnp.copy, S)
                              if rs is not None else None)
                    res = self._place(P, S)
                if not bool(self._read(res["overflow"])) \
                        and bool(self._read(res["converged"])):
                    break
                # pool too small for this chunk's cold starts (clamped
                # writes may also stall convergence): results are discarded
                # (no state was committed) and the chunk re-runs
                if rs is not None:
                    self.resident_regrows += 1
                    if not interpret:
                        # donated seed was consumed — restore from backup
                        rs.busy, rs.last, rs.cnt = (
                            backup["busy0"], backup["last0"], backup["cnt0"])
                        rs.cap = int(jax.tree.leaves(rs.busy)[0].shape[1])
                        cap = rs.cap
                        if "h0" in backup:
                            rs.h = backup["h0"]
                        if "s0" in backup:
                            rs.s = backup["s0"]
                    if not compacted and self.n_cloud:
                        # reap ON DEVICE first — a long resident stream
                        # accumulates dead records (the deferred reap), so
                        # compaction usually beats growing the pool and
                        # keeps steady-state pool width bounded by the LIVE
                        # container count, all without a host sync
                        rs.busy, rs.last, rs.cnt = self._compact(
                            rs.busy, rs.last, rs.cnt,
                            A.const(rs.t_last - rs.base))
                        rs.cnt_max = int(self._read(rs.cnt).max())
                        max_existing = rs.cnt_max
                        compacted = True
                        continue
                # ... against a doubled pool, capped at existing+R where
                # overflow is impossible and convergence is guaranteed
                new_cap = min(cap * 2, _next_pow2(max_existing + R))
                if new_cap <= cap:
                    raise RuntimeError(
                        "jax placement did not converge with an "
                        "overflow-proof container pool")
                cap = new_cap
            self._cap_hint = cap
            if spans is not None:
                spans.switch("d2h")

            out = {k: self._read_f(res[k])[:n] for k in
                   ("lat", "cost", "comp", "wait", "allowed")}
            out.update({k: self._read(res[k])[:n] for k in
                        ("gcode", "cold", "feas")})
            iters, splits = (int(v) for v in self._read(res["counts"]))
            self.cost_rank_splits += splits
            t_last = float(nows_np[-1])
            if residency:
                # ---- stay resident: committed state LIVES on device -------
                if rs is None:
                    rs = DeviceStreamState(base=base)
                if self.n_cloud:
                    rs.busy, rs.last, rs.cnt = \
                        res["busyF"], res["lastF"], res["cntF"]
                    rs.cnt_max = int(self._read(res["cnt_max"]))
                if self.has_edge:
                    rs.h = res["h_fin"]
                if self.is_minlat:
                    rs.s = res["s_fin"]
                rs.cap = cap
                rs.t_last = t_last
                rs.chunks += 1
                rs.rng_draws += draws
                rs.cil, rs.policy, rs.queues = cil, policy, edge_queues
                self._resident = rs
                self.resident_chunks += 1
            else:
                # ---- commit host state (the numpy accept step, once) ------
                if self.is_minlat:
                    policy.surplus = float(self._read_f(res["s_fin"]))
                if self.has_edge:
                    h_fin = self._read_f(res["h_fin"]) + base
                    for d, nm in enumerate(dev_names):
                        edge_queues[nm].horizon_ms = float(h_fin[d])
                if self.n_cloud:
                    self._commit_pools(cil, self._read_f(res["busyF"]) + base,
                                       self._read_f(res["lastF"]) + base,
                                       self._read(res["cntF"]), t_last)
                self.chunk_commits += 1

        nom_out = None
        if self.has_edge:
            nom_out = self._read(res["nom"])[:n].astype(np.int64)
        engine.columnar_stats = {"chunks": 1, "repairs": max(iters - 1, 0),
                                 "walked": 0, "n": n}
        self.last_stats = {"n": n, "passes": iters + 1, "rows": R,
                           "pool_cap": cap, "interpret": interpret,
                           "gbrt_kernel": self.use_gbrt_kernel,
                           "resident": residency}
        engine.jax_stats = dict(self.last_stats)
        return DecisionBatch(
            batch=None,
            names=tuple(cloud_names) + tuple(dev_names),
            n_cloud=self.n_cloud,
            task_idx=task_idx,
            target_codes=out["gcode"].astype(np.int64),
            latency_ms=out["lat"],
            cost=out["cost"],
            cold=out["cold"].astype(bool),
            comp_ms=out["comp"],
            queue_wait_ms=out["wait"],
            feasible=out["feas"].astype(bool),
            allowed_cost=out["allowed"],
            edge_device_codes=nom_out,
            batch_factory=lambda pred=engine.predictor, ts=tasks:
                pred.predict_batch(ts),
        )


# ------------------------------------------------------------------ caching
def core_for(engine) -> JaxPlacementCore | None:
    """The engine's cached core, rebuilt when model identities / policy /
    kernel mode change; ``None`` when the engine shape is outside the core
    (``CoreIneligible``). Any other build failure raises."""
    key = _engine_key(engine)
    hit = engine.__dict__.get("_jax_core_cache")
    if hit is not None and hit[0] == key:
        core = hit[1]
        if core is None or core.valid_for(engine):
            return core
    if hit is not None and hit[1] is not None:
        # the outgoing core may hold resident stream state (a hedged-policy
        # swap mid-stream changes the key): materialize before replacing,
        # or the unsynced device state would be orphaned
        hit[1].sync_host("fallback")
    try:
        core = JaxPlacementCore(engine)
    except CoreIneligible:
        core = None
    engine.__dict__["_jax_core_cache"] = (key, core)
    return core


def sync_engine(engine, reason: str = "external") -> bool:
    """Materialize any device-resident stream state this engine's core
    holds back into the host CIL / queues / policy — the hook for external
    consumers (twin executors, admission snapshots, direct state reads).
    Safe no-op (``False``) when nothing is resident."""
    hit = engine.__dict__.get("_jax_core_cache")
    if hit is not None and hit[1] is not None:
        return hit[1].sync_host(reason)
    return False
