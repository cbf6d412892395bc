"""One run of one cell: set up, warm up, measure, check, report.

Phases, on the host clock from the process's start:

1. set-up: imports, the deployment's fitted tables, the runtime, and the
   warm-up chunks, which compile (or load from the compile cache) every
   program the window uses; ``setup_s`` ends when the window opens;
2. the window, as the traffic file's kind says (``harness.window``), with
   the warmed heap frozen out of the garbage collector's reach; with
   ``--trace 1`` the profiler records from the window's start to the end of
   the stream;
3. after the stream: the device's peak memory, then the program's state is
   dropped and the program's host path serves the same chunks again, untimed
   through the warm-up and timed over the window's first chunks (the
   single-threaded baseline);
4. the plain reference over every task served, and the comparison;
5. the result line.
"""

from __future__ import annotations

import gc
import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from harness import arrivals, compare, models, reference, spec, sut, trace
from harness import window as win

BASELINE_TASKS = 16384   # window tasks the host-path baseline times, at most
BASELINE_S = 5.0         # ... and seconds, at most (it stops after a chunk)


class CompileCounter:
    """Counts executables JAX builds or loads from its cache (each new
    program shape), and the seconds spent loading cached ones."""

    def __init__(self):
        import jax

        self.builds = 0
        self.cache_misses = 0
        self.cache_load_s = 0.0
        self.compile_s = 0.0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.builds += 1
                self.compile_s += duration
            elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
                self.cache_load_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


class _Tracer:
    def __init__(self, directory: Path):
        self.dir = directory
        self.on = False
        self.t0 = self.t1 = math.nan
        self.chunks = 0      # chunks complete when the trace stopped

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.t0 = time.perf_counter()
        self.on = True

    def stop(self):
        import jax

        if self.on:
            self.t1 = time.perf_counter()   # the recorded span ends here
            jax.profiler.stop_trace()
            self.on = False


class Hook:
    """The chunk callback the runtime's twin calls: a kind's driver sets
    ``fn``, the run sets ``after``."""

    def __init__(self):
        self.fn = self.after = None

    def __call__(self, c):
        if self.fn is not None:
            self.fn(c)
        if self.after is not None:
            self.after(c)


def _replay(rt, backend, hook, seed, traffic, cfg, seconds, open_window,
            kept):
    """Closed-loop replay: chunks back to back until the window closes,
    plus the one chunk a prefetching stream has already taken. Warm-up chunk
    ``k`` arrives at ``warm_rate_scale[k]`` times the stream's rate: a denser
    stretch grows the container pools to the width a day of service with its
    peaks would have left, so that no pool regrowth (a recompile) falls in
    the window. With ``warm_seed`` every run's warm-up is the same history,
    so every run enters its window in the same state (the Alg. 1 surplus
    bank settles at a level that depends on the history and then holds)."""
    rows = int(traffic["chunk_rows"])
    scales = [float(x) for x in traffic["warm_rate_scale"]]
    warm = len(scales)
    start_ms = cfg["stream_start_ms"]
    blocks, stream = arrivals.warm_and_window(
        cfg["app_spec"], traffic["process"], seed, [rows] * warm, scales,
        traffic.get("warm_seed"))
    t_out: list[float] = []

    def on_chunk(c):
        t_out.append(c.t_out)
        if len(t_out) == warm:
            open_window()

    hook.fn = on_chunk

    def chunks():
        k = 0
        while k < warm or win.replay_close(t_out, warm, seconds) is None:
            arr, size, nb = blocks[k] if k < warm else stream.block(rows)
            kept.append((arr, size, nb))
            yield sut.task_chunk(k * rows, arr + start_ms, size, nb)
            k += 1

    res = sut.serve(rt, chunks(), rows)
    return res, {"warm": warm, "rows": rows}


def _open_loop(rt, backend, hook, seed, traffic, cfg, seconds, open_window,
               kept):
    """Open loop on the wall clock after a warm-up in simulated time: one
    chunk of each of ``warm_rows`` (every padded shape the window may use),
    then every task due within ``seconds`` of the last warm-up arrival,
    released when due."""
    start_ms = cfg["stream_start_ms"]
    warm_rows = [int(r) for r in traffic["warm_rows"]]
    max_batch = int(traffic["max_batch"])
    idx = [0]

    def emit(arr, size, nb):
        kept.append((arr, size, nb))
        c = sut.task_chunk(idx[0], arr + start_ms, size, nb)
        idx[0] += len(arr)
        return c

    warm, stream = arrivals.warm_and_window(
        cfg["app_spec"], traffic["process"], seed, warm_rows,
        [1.0] * len(warm_rows), traffic.get("warm_seed"))
    t_warm_end = float(warm[-1][0][-1])
    per = max(16, int(traffic["process"]["rate_per_s"] * seconds * 1.5))
    parts = []
    while not parts or parts[-1][0][-1] - t_warm_end <= seconds * 1e3:
        parts.append(stream.block(per))
    arr, size, nb = (np.concatenate([p[i] for p in parts]) for i in range(3))
    keep = arr - t_warm_end <= seconds * 1e3
    arr, size, nb = arr[keep], size[keep], nb[keep]
    loop = win.OpenLoop((arr - t_warm_end) / 1e3, max_batch,
                        float(traffic["drain_s"]))
    n_warm = len(warm_rows)
    chunk_of: list[tuple[int, int]] = []

    def on_chunk(c):
        if len(backend.chunks) == n_warm:
            open_window()
            loop.start(time.perf_counter())

    hook.fn = on_chunk

    def chunks():
        for a, s, b in warm:
            yield emit(a, s, b)
        while True:
            got = loop.next_batch()
            if got is None:
                return
            lo, hi = got
            chunk_of.append((lo, hi))
            yield emit(arr[lo:hi], size[lo:hi], nb[lo:hi])

    res = sut.serve(rt, chunks(), max_batch)
    return res, {"warm": n_warm, "loop": loop, "chunk_of": chunk_of,
                 "rows": max_batch}


# The window drivers of the traffic kinds. A mix that needs code of its own
# brings ``chipbench/traffic/<mix>.py`` with a ``drive`` function of the same
# signature, which returns what the driver of its file's ``kind`` returns.
KINDS = {"replay": _replay, "open_loop": _open_loop}


def run(root: Path, cell_name: str, seed: int, seconds: int, traced: bool,
        t_start: float, device: dict) -> dict:
    import jax

    bench = spec.load(root)
    cell = spec.cell(bench, cell_name)
    cfg = spec.config(root, bench, cell["config"])
    traffic = spec.traffic(root, cell["traffic"])
    kind = traffic["kind"]
    counter = CompileCounter()
    t_imported = time.perf_counter()

    tables = models.fit_deployment(cfg)
    t_fit = time.perf_counter()
    hook = Hook()
    rt, backend = sut.build(cfg, tables, seed, on_chunk=hook)
    proc = dict(traffic["process"])
    if proc.get("rate_per_s") is None:
        proc["rate_per_s"] = cfg["app_spec"]["arrival_rate_per_s"]
    traffic = dict(traffic, process=proc)
    tracer = _Tracer(root / "chipbench" / ".trace" / cell_name) \
        if traced else None
    kept: list = []
    at_open = {}

    full_pauses: list[float] = []   # full collections in the stream, s
    gc_t0 = [0.0]

    def full_gc(phase, info):
        if info["generation"] == 2:
            if phase == "start":
                gc_t0[0] = time.perf_counter()
            else:
                full_pauses.append(time.perf_counter() - gc_t0[0])

    def open_window():
        at_open["builds"] = counter.builds
        at_open["jit"] = sut.compile_stats(rt)
        # The warmed heap (the objects JAX's caches keep from tracing every
        # shape) moves out of the collector's reach, as a latency-bound
        # Python server does after its warm-up: otherwise a full collection
        # walks all of it, stalling the window, whenever the objects made
        # since the last one pass a quarter of it. Objects made in the
        # window are collected as before.
        gc.freeze()
        gc.callbacks.append(full_gc)
        if tracer is not None:
            tracer.start()

    if tracer is not None:
        # the trace spans the window's first chunks, until ``trace_s`` has
        # passed: a place program runs millions of small device operations,
        # and the profiler's buffer holds about one chunk of them
        trace_s = float(traffic.get("trace_s", 0.0))

        def after(c):
            if tracer.on and c.t_out - tracer.t0 >= trace_s:
                tracer.stop()
                tracer.chunks = len(backend.chunks)
        hook.after = after
    drive = spec.traffic_driver(root, cell["traffic"]) or KINDS[kind]
    res, info = drive(rt, backend, hook, seed, traffic, cfg, seconds,
                      open_window, kept)
    t_stream_end = time.perf_counter()
    gc.callbacks.remove(full_gc)
    frozen = gc.get_freeze_count()
    gc.unfreeze()
    if tracer is not None and tracer.on:
        tracer.stop()
        tracer.chunks = len(backend.chunks)
    chunks = backend.chunks
    warm = info["warm"]
    t_window = chunks[warm - 1].t_out
    setup_s = t_window - t_start
    window_builds = counter.builds - at_open["builds"]
    stats_after = sut.compile_stats(rt)
    mem = jax.devices()[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    pool_cap = chunks[-1].stats.get("pool_cap")

    metrics_e2e = {}
    if kind == "replay":
        rate, n_win, span = win.replay_rate([c.t_out for c in chunks],
                                            [c.n for c in chunks], warm,
                                            seconds)
        metrics_e2e["replay_rate"] = {"value": rate, "unit": "tasks/s"}
        k_close = win.replay_close([c.t_out for c in chunks], warm, seconds)
        window_chunks = list(range(warm, k_close + 1))
        _log(f"window: {n_win} tasks in {span:.6f} s over "
             f"{len(window_chunks)} chunks of {info['rows']} rows; "
             f"drain {len(chunks) - k_close - 1} chunk(s)")
        took = sorted(((chunks[k].t_out - chunks[k - 1].t_out, k)
                       for k in window_chunks), reverse=True)
        _log("slowest window chunks (s, passes): " + ", ".join(
            f"#{k - warm} {dt:.6f} {chunks[k].stats.get('passes')}"
            for dt, k in took[:3]) + f"; median {took[len(took) // 2][0]:.6f}")
    else:
        loop, chunk_of = info["loop"], info["chunk_of"]
        decided = np.full(loop.off.shape[0], np.nan)
        for (lo, hi), c in zip(chunk_of, chunks[warm:]):
            decided[lo:hi] = c.t_in
        lat = win.decision_latency_ms(loop.due(), decided, t_stream_end)
        p50, p95 = win.percentile(lat, 50), win.percentile(lat, 95)
        metrics_e2e["decision_p50_ms"] = {"value": p50, "unit": "ms"}
        metrics_e2e["decision_p95_ms"] = {"value": p95, "unit": "ms"}
        window_chunks = list(range(warm, len(chunks)))
        info["gen_late_ms"] = (loop.release - loop.due()) * 1e3
        batches = [hi - lo for lo, hi in chunk_of]
        due = loop.due()

        def backlog(t):
            # tasks due by ``t`` whose decisions had not reached the host
            return int(np.count_nonzero(due <= t)
                       - np.count_nonzero(decided <= t))
        half, close = loop.t0 + seconds / 2, loop.t0 + seconds
        _log(f"window: {loop.off.shape[0]} tasks due in {seconds} s "
             f"({loop.off.shape[0] / seconds:.3f}/s), "
             f"{int(np.isnan(decided).sum())} undecided; "
             f"{len(chunk_of)} micro-batches, mean {np.mean(batches):.3f} "
             f"rows, max {max(batches)}; backlog {backlog(half)} tasks at "
             f"half time, {backlog(close)} at close; p50 {p50:.6f} ms p95 "
             f"{p95:.6f} ms; stream end {t_stream_end - close:.6f} s after "
             f"close")
    metrics_e2e["setup_s"] = {"value": setup_s, "unit": "s"}
    wanted = [m["name"] for m in spec.e2e_of(bench, cell_name)]
    missing_m = [m for m in wanted if m not in metrics_e2e]
    if missing_m:
        raise ValueError(f"{cell_name} ({kind}) cannot report {missing_m}")
    metrics_e2e = {m: metrics_e2e[m] for m in wanted}
    warm_s = t_window - t_fit
    _log(f"setup: {setup_s:.6f} s = imports {t_imported - t_start:.6f} + "
         f"fit {t_fit - t_imported:.6f} + build and warm-up "
         f"{warm_s:.6f} (programs built or loaded {at_open['builds']}, "
         f"compiled {counter.cache_misses} uncached, compile "
         f"{counter.compile_s:.6f} s of which cache loads "
         f"{counter.cache_load_s:.6f} s)")
    _log(f"compiles in the window: {window_builds} "
         f"(jit caches {at_open['jit']} -> {stats_after}); "
         f"final pool_cap {pool_cap}; memory_peak_bytes {peak}")
    _log(f"garbage collector: {frozen} objects frozen at the "
         f"window's start; {len(full_pauses)} full collections in the "
         f"stream, longest {max(full_pauses, default=0.0):.6f} s")
    layer_ctx = {"chunks": chunks, "window_chunks": window_chunks,
                 "warm": warm, "cfg": cfg, "tables": tables,
                 "device": device, "info": info}
    stream_stats = dict(rt.stream_stats or {})

    # ---- after the window: drop the program's state ---------------------
    del rt
    gc.collect()
    arr = np.concatenate([k[0] for k in kept]) + cfg["stream_start_ms"]
    size = np.concatenate([k[1] for k in kept])
    nb = np.concatenate([k[2] for k in kept])
    served = sut.records(res)
    del res
    gc.collect()
    base_chunks, lo = [], 0
    for a, _, _ in kept:
        base_chunks.append(sut.task_chunk(lo, arr[lo:lo + len(a)],
                                          size[lo:lo + len(a)],
                                          nb[lo:lo + len(a)]))
        lo += len(a)
    b_rate, b_n, b_s = sut.numpy_rate(cfg, tables, seed, base_chunks,
                                      info["rows"], warm, BASELINE_TASKS,
                                      BASELINE_S)
    _log(f"host path (numpy, GBRT tree walk) on the window's first chunks, "
         f"from the same warm-up: {b_rate:.6f} tasks/s ({b_n} tasks in "
         f"{b_s:.6f} s); device path stream stats {stream_stats}")

    # ---- the check ---------------------------------------------------------
    t = time.perf_counter()
    ref = reference.serve(cfg, tables, seed, arr, size, nb)
    numbers = compare.readings(served, ref, reference.target_names(cfg))
    if kind == "open_loop":
        # window tasks never released: their answers never came
        numbers["missing"] += int(info["loop"].off.shape[0] - info["loop"].i)
    correct, rows_ = compare.verdict(numbers)
    _log(f"reference over {arr.shape[0]} tasks: "
         f"{time.perf_counter() - t:.6f} s")

    dev_out = dict(device)
    dev_out["memory_peak_bytes"] = peak
    out = {"correct": bool(correct), "attempted": int(arr.shape[0]),
           "failed": int(numbers["missing"] + numbers["decisions_differ"]),
           "metrics": metrics_e2e, "device": dev_out}
    if traced:
        red = trace.reduce(tracer.dir, tracer.t0, tracer.t1)
        layer_ctx["trace"] = red
        layer_ctx["traced"] = list(range(warm, tracer.chunks))
        out["device"]["busy_s"] = red["busy_s"]
        out["device"]["window_s"] = red["window_s"]
        out["metrics"] = spec.per_layer(root, bench, cell_name, layer_ctx)
        out["breakdown"] = red["breakdown"]
        for note in layer_ctx.get("notes", []):
            _log(note)
        _log(f"trace: busy {red['busy_s']:.6f} s of {red['window_s']:.6f} s;"
             f" programs {red['programs']}; kernel {red['kernel_s']:.9f} s "
             f"over {len(red['kernel_calls'])} launches")
    out["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows_}
    for k, v, lim in rows_:
        _log(f"check {k}: {v} (limit {lim})")
    return out
