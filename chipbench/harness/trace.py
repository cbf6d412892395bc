"""Reduction of a profiler trace (``.xplane.pb``) to device times.

Read with ``jax.profiler.ProfileData``. A TPU's plane is named
``/device:TPU:<n>``; its line ``XLA Modules`` holds one event per execution
of a compiled program (``jit_place(<hash>)``, ``jit_predict(<hash>)``, ...),
and ``XLA Ops`` one per operation, which the profiler stops recording when
its buffer fills (a long place program runs millions of small operations).
So:

- device busy time is the union of the program executions' intervals, per
  chip, averaged over the chips traced;
- a program's device time is the sum of its executions' durations, by the
  name JAX gives it with ``jit_`` and the hash taken off;
- a kernel's device time is the sum of the operation events that name it,
  inside executions of the program that launches it, counted only for the
  executions whose operations were all recorded (before the buffer filled);
- each long idle gap between program executions is labelled with what the
  host was doing: the host event that overlaps the gap most.

Host and device events share the trace's clock (ns from its start).
"""

from __future__ import annotations

import re
from pathlib import Path

import numpy as np

_HASH = re.compile(r"\(\d+\)$")
MODULES, OPS = "XLA Modules", "XLA Ops"


def program_name(event_name: str) -> str:
    """``jit_place(1234)`` -> ``place``."""
    n = _HASH.sub("", event_name)
    return n[4:] if n.startswith("jit_") else n


def union_length(iv: np.ndarray) -> float:
    """Total length covered by intervals ``iv`` (n, 2)."""
    if iv.size == 0:
        return 0.0
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    total, cs, ce = 0.0, iv[0, 0], iv[0, 1]
    for s, e in iv[1:]:
        if s > ce:
            total += ce - cs
            cs, ce = s, e
        elif e > ce:
            ce = e
    return total + (ce - cs)


def gaps(iv: np.ndarray) -> list[tuple[float, float]]:
    """The idle stretches between the merged intervals ``iv``."""
    if iv.size == 0:
        return []
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out, ce = [], iv[0, 1]
    for s, e in iv[1:]:
        if s > ce:
            out.append((ce, s))
        ce = max(ce, e)
    return out


def xplane_file(directory: Path) -> Path:
    files = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def load(path: Path) -> dict:
    """The events the reduction uses, as plain tuples:
    ``{"devices": {plane: {"modules": [(name, start_ns, dur_ns)], "ops":
    [(name, start_ns, dur_ns)]}}, "host": [(name, start_ns, dur_ns)]}``.
    ``path`` may be gzip-compressed (``.gz``)."""
    import gzip

    import jax

    raw = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    pd = jax.profiler.ProfileData.from_serialized_xspace(raw)
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            d = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {MODULES: "modules", OPS: "ops"}.get(line.name)
                if key is not None:
                    d[key] = [(e.name, e.start_ns, e.duration_ns)
                              for e in line.events]
            out["devices"][plane.name] = d
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend((e.name, e.start_ns, e.duration_ns)
                                   for e in line.events)
    return out


def is_kernel(op_name: str, kernel: str) -> bool:
    return "custom-call" in op_name and kernel in op_name


def reduce_events(ev: dict, window_s: float, kernel: str = "gbrt",
                  kernel_program: str = "predict", top: int = 10) -> dict:
    """Device times from loaded events (see ``load``)."""
    devs = ev["devices"]
    if not devs:
        raise ValueError("the trace holds no TPU plane")
    busy, programs, kernel_s, kernel_calls = [], {}, 0.0, []
    gap_list = []
    for d in devs.values():
        mods = d["modules"]
        iv = np.array([(s, s + t) for _, s, t in mods], np.float64) \
            .reshape(-1, 2)
        busy.append(union_length(iv) / 1e9)
        gap_list.extend(gaps(iv))
        for name, _, t in mods:
            p = program_name(name)
            programs[p] = programs.get(p, 0.0) + t / 1e9
        # kernel operations, per execution of the launching program; the op
        # line ends where the profiler's buffer filled, so an execution
        # counts only if an operation starts after it ended
        ops = sorted(d["ops"], key=lambda o: o[1])
        last_op = ops[-1][1] if ops else -1.0
        starts = np.array([o[1] for o in ops], np.float64)
        calls = [(s, s + t) for name, s, t in mods
                 if program_name(name) == kernel_program]
        for i, (s, e) in enumerate(sorted(calls)):
            if last_op < e:
                break
            lo, hi = np.searchsorted(starts, [s, e], side="left")
            k = [ops[j][2] for j in range(lo, hi)
                 if is_kernel(ops[j][0], kernel)]
            if not k:
                break
            kernel_s += sum(k) / 1e9
            kernel_calls.append(i)
    n_dev = len(devs)
    host = sorted(ev["host"], key=lambda h: h[1])
    hs = np.array([h[1] for h in host], np.float64)
    he = np.array([h[1] + h[2] for h in host], np.float64)
    labelled = []
    for a, b in sorted(gap_list, key=lambda g: g[0] - g[1])[:top]:
        ov = np.minimum(he, b) - np.maximum(hs, a)
        j = int(np.argmax(ov)) if ov.size else -1
        label = host[j][0] if j >= 0 and ov[j] > 0 else "no host event"
        labelled.append([label, float((b - a) / 1e9)])
    ranked = sorted(programs.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": float(sum(busy) / n_dev), "window_s": float(window_s),
            "programs": {k: v / n_dev for k, v in programs.items()},
            "kernel_s": kernel_s / n_dev, "kernel_calls": kernel_calls,
            "breakdown": {"device_ops": [[k, v / n_dev] for k, v in ranked],
                          "idle_gaps": labelled}}


def reduce(directory: Path, t0: float, t1: float, **kw) -> dict:
    return reduce_events(load(xplane_file(directory)), t1 - t0, **kw)
