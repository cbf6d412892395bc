"""The plain reference: the paper's placement and the twin's ground truth,
task by task, written apart from the program.

Given the deployment (configuration + the benchmark's fitted tables), a task
stream and the twin seed, it returns what a correct serve produces for every
task: the chosen target, its predicted latency, cost and cold start, whether
the policy found it feasible, and the executed outcome (actual latency, cost,
cold start). It imports nothing of the program and reads nothing the program
made.

Semantics (paper Sec. III-V, Alg. 1):

- predict: cloud latency = upload + start (warm or cold) + compute + store,
  with compute from the GBRT over (size, memory) and cost from Lambda billing
  (round to ms, at least 1, up to the 100 ms quantum, x GB x rate); edge
  latency = predicted FIFO wait + compute/speed + IoT upload + store, cost 0;
- the container information list: a config is predicted warm when one of its
  containers is idle and unexpired at the arrival (completion <= now <=
  completion + T_idl); a dispatch reuses the most recently completed idle
  container, else adds one;
- the balancer nominates the edge device with the least predicted wait (fleet
  order breaks ties); the policy sees the cloud configs plus that device;
- MinLatency: the cheapest-enough targets (cost <= c_max + alpha*surplus),
  least (latency, cost), first in order on ties; surplus += c_max - cost;
- MinCost: targets meeting the deadline, least (cost, latency); none: the
  nominated device, infeasible;
- the twin: one random stream per (substrate, leg), seeded ``[seed, 7, leg]``
  for the cloud and ``[seed, crc32(device), leg]`` per edge device, container
  lifetimes from ``default_rng(seed)``; a per-config container pool walked in
  dispatch order at trigger time (arrival + upload), and a single-slot FIFO
  per edge device.

``dtype`` selects the arithmetic: float64 is the configuration's precision;
float32 is the control (every input, table and running value rounded to
float32), which the comparison must refuse.
"""

from __future__ import annotations

import bisect
import zlib

import numpy as np

from harness.models import FULL_VCPU_MB, _scaled, tree_walk

CLOUD_LEGS = 4      # upload, start, compute, store
BLOCK = 8192        # rows converted to Python scalars at a time


def target_names(cfg: dict) -> list[str]:
    return [str(m) for m in cfg["memory_configs_mb"]] + list(cfg["edge_fleet"])


def gbrt_columns(g: dict, sizes: np.ndarray, mems, dtype) -> np.ndarray:
    """(n, C) GBRT predictions of ``sizes`` at each memory config.

    With the memory feature fixed, every split on it is a constant, so the
    ensemble is a step function of the size whose steps sit at the size
    thresholds: walking the trees once at each step's right end and reading
    the step of every size gives exactly the tree walk of every row."""
    feats = g["features"]
    thr = np.asarray(g["thresholds"], dtype)
    leaves = np.asarray(g["leaves"], dtype)
    lr = dtype(g["learning_rate"])
    breaks = np.unique(thr[(feats == 0) & np.isfinite(thr)])
    reps = np.concatenate([breaks, np.array([np.inf], dtype)])
    sizes = np.asarray(sizes, dtype)
    step = np.searchsorted(breaks, sizes, side="left")
    out = np.empty((sizes.shape[0], len(mems)), dtype)
    for c, mem in enumerate(mems):
        x = np.stack([reps, np.full(reps.shape[0], dtype(mem), dtype)], axis=1)
        vals = np.full(reps.shape[0], dtype(g["base"]), dtype)
        for t in range(feats.shape[0]):
            vals = vals + lr * tree_walk(x, feats[t], thr[t], leaves[t],
                                         int(g["max_depth"]))
        out[:, c] = vals[step]
    return out


def predict(cfg: dict, tables: dict, size, nbytes, dtype=np.float64) -> dict:
    """Every component prediction over all tasks x targets (no state)."""
    f = dtype
    mems = [f(m) for m in cfg["memory_configs_mb"]]
    size = np.asarray(size, f)
    nbytes = np.asarray(nbytes, f)
    zero = f(0.0)
    comp = np.maximum(gbrt_columns(tables["gbrt"], size, cfg["memory_configs_mb"],
                                   f), zero)
    u0, u1 = (f(v) for v in tables["upld"])
    upld = np.maximum(u0 + nbytes * u1, zero)[:, None]
    sw = max(f(tables["start_warm"]), zero)
    sc = max(f(tables["start_cold"]), zero)
    st = max(f(tables["store_cloud"]), zero)
    occ_w = (upld + sw) + comp
    occ_c = (upld + sc) + comp
    pr = cfg["pricing"]
    q = f(pr["quantum_ms"])
    billed = np.ceil(np.maximum(np.round(comp), f(1.0)) / q) * q
    gb = np.array([m / f(1024.0) for m in mems], f)
    cost = ((billed / f(1000.0)) * gb[None, :]) * f(pr["gb_second_rate"])
    e0, e1 = (f(v) for v in tables["edge_comp"])
    iot = max(f(tables["iotup"]), zero)
    est = max(f(tables["store_edge"]), zero)
    ecomp, elat = [], []
    for speed in cfg["edge_fleet"].values():
        c = e0 + size * e1
        if float(speed) != 1.0:
            c = c * f(1.0 / float(speed))
        c = np.maximum(c, zero)
        ecomp.append(c)
        elat.append((c + iot) + est)
    return {"occ_w": occ_w, "occ_c": occ_c, "lat_w": occ_w + st,
            "lat_c": occ_c + st, "cost": cost,
            "ecomp": np.stack(ecomp, axis=1), "elat": np.stack(elat, axis=1)}


def _rows(a: np.ndarray, lo: int, hi: int, dtype):
    """Rows ``lo:hi`` as Python scalars of the reference's precision."""
    if dtype is np.float64:
        return a[lo:hi].tolist()
    return [list(r) if np.ndim(r) else r for r in a[lo:hi]]


def place(cfg: dict, tables: dict, arrival, size, nbytes,
          dtype=np.float64) -> dict:
    """The sequential decision engine over one stream, task by task."""
    f = dtype
    n = len(arrival)
    P = predict(cfg, tables, size, nbytes, dtype)
    C = len(cfg["memory_configs_mb"])
    D = len(cfg["edge_fleet"])
    pol = cfg["policy"]
    minlat = pol["kind"] == "min_latency"
    if not minlat and pol["kind"] != "min_cost":
        raise ValueError(f"unknown policy {pol['kind']!r}")
    c_max = f(pol.get("c_max", 0.0))
    alpha = f(pol.get("alpha", 0.0))
    deadline = f(pol.get("deadline_ms", 0.0))
    t_idl = f(cfg["predicted_t_idl_ms"])
    zero = f(0.0)
    nows_all = np.asarray(arrival, f)
    # container pools: completion times in ascending order with their
    # expiry (completion + T_idl), one pool per config
    comp_t = [[] for _ in range(C)]
    exp_t = [[] for _ in range(C)]
    h = [zero] * D
    surplus = zero
    code = np.empty(n, np.int64)
    lat_o = np.empty(n, f)
    cost_o = np.empty(n, f)
    cold_o = np.zeros(n, bool)
    feas_o = np.ones(n, bool)
    allowed_o = np.full(n, np.inf, f)
    for lo in range(0, n, BLOCK):
        hi = min(n, lo + BLOCK)
        nows = _rows(nows_all, lo, hi, f)
        lw, lc = _rows(P["lat_w"], lo, hi, f), _rows(P["lat_c"], lo, hi, f)
        ow, oc = _rows(P["occ_w"], lo, hi, f), _rows(P["occ_c"], lo, hi, f)
        co = _rows(P["cost"], lo, hi, f)
        ec, el = _rows(P["ecomp"], lo, hi, f), _rows(P["elat"], lo, hi, f)
        for k in range(hi - lo):
            now = nows[k]
            # nominated edge device: least predicted wait, first on ties
            dn, wn = 0, max(h[0] - now, zero)
            for d in range(1, D):
                w = max(h[d] - now, zero)
                if w < wn:
                    dn, wn = d, w
            e_lat = wn + el[k][dn]
            row_c = co[k]
            best, best_lat, best_cost, best_cold = -1, None, None, False
            allowed = c_max + alpha * surplus if minlat else None
            for m in range(C):
                cost = row_c[m]
                if minlat and not cost <= allowed:
                    continue
                cs = comp_t[m]
                j = bisect.bisect_right(cs, now)
                warm = j > 0 and now <= exp_t[m][j - 1]
                lat = lw[k][m] if warm else lc[k][m]
                if minlat:
                    better = best < 0 or lat < best_lat or (
                        lat == best_lat and cost < best_cost)
                else:
                    if not lat <= deadline:
                        continue
                    better = best < 0 or cost < best_cost or (
                        cost == best_cost and lat < best_lat)
                if better:
                    best, best_lat, best_cost, best_cold = m, lat, cost, not warm
            # the nominated device comes last in the policy's view; cost 0
            if minlat:
                take_edge = zero <= allowed and (
                    best < 0 or e_lat < best_lat
                    or (e_lat == best_lat and zero < best_cost))
                feasible = True
            else:
                e_ok = e_lat <= deadline
                take_edge = e_ok and (
                    best < 0 or zero < best_cost
                    or (zero == best_cost and e_lat < best_lat))
                feasible = best >= 0 or e_ok
                if not feasible:
                    take_edge = True
            i = lo + k
            if minlat:
                allowed_o[i] = allowed
            if take_edge:
                code[i] = C + dn
                lat_o[i] = e_lat
                cost_o[i] = zero
                if minlat:
                    surplus = surplus + (c_max - zero)
                h[dn] = max(h[dn], now) + ec[k][dn]
            else:
                m = best
                code[i] = m
                lat_o[i] = best_lat
                cost_o[i] = best_cost
                cold_o[i] = best_cold
                if minlat:
                    surplus = surplus + (c_max - best_cost)
                occ = oc[k][m] if best_cold else ow[k][m]
                done = now + occ
                cs, es = comp_t[m], exp_t[m]
                if not best_cold:
                    j = bisect.bisect_right(cs, now) - 1
                    del cs[j], es[j]
                # drop containers expired by now: they are never warm again
                # (arrivals do not go back in time)
                x = 0
                while x < len(cs) and cs[x] <= now and now > es[x]:
                    x += 1
                if x:
                    del cs[:x], es[:x]
                j = bisect.bisect_right(cs, done)
                cs.insert(j, done)
                es.insert(j, done + t_idl)
            feas_o[i] = feasible
    return {"code": code, "pred_latency": lat_o, "pred_cost": cost_o,
            "pred_cold": cold_o, "feasible": feas_o, "allowed": allowed_o}


def execute(cfg: dict, seed: int, arrival, size, nbytes, code,
            dtype=np.float64) -> dict:
    """The twin's ground truth for the dispatches ``code`` (target index per
    task), in stream order."""
    f = dtype
    spec = cfg["app_spec"]
    C = len(cfg["memory_configs_mb"])
    fleet = list(cfg["edge_fleet"].items())
    n = len(arrival)
    nows = np.asarray(arrival, np.float64)
    scaled = _scaled(spec, np.asarray(size, np.float64))
    nbytes = np.asarray(nbytes, np.float64)
    lat = np.empty(n, f)
    cost = np.zeros(n, f)
    cold = np.zeros(n, bool)
    done = np.empty(n, f)
    # ---- cloud: four leg streams plus the container lifetimes ----------
    ci = np.nonzero(code < C)[0]
    nc = ci.shape[0]
    rng = [np.random.default_rng([seed, 7, i]) for i in range(CLOUD_LEGS)]
    mem = np.asarray(cfg["memory_configs_mb"], np.float64)[code[ci]]
    upld = (spec["upld_base_ms"] + nbytes[ci] * spec["upld_ms_per_byte"]) \
        * rng[0].lognormal(0.0, spec["upld_sigma"], nc)
    zs = rng[1].standard_normal(nc)
    share = np.minimum(mem, FULL_VCPU_MB) / FULL_VCPU_MB
    comp = (spec["c0_ms"] + spec["c1_ms"] * scaled[ci]) / share \
        * rng[2].lognormal(0.0, spec["comp_sigma"], nc)
    store = np.maximum(rng[3].normal(spec["store_cloud_mean"],
                                     spec["store_cloud_std"], nc), 1.0)
    life = cfg["actual_t_idl_ms"]
    t_idl = np.maximum(life["mean"] + life["std"]
                       * np.random.default_rng(seed).standard_normal(nc),
                       life["min"])
    warm_s = np.maximum(spec["warm_mean"] + spec["warm_std"] * zs, 1.0)
    cold_s = np.maximum(spec["cold_mean"] + spec["cold_std"] * zs, 1.0)
    pr = cfg["pricing"]
    q = f(pr["quantum_ms"])
    compf = comp.astype(f)
    billed = np.ceil(np.maximum(np.round(compf), f(1.0)) / q) * q
    cost[ci] = ((billed / f(1000.0)) * (mem.astype(f) / f(1024.0))) \
        * f(pr["gb_second_rate"])
    args = [a.astype(f) for a in (nows[ci], upld, warm_s, cold_s, compf,
                                  store, t_idl)]
    cols = [_rows(a, 0, nc, f) for a in args]
    pools: dict[int, list] = {}
    for j in range(nc):
        now, up, ws, cs_, cp, sto, tl = (c[j] for c in cols)
        pool = pools.setdefault(int(code[ci[j]]), [])
        t = now + up
        best, best_last = -1, None
        keep = []
        for c in pool:           # c = [busy_until, last_completion, expiry]
            if c[0] <= t and t > c[2]:
                continue         # expired idle container: reaped
            if c[0] <= t and (best < 0 or c[1] > best_last):
                best, best_last = len(keep), c[1]
            keep.append(c)
        st = ws if best >= 0 else cs_
        end = t + (st + cp)
        if best >= 0:
            keep[best] = [end, end, end + tl]
        else:
            keep.append([end, end, end + tl])
            cold[ci[j]] = True
        pools[int(code[ci[j]])] = keep
        lat[ci[j]] = ((up + st) + cp) + sto
        done[ci[j]] = now + lat[ci[j]]
    # ---- edge: per-device leg streams and a single-slot FIFO -------------
    for d, (name, speed) in enumerate(fleet):
        di = np.nonzero(code == C + d)[0]
        nd = di.shape[0]
        key = zlib.crc32(name.encode("utf-8"))
        r = [np.random.default_rng([seed, key, i]) for i in range(3)]
        ecomp = (spec["e0_ms"] + spec["e1_ms"] * scaled[di]) \
            * r[0].lognormal(0.0, spec["edge_sigma"], nd) / float(speed)
        if spec["iotup_mean"] > 0:
            iot = np.maximum(r[1].normal(spec["iotup_mean"],
                                         spec["iotup_std"], nd), 0.0)
        else:
            iot = np.zeros(nd)
        est = np.maximum(r[2].normal(spec["store_edge_mean"],
                                     spec["store_edge_std"], nd), 1.0)
        cols = [_rows(a.astype(f), 0, nd, f)
                for a in (nows[di], ecomp, iot, est)]
        free = f(0.0)
        for j in range(nd):
            now, cp, io, sto = (c[j] for c in cols)
            start = max(free, now)
            free = start + cp
            lat[di[j]] = (((start - now) + cp) + io) + sto
            done[di[j]] = now + lat[di[j]]
    return {"actual_latency": lat, "actual_cost": cost, "actual_cold": cold,
            "completion": done}


def serve(cfg: dict, tables: dict, seed: int, arrival, size, nbytes,
          dtype=np.float64) -> dict:
    """Placement and execution of the whole stream."""
    out = place(cfg, tables, arrival, size, nbytes, dtype)
    out.update(execute(cfg, seed, arrival, size, nbytes, out["code"], dtype))
    return out
