"""Plain NumPy reference of what the prediction service answers.

It imports nothing of the program under test.  It works from a
configuration file, either platform records (the JSON form of
``repro.platforms.Platform``) or TOP500 list rows with the inference
rules that turn a row into a record, and the what-if scales the
traffic drew, and follows the semantics the service documents:

* a platform record gives the per-rank simulator parameters (peak,
  GEMM efficiency, memory bandwidth, BLAS overhead, link bandwidth,
  message latency, calibration overrides);
* the HPL run is the max-plus panel recurrence over the P x Q grid:
  panel factorization on the owning column, a 1-ring broadcast along
  each process row, row swaps synchronised on the column maximum, the
  trailing update, and a one-deep lookahead;
* the fleet product sizes each machine's run by the memory rule on a
  proxy grid of at most ``max_ranks`` ranks, scales the proxy's rate to
  the whole machine, and calibrates one efficiency factor per fabric
  family on a stratified train split.

The recurrence is written in absolute grid coordinates, one panel at a
time, with no shape padding; lanes that share a (P, Q) grid are carried
side by side and each stops at its own panel count.  ``dtype`` sets the
floating-point precision of every operation, so the same code serves as
the float32 control.
"""
from __future__ import annotations

import math
import re
import statistics
from typing import Dict, List, Sequence, Tuple

import numpy as np

#: what-if knob -> (section, field) of a platform record
SCALE_FIELDS = {
    "link_bw": ("fabric", "link_bw"),
    "gemm_eff": ("node", "gemm_efficiency"),
    "mem_bw": ("node", "mem_bw"),
    "net_latency": ("mpi", "net_latency"),
}


def scaled(plat: dict, scales: Dict[str, float]) -> dict:
    """A copy of a platform record with each knob's field multiplied."""
    out = {k: (dict(v) if isinstance(v, dict) else v)
           for k, v in plat.items()}
    for knob, s in scales.items():
        section, field = SCALE_FIELDS[knob]
        if out[section][field] is None:
            raise ValueError(f"{plat['name']}: {section}.{field} is unset, "
                             f"so {knob} cannot be scaled")
        out[section][field] = out[section][field] * s
    return out


def rank_params(plat: dict) -> Dict[str, float]:
    """Per-rank simulator parameters of one platform record."""
    node, fab, mpi = plat["node"], plat["fabric"], plat["mpi"]
    rpn = plat["scale"]["ranks_per_node"]
    lat = mpi["net_latency"]
    if lat is None:         # software overhead + base latency + two hops
        lat = mpi["overhead"] + fab["base_latency"] + 2.0 * fab["hop_latency"]
    prm = {"peak_flops": node["peak_flops"] / rpn,
           "gemm_eff": node["gemm_efficiency"],
           "mem_bw": node["mem_bw"] * node["mem_efficiency"] / rpn,
           "theta": node["blas_latency"],
           "link_bw": fab["link_bw"],
           "net_latency": lat,
           "bcast_bw_scale": 1.0, "swap_bw_scale": 1.0, "lookahead": 1.0}
    prm.update({k: float(v) for k, v in plat.get("calibration", ())})
    return prm


def hpl_flops(N: int) -> float:
    return (2.0 / 3.0) * N ** 3 + 1.5 * N ** 2


def _numroc(rem, nb, shift: int, nprocs: int):
    """Rows (or columns) of a trailing matrix of ``rem`` rows held by each
    of ``nprocs`` processes, block-cyclic in ``nb`` from process
    ``shift``: (B, nprocs) int64 for (B, 1) ``rem`` and ``nb``."""
    ip = (np.arange(nprocs)[None, :] - shift) % nprocs
    nblocks = rem // nb
    base = (nblocks // nprocs) * nb
    extra = nblocks % nprocs
    return base + np.where(ip < extra, nb,
                           np.where(ip == extra, rem % nb, 0))


def hpl_times(N, nb, P: int, Q: int, prm: Dict[str, Sequence[float]],
              dtype=np.float64) -> np.ndarray:
    """Simulated HPL wall time of each lane: (B,) for (B,) ``N``, ``nb``
    and parameter vectors, all on one P x Q grid."""
    if P < 2 or Q < 2:
        raise ValueError(f"reference covers grids of at least 2 x 2, "
                         f"not {P} x {Q}")
    f = np.dtype(dtype).type
    # every per-lane quantity is a (B, 1) column; grids are (B, P, Q)
    N = np.asarray(N, np.int64)[:, None]
    nb = np.asarray(nb, np.int64)[:, None]
    v = {k: np.asarray(x, np.float64).astype(f)[:, None]
         for k, x in prm.items()}
    peak = v["peak_flops"] * v["gemm_eff"]
    mem_bw, theta, alpha = v["mem_bw"], v["theta"], v["net_latency"]
    bcast_bw = v["link_bw"] * v["bcast_bw_scale"]
    swap_bw = v["link_bw"] * v["swap_bw_scale"]
    lookahead = v["lookahead"]
    ar_lat = f(2.0 * math.ceil(math.log2(P))) * alpha    # allreduce rounds
    sw_rounds = f(max(math.ceil(math.log2(P)), 1))       # swap rounds
    n_panels = (N + nb - 1) // nb
    ring = np.arange(Q).astype(f)[None, None, :]         # ring distance

    def width(rem):
        return np.clip(np.minimum(nb, rem), 0, None)

    def fact_time(k):
        """Factorization of panel k on each row rank of its column."""
        rem = N - k * nb
        wf = width(rem).astype(f)
        mloc = _numroc(rem, nb, k % P, P).astype(f)
        pf_bytes = f(8.0) * (np.maximum(mloc * wf * wf - wf * wf * wf
                                        / f(3.0), f(0.0))
                             + f(3.0) * mloc * wf)
        return pf_bytes / mem_bw + wf * (f(3.0) * theta) + wf * ar_lat

    T = np.zeros((N.shape[0], P, Q), f)      # time each rank finishes
    F = fact_time(0)                         # (B, P): panel k is ready
    for k in range(int(n_panels.max())):
        rem = N - k * nb
        w = width(rem)
        wf = w.astype(f)
        mloc = _numroc(rem, nb, k % P, P).astype(f)               # (B, P)
        nloc = _numroc(np.maximum(rem - w, 0), nb, (k + 1) % Q,
                       Q).astype(f)                               # (B, Q)
        root = k % Q

        # broadcast along each row, store and forward from the root
        hop = alpha + f(8.0) * (mloc + wf) * wf / bcast_bw        # (B, P)
        hi = hop[:, :, None] * ring
        d = np.roll(T, -root, axis=2)
        d -= hi
        d[:, :, 0] = F
        arrival = np.maximum.accumulate(d, axis=2)
        arrival += hi
        arrival[:, :, 0] = F
        arrival = np.roll(arrival, root, axis=2)

        # row swaps, synchronised on each column's latest rank
        u_bytes = f(8.0) * wf * nloc
        swap = np.where(u_bytes > 0,
                        sw_rounds * (alpha + (u_bytes / sw_rounds)
                                     / swap_bw)
                        + (f(32.0) * wf * nloc) / mem_bw, f(0.0))
        np.maximum(arrival, T, out=arrival)
        after_swap = arrival.max(axis=1) + swap                    # (B, Q)

        # trailing update: triangular solve on U, then the GEMM
        trsm = (wf * wf * nloc) / peak + theta                    # (B, Q)
        mn = (f(2.0) * mloc)[:, :, None] * nloc[:, None, :]
        T_new = mn * wf[:, :, None]
        T_new += mn
        T_new /= peak[:, :, None]
        T_new += theta[:, :, None]
        T_new += (after_swap + trsm)[:, None, :]

        # lookahead: the next panel's column factors it right after
        # updating just those columns, unless finishing serially is sooner
        nxt = (k + 1) % Q
        mloc_n = _numroc(np.maximum(rem - nb, 0), nb, (k + 1) % P,
                         P).astype(f)
        gemm_nb = (f(2.0) * mloc_n * width(rem - nb).astype(f) * wf) \
            / peak + theta                                        # (B, P)
        ft = fact_time(k + 1)
        serial = T_new[:, :, nxt] + ft
        overlap = after_swap[:, nxt:nxt + 1] + gemm_nb + ft
        F_new = (lookahead * np.minimum(overlap, serial)
                 + (f(1.0) - lookahead) * serial)

        live = k < n_panels                                       # (B, 1)
        if live.all():
            T, F = T_new, F_new
        else:
            T = np.where(live[:, :, None], T_new, T)
            F = np.where(live, F_new, F)

    Nf, nbf = N.astype(f), nb.astype(f)
    return (T.max(axis=(1, 2))
            + (f(2.0) * Nf * Nf / (peak * f(P) * f(Q)))[:, 0]
            + (Nf / nbf * alpha)[:, 0])


def published_times(plats: Sequence[dict], dtype=np.float64) -> np.ndarray:
    """Each platform record's published HPL run: simulated seconds."""
    out = np.empty(len(plats), np.float64)
    groups: Dict[Tuple[int, int], List[int]] = {}
    for i, p in enumerate(plats):
        groups.setdefault(tuple(p["scale"]["grid"]), []).append(i)
    for (P, Q), idx in groups.items():
        sel = [plats[i] for i in idx]
        prms = [rank_params(p) for p in sel]
        out[idx] = hpl_times([p["scale"]["hpl_n"] for p in sel],
                             [p["scale"]["hpl_nb"] for p in sel], P, Q,
                             {k: [q[k] for q in prms] for k in prms[0]},
                             dtype)
    return out


# ------------------------------------------------------------ the fleet
def memory_sized_n(n_nodes: int, hbm_bytes: float, nb: int,
                   mem_fraction: float) -> int:
    """Largest multiple of nb with 8 N^2 within mem_fraction of memory."""
    n = math.sqrt(mem_fraction * n_nodes * hbm_bytes / 8.0)
    return max(int(n) // nb * nb, nb)


def _first_match(rules: Sequence[dict], text: str) -> dict:
    return next(r for r in rules
                if re.search(r["pattern"], text, re.IGNORECASE))


def infer(row: dict, rules: dict) -> dict:
    """The platform record of one TOP500 list row, by the configuration's
    inference rules: the CPU family from the processor string (cores and
    clock parsed from it, the family's values where it lacks them); the
    node's nominal peak taken from Rpeak where the row is accelerated or
    the family's figure misses Rpeak by more than the tolerance; the
    sustained-clock derate; memory per core, with a bandwidth floor per
    accelerator flop; the fabric family from the interconnect string; a
    near-square grid of one rank per node; the published Nmax, else the
    memory rule."""
    cpu = _first_match(rules["cpu_families"], row["processor"])
    m = re.search(r"(\d+)\s*C\b", row["processor"], re.IGNORECASE)
    cores_per_socket = int(m.group(1)) if m else cpu["default_cores"]
    m = re.search(r"([\d.]+)\s*GHz", row["processor"], re.IGNORECASE)
    ghz = float(m.group(1)) if m else cpu["default_ghz"]
    cores = cpu["sockets_per_node"] * cores_per_socket
    n_nodes = max(max(row["cores"] - row["accel_cores"], 0)
                  // max(cores, 1), 1)

    per_core = cpu["flops_per_cycle"] * ghz * 1e9
    node_peak = per_core * cores
    rpeak_node = row["rpeak_tflops"] * 1e12 / n_nodes
    accelerated = row["accel_cores"] > 0 or bool(row["accelerator"])
    if accelerated or abs(node_peak - rpeak_node) \
            > rules["rpeak_tolerance"] * rpeak_node:
        node_peak = rpeak_node
    mem_bw = cpu["mem_bw_core_gbs"] * 1e9 * cores
    hbm = cpu["mem_core_gb"] * 1e9 * cores
    if accelerated:
        accel = max(node_peak - per_core * cores, 0.0)
        mem_bw = max(mem_bw, rules["accel_bytes_per_flop"] * accel)
    sustained = rules["accel_sustained_frac"] if accelerated \
        else cpu["sustained_frac"]

    fabric = _first_match(rules["fabric_families"], row["interconnect"])
    P = next(p for p in range(math.isqrt(n_nodes), 0, -1)
             if n_nodes % p == 0)
    nb = rules["hpl_nb"]
    return {
        "name": f"r{row['rank']:03d}",
        "node": {"peak_flops": node_peak * sustained, "mem_bw": mem_bw,
                 "gemm_efficiency": rules["gemm_efficiency"],
                 "mem_efficiency": rules["mem_efficiency"],
                 "blas_latency": rules["accel_blas_latency" if accelerated
                                       else "blas_latency"],
                 "hbm_bytes": hbm},
        "fabric": {"link_bw": fabric["link_bw"]},
        "mpi": {"net_latency": rules["net_latency"]},
        "scale": {"n_nodes": n_nodes, "ranks_per_node": 1,
                  "grid": [P, n_nodes // P],
                  "hpl_n": row["nmax"] or memory_sized_n(
                      n_nodes, hbm, nb, rules["mem_fraction"]),
                  "hpl_nb": nb, "reported_tflops": row["rmax_tflops"]},
        "provenance": [["fabric_group", fabric["family"]]],
    }


def tune(plat: dict, tuning: dict) -> Tuple[int, int, int, int, float]:
    """(N, nb, P, Q, scale): the memory-rule run on the proxy grid, and
    how many proxies make the whole machine."""
    sc = plat["scale"]
    r = min(sc["n_nodes"] * sc["ranks_per_node"], tuning["max_ranks"])
    P = math.isqrt(r)
    Q = r // P
    proxy_nodes = max(P * Q // sc["ranks_per_node"], 1)
    hbm = plat["node"]["hbm_bytes"]
    nb = tuning["nb_min"]
    N = memory_sized_n(proxy_nodes, hbm, nb, tuning["mem_fraction"])
    if (N + nb - 1) // nb > tuning["panels_cap"]:
        nb = -(-N // (tuning["panels_cap"] * tuning["nb_step"])) \
            * tuning["nb_step"]
        N = memory_sized_n(proxy_nodes, hbm, nb, tuning["mem_fraction"])
    return N, nb, P, Q, sc["n_nodes"] / proxy_nodes


def fabric_family(plat: dict) -> str:
    return dict(plat.get("provenance", ())).get("fabric_group", "unknown")


def fleet(plats: Sequence[dict], tuning: dict, dtype=np.float64,
          map_=map, chunk: int = 12) -> dict:
    """Raw and calibrated Rmax (TFLOP/s) of every machine, and the median
    absolute error of the held-out machines after calibration.  Lanes of
    one grid run in chunks of at most ``chunk`` through ``map_``."""
    runs = [tune(p, tuning) for p in plats]
    groups: Dict[Tuple[int, int], List[int]] = {}
    for i, (_, _, P, Q, _) in enumerate(runs):
        groups.setdefault((P, Q), []).append(i)
    tasks = [(P, Q, idx[j:j + chunk]) for (P, Q), idx in groups.items()
             for j in range(0, len(idx), chunk)]
    args = []
    for P, Q, idx in tasks:
        prms = [rank_params(plats[i]) for i in idx]
        args.append(([runs[i][0] for i in idx], [runs[i][1] for i in idx],
                     P, Q, {k: [q[k] for q in prms] for k in prms[0]},
                     dtype))
    pred = np.empty(len(plats), np.float64)
    for (_, _, idx), t in zip(tasks, map_(hpl_times, *zip(*args))):
        for i, ti in zip(idx, t):
            pred[i] = hpl_flops(runs[i][0]) / float(ti) / 1e12 * runs[i][4]

    # stratified split: by family, largest published first, even train
    pub = [p["scale"]["reported_tflops"] for p in plats]
    fam = [fabric_family(p) for p in plats]
    split = [""] * len(plats)
    by_fam: Dict[str, List[int]] = {}
    for i in range(len(plats)):
        if pub[i] > 0:
            by_fam.setdefault(fam[i], []).append(i)
    for idx in by_fam.values():
        idx.sort(key=lambda i: -pub[i])
        for j, i in enumerate(idx):
            split[i] = "train" if j % 2 == 0 or len(idx) == 1 else "test"
    train = [i for i in range(len(plats)) if split[i] == "train"]
    ratios: Dict[str, List[float]] = {}
    for i in train:
        if pred[i] > 0:
            ratios.setdefault(fam[i], []).append(pub[i] / pred[i])
    factors = {f: statistics.median(r) for f, r in ratios.items()}
    overall = statistics.median([pub[i] / pred[i] for i in train
                                 if pred[i] > 0])
    cal = np.asarray([pred[i] * factors.get(fam[i], overall)
                      for i in range(len(plats))])
    test = [i for i in range(len(plats)) if split[i] == "test"]
    held = statistics.median([abs(cal[i] - pub[i]) / pub[i]
                              for i in test]) if test else float("nan")
    return {"predicted_tflops": pred, "calibrated_tflops": cal,
            "heldout_median_abs_err": held}
