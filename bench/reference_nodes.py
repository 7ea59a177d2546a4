"""Plain NumPy reference of the prediction service's node-aware HPL run.

A copy of ``reference.py``'s recurrence that adds what a node holding
several ranks changes: ``R`` ranks per node and HPL.dat's process
mapping ``pmap``, ``"col"`` (rank(p, q) = q*P + p) or ``"row"``
(rank(p, q) = p*Q + q); node(rank) = rank // R.  It imports nothing of
the program under test, and works in absolute grid coordinates from the
node of every rank:

* a broadcast hop from column c to c+1 (mod Q) of a row is intra-node
  when both ranks share a node: it costs the latency ``alpha0`` = MPI
  overhead + fabric base latency, what the DES charges a message whose
  route is empty, with no bandwidth term; a hop that
  leaves the node costs net_latency plus the panel's bytes over the link,
  shared by every rank of the sending node whose hop leaves it at the
  same column;
* a swap round (p -> p+1 within a column) costs the largest of its
  pairs: an intra-node pair ``alpha0``, an inter-node pair net_latency
  plus its bytes over the link, shared by the ranks of the node that
  cross at the same row;
* the pivot allreduce, the update and the lookahead are ``reference``'s.

Only mappings under which every node holds a whole block of the grid
are answered: ``"col"`` with R | P or P | R, ``"row"`` with R | Q or
Q | R; any other raises ``ValueError``.  With R = 1 every line is
``reference.hpl_times``'s, so the answers are bitwise its answers.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

from reference import SCALE_FIELDS, _numroc, hpl_flops, scaled  # noqa: F401
from reference import rank_params as _rank_params

PMAPS = ("col", "row")


def rank_params(plat: dict) -> Dict[str, float]:
    """``reference.rank_params`` and the latency of a message between two
    ranks of one node, ``intra_latency``."""
    prm = _rank_params(plat)
    prm["intra_latency"] = (plat["mpi"]["overhead"]
                            + plat["fabric"]["base_latency"])
    prm.update({k: float(v) for k, v in plat.get("calibration", ())
                if k == "intra_latency"})
    return prm


def check_mapping(R: int, pmap: str, P: int, Q: int) -> None:
    """Raise unless every node of ``R`` ranks holds a block of the grid."""
    if pmap not in PMAPS:
        raise ValueError(f"pmap {pmap!r} is not one of {PMAPS}")
    if R == 1:
        return
    n = P if pmap == "col" else Q
    if n % R and R % n:
        raise ValueError(f"{R} ranks per node under pmap={pmap!r} on a "
                         f"{P} x {Q} grid: nodes do not hold blocks")


def node_map(R: int, pmap: str, P: int, Q: int) -> np.ndarray:
    """(P, Q) node of each grid position."""
    p = np.arange(P)[:, None]
    q = np.arange(Q)[None, :]
    rank = q * P + p if pmap == "col" else p * Q + q
    return rank // R


def _crossings(node: np.ndarray, axis: int) -> Tuple[np.ndarray,
                                                      np.ndarray]:
    """Hops to the next position along ``axis`` (cyclic): whether the hop
    into each position leaves the sender's node, and how many ranks of
    that node leave it at the same step.  For ``axis=1`` (broadcast) the
    arrays are indexed by the receiving column; for ``axis=0`` (swap) by
    the sending row."""
    if axis == 1:
        src = np.roll(node, 1, axis=1)            # sender of the hop into q
        dst = node
    else:
        src = node
        dst = np.roll(node, -1, axis=0)           # p sends to p + 1
    cross = src != dst
    share = np.zeros(node.shape, np.int64)
    other = 0 if axis == 1 else 1
    for i in range(node.shape[other]):
        # ranks of the same node crossing at the same step
        s = np.take(src, [i], axis=other)
        c = np.take(cross, [i], axis=other)
        share += (src == s) & c
    return cross, share


def hpl_times(N, nb, P: int, Q: int, prm: Dict[str, Sequence[float]],
              dtype=np.float64, R: int = 1, pmap: str = "col"
              ) -> np.ndarray:
    """Simulated HPL wall time of each lane: (B,) for (B,) ``N``, ``nb``
    and parameter vectors, all on one P x Q grid of ``R`` ranks a node
    under ``pmap``."""
    if P < 2 or Q < 2:
        raise ValueError(f"reference covers grids of at least 2 x 2, "
                         f"not {P} x {Q}")
    check_mapping(R, pmap, P, Q)
    f = np.dtype(dtype).type
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
    if R > 1:
        alpha0 = v["intra_latency"]
        node = node_map(R, pmap, P, Q)
        b_cross, b_share = _crossings(node, 1)           # (P, Q)
        s_cross, s_share = _crossings(node, 0)
        b_share = b_share.astype(f)[None]
        s_share = s_share.astype(f)[None]

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
        if R == 1:
            hop = alpha + f(8.0) * (mloc + wf) * wf / bcast_bw    # (B, P)
            hi = hop[:, :, None] * ring
        else:
            # each hop's cost, by the column it reaches (B, P, Q), then
            # summed along the ring from the root
            pb = f(8.0) * (mloc + wf) * wf                        # (B, P)
            cost = np.where(b_cross[None],
                            alpha[:, :, None] + pb[:, :, None] * b_share
                            / bcast_bw[:, :, None],
                            alpha0[:, :, None])
            cost = np.roll(cost, -root, axis=2)
            cost[:, :, 0] = f(0.0)
            hi = np.cumsum(cost, axis=2, dtype=f)
        d = np.roll(T, -root, axis=2)
        d -= hi
        d[:, :, 0] = F
        arrival = np.maximum.accumulate(d, axis=2)
        arrival += hi
        arrival[:, :, 0] = F
        arrival = np.roll(arrival, root, axis=2)

        # row swaps, synchronised on each column's latest rank
        u_bytes = f(8.0) * wf * nloc
        if R == 1:
            swap = np.where(u_bytes > 0,
                            sw_rounds * (alpha + (u_bytes / sw_rounds)
                                         / swap_bw)
                            + (f(32.0) * wf * nloc) / mem_bw, f(0.0))
        else:
            # a round lasts as long as its slowest pair (B, P, Q)
            pair = np.where(s_cross[None],
                            alpha[:, :, None]
                            + (u_bytes / sw_rounds)[:, None, :] * s_share
                            / swap_bw[:, :, None],
                            alpha0[:, :, None])
            swap = np.where(u_bytes > 0,
                            sw_rounds * pair.max(axis=1)
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


def mapping(plat: dict) -> Tuple[int, str]:
    """(ranks per node, process mapping) of a platform record."""
    return (int(plat["scale"]["ranks_per_node"]),
            plat["mpi"].get("pmap", "col"))


def published_times(plats: Sequence[dict], dtype=np.float64) -> np.ndarray:
    """Each platform record's published HPL run: simulated seconds."""
    out = np.empty(len(plats), np.float64)
    groups: Dict[Tuple[int, int, int, str], List[int]] = {}
    for i, p in enumerate(plats):
        groups.setdefault(tuple(p["scale"]["grid"]) + mapping(p),
                          []).append(i)
    for (P, Q, R, pmap), idx in groups.items():
        sel = [plats[i] for i in idx]
        prms = [rank_params(p) for p in sel]
        out[idx] = hpl_times([p["scale"]["hpl_n"] for p in sel],
                             [p["scale"]["hpl_nb"] for p in sel], P, Q,
                             {k: [q[k] for q in prms] for k in prms[0]},
                             dtype, R, pmap)
    return out
