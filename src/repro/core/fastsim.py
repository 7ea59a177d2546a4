"""fastsim — the HPL simulator itself as a JAX program (beyond-paper).

The paper's SystemC engine needs 4.8 h to simulate HPL on Frontera.  The
per-panel timing recurrence is a max-plus system over the P x Q grid:

  fact_k(p)        panel factorization on owning column (SimBLAS closed forms)
  arrival_k(p,q)   1-ring store&forward broadcast = prefix-max along the row
                   ring: a_i = H_i + cummax_j<=i (d_j - H_j), where H_i is
                   the cost of the first i hops from the root: hop*i with one
                   rank a node; with R ranks a node (``ranks_per_node``)
                   hop*n_inter(i) + alpha0*n_intra(i), a hop that leaves the
                   node shared by the node's ranks that forward with it, one
                   inside the node latency alone (see ``_sim_core``)
  T_{k+1}(p,q)     = max(T_k, arrival, colmax(arrival)) + swap + update

Everything is vectorized over the grid, whose process rows are held as
at most 9 row classes (NUMROC's case of a row in two panels running, see
``_sim_core``).  The panel loop runs in blocks
of up to 256 panels: a block first builds, as tables in one vectorised
pass, everything its panels need that does not depend on the timing
state (widths, NUMROC counts, broadcast hops, trsm, swap and
factorization times), then a serial loop steps only the state-dependent
max-plus chain.  Frontera's 24k panels x 8,008 ranks simulate in seconds
on a laptop-class CPU (cross-validated against the DES path in
tests/test_hpl_sim.py).

Beyond single runs, this module is a *batched sweep engine* (DESIGN.md
§11): ``(N, nb, P, Q)`` and every ``FastSimParams`` field are traced
values, array shapes are padded to a small set of buckets with masking,
and compiled programs live in an LRU cache keyed on the bucket.  Hardware
what-ifs (link_bw, gemm_eff, mem_bw, lookahead, ...) therefore never
recompile, and ``sweep_hpl`` runs a whole scenario grid as one program
with a trailing scenario axis (``jax.vmap`` only for mixed-geometry
sweeps).  Because parameters are traced,
``jax.grad``/``jax.value_and_grad`` flow through the full recurrence —
see ``calibrate.fit_fastsim_params`` for gradient-based calibration.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import Timer, get_global_metrics

from .apps.hpl import HPLConfig
from .hardware.node import NodeModel


@dataclasses.dataclass(frozen=True)
class FastSimParams:
    # node
    peak_flops: float            # per rank
    gemm_eff: float
    mem_bw: float                # per rank, effective
    theta: float                 # per-BLAS-call overhead
    # network
    link_bw: float               # per-NIC bytes/s
    net_latency: float           # per-message software+wire latency
    hop_latency: float = 90e-9
    bcast_bw_scale: float = 1.0  # contention scale on panel broadcast
    swap_bw_scale: float = 1.0   # contention scale on row swaps
    lookahead: float = 1.0       # HPL lookahead depth (1 = overlap panel)
    # ranks sharing one node and its NIC (the per-rank fields above are
    # already the rank's share); > 1 selects the node-aware recurrence
    ranks_per_node: int = 1
    # latency of a message between two ranks of one node: MPI overhead +
    # fabric base latency, what the DES charges; the node-aware recurrence
    # needs it
    intra_latency: Optional[float] = None

    @staticmethod
    def from_node(node: NodeModel, *, link_bw: float,
                  ranks_per_node: int = 1, net_latency: float = 2e-6,
                  intra_latency: Optional[float] = None,
                  **kw) -> "FastSimParams":
        return FastSimParams(
            peak_flops=node.peak_flops / ranks_per_node,
            gemm_eff=node.gemm_efficiency,
            mem_bw=node.mem_bw * node.mem_efficiency / ranks_per_node,
            theta=node.blas_latency,
            link_bw=link_bw, net_latency=net_latency,
            ranks_per_node=ranks_per_node, intra_latency=intra_latency,
            **kw)


#: fields that reach a program as arguments of their own (``_node_args``)
_NODE_FIELDS = ("ranks_per_node", "intra_latency")
#: the traced fields
_PARAM_FIELDS = tuple(f.name for f in dataclasses.fields(FastSimParams)
                      if f.name not in _NODE_FIELDS)

# Registered as a pytree: a FastSimParams passed to jit is *traced*, so
# changing any value reuses the compiled program (the old code passed a
# dict of Python floats baked in at trace time).
jax.tree_util.register_dataclass(
    FastSimParams, data_fields=list(_PARAM_FIELDS),
    meta_fields=list(_NODE_FIELDS))


def _f64_params(prm: FastSimParams) -> FastSimParams:
    """Normalize leaves to Python floats so the jit cache sees one dtype."""
    return dataclasses.replace(
        prm, **{n: float(getattr(prm, n)) for n in _PARAM_FIELDS})


def node_block(cfg: HPLConfig,
               ranks_per_node: int) -> Optional[Tuple[int, int]]:
    """(rows, columns) of the block of the P x Q grid that one node
    holds, or None for one rank per node (the node-blind recurrence).

    Node r // R holds R consecutive MPI ranks, which ``cfg.pmap`` lays
    out down grid columns ("col": rank q*P + p) or along grid rows
    ("row": rank p*Q + q).  The node-aware recurrence prices its hops by
    that block, so it answers only where every node holds one: "col"
    with R | P (R rows of one column) or P | R (R/P whole columns), "row"
    with R | Q or Q | R; any other mapping raises ``ValueError``, as does
    a broadcast other than "1ring" (the DES, ``HPLSim``, runs both)."""
    if cfg.bcast != "1ring":
        raise ValueError(f"the fast model runs the 1ring broadcast, not "
                         f"bcast={cfg.bcast!r}: use the DES (HPLSim)")
    R = int(ranks_per_node)
    if R < 1:
        raise ValueError(f"ranks_per_node={R} must be >= 1")
    if R == 1:
        return None
    P, Q = cfg.P, cfg.Q
    if cfg.pmap == "col":
        if P % R == 0:
            return R, 1
        if R % P == 0:
            return P, R // P
    else:
        if Q % R == 0:
            return 1, R
        if R % Q == 0:
            return R // Q, Q
    axis, n = ("P", P) if cfg.pmap == "col" else ("Q", Q)
    raise ValueError(
        f"{R} ranks per node under pmap={cfg.pmap!r} on a {P} x {Q} grid: "
        f"the fast model needs {axis}={n} and R to divide one another, so "
        f"that every node holds a block of the grid; the DES (HPLSim) "
        f"runs any mapping")


def _node_args(cfg: HPLConfig, prm: FastSimParams) -> tuple:
    """The node-aware program's own arguments for one scenario: the
    node's block of the grid (``node_block``) and the intra-node latency;
    () for one rank a node."""
    block = node_block(cfg, prm.ranks_per_node)
    if block is None:
        return ()
    if prm.intra_latency is None:
        raise ValueError(
            f"ranks_per_node={prm.ranks_per_node} needs intra_latency, the "
            f"latency of a message between two ranks of one node (MPI "
            f"overhead + fabric base latency, as the DES charges it)")
    return (np.int64(block[0]), np.int64(block[1]),
            np.float64(prm.intra_latency))


# ------------------------------------------------------------- bucketing
def _bucket(n: int) -> int:
    """Smallest b >= n of the form 2^k or 3*2^(k-1) (<= 1.5x padding)."""
    n = max(int(n), 1)
    p = 1 << (n - 1).bit_length()
    if p >= 4 and 3 * p // 4 >= n:
        return 3 * p // 4
    return p


def bucket_key(cfg: HPLConfig) -> Tuple[int, int, int]:
    """(n_panels_max, P_max, Q_max) compile-cache key for a config."""
    return (_bucket(cfg.n_panels), _bucket(cfg.P), _bucket(cfg.Q))


# ------------------------------------------------------------ traced core
# NUMROC deals a process row of a panel one of three cases: a whole extra
# block, the tail, or none.  The recurrence's state holds a slot per pair
# (case in panel k, case in panel k+1), slot 3 * first + second, in
# place of a row per process (``_sim_core``)
_CASES = 3
_SLOTS = _CASES * _CASES
# most panels per block of _sim_core (128, 256 and 512 ran Frontera's
# recurrence within 3% of each other on a TPU v5e)
_BLOCK = 256


@functools.partial(jax.custom_jvp, nondiff_argnums=(3,))
def _row_max(x, where, n_rows, axes):
    """Max of ``x`` along ``axes`` over the entries ``where`` marks, each
    slot standing for ``n_rows`` process rows (both broadcast against
    ``x``).  The derivative shares a tie among the tied rows, as the max
    over a row per process does: a slot's share is its rows'."""
    return jnp.max(x, axis=axes, where=where, initial=-jnp.inf)


@_row_max.defjvp
def _row_max_jvp(axes, primals, tangents):
    x, where, n_rows = primals
    ans = _row_max(x, where, n_rows, axes)
    hit = jnp.where(where & (x == jnp.expand_dims(ans, axes)), n_rows, 0.0)
    return ans, (jnp.sum(hit * tangents[0], axis=axes)
                 / jnp.sum(hit, axis=axes))


def _numroc(blocks, tail, nb, shift, nprocs, size):
    """Vectorized NUMROC for procs 0..size-1 of panels with ``blocks``
    whole ``nb``-blocks and ``tail`` columns left (K,), dealt from owner
    ``shift`` ((K,) or scalar, in [0, nprocs)): the local count of each
    case — a whole extra block, the tail, none — (K, 3), and the case of
    each proc (K, size)."""
    ip = jnp.arange(size) - jnp.reshape(shift, (-1, 1))
    ip = jnp.where(ip < 0, ip + nprocs, ip)  # (p - shift) % nprocs, p live
    per = blocks // nprocs
    extra = (blocks - per * nprocs)[:, None]
    counts = per[:, None] * nb + jnp.stack(
        [jnp.broadcast_to(nb, tail.shape), tail, jnp.zeros_like(tail)],
        axis=1)
    return counts, jnp.where(ip < extra, 0, jnp.where(ip == extra, 1, 2))


def _by_case(counts, case):
    """Each proc's entry of ``counts`` (K, 3) by its ``case`` (K, size),
    by selects: a gather, per lane in batch mode, is slow on the TPU."""
    return jnp.where(case == 0, counts[:, :1],
                     jnp.where(case == 1, counts[:, 1:2], counts[:, 2:]))


def _to_slots(x, second: bool):
    """Per case (K, 3) to per slot (K, S): slot 3 * a + b takes the
    entry of its pair's first case a, or of its ``second`` b."""
    y = x[:, None, :] if second else x[:, :, None]
    return jnp.broadcast_to(y, (x.shape[0], _CASES, _CASES)).reshape(
        x.shape[0], _SLOTS)


def _geometry(n_blocks, n_tail, nb, P, P_max, ks):
    """Per panel of ``ks`` (K,) of a matrix of ``n_blocks`` whole
    ``nb``-blocks and ``n_tail`` trailing columns on ``P`` process rows:
    whole blocks and tail left (K,), width (K,), each row case's local
    rows (K, 3) and the case of each process row 0..P_max-1 (K, P_max).
    Panel k has max(n_blocks - k, 0) whole blocks and the tail (while
    k <= n_blocks) left, so the tables divide only by P."""
    blocks = jnp.maximum(n_blocks - ks, 0)
    tail = jnp.where(ks <= n_blocks, n_tail, 0)
    # nb except on the trailing partial panel, 0 past the live panels
    width = jnp.where(blocks > 0, nb, tail)
    owner = ks - ks // P * P
    rows, case = _numroc(blocks, tail, nb, owner, P, P_max)
    return (blocks, tail, width.astype(jnp.float64),
            rows.astype(jnp.float64), case)


def _slot_rows(case, P):
    """How many live process rows (p < ``P``) each slot of the state
    between panels k and k+1 stands for, given each process row's case
    in panels k .. k+K (K+1, P_max): (K, S)."""
    pair = _CASES * case[:-1] + case[1:]                     # (K, P_max)
    row_on = jnp.arange(case.shape[1]) < P
    return jnp.sum((pair[:, :, None] == jnp.arange(_SLOTS))
                   & row_on[None, :, None], axis=1).astype(jnp.float64)


def _block_size(n_panels_max: int) -> int:
    """Panels per block for a panel bucket: the whole bucket up to
    ``_BLOCK`` panels, else the largest power of two <= ``_BLOCK`` that
    divides it (buckets are 2^k or 3*2^(k-1): 256, or 128 at 384)."""
    if n_panels_max <= _BLOCK:
        return n_panels_max
    return math.gcd(n_panels_max, _BLOCK)


def _sim_core(N, nb, P, Q, prm: FastSimParams,
              n_panels_max: int, P_max: int, Q_max: int, *nodes):
    """HPL panel recurrence with *traced* (N, nb, P, Q, prm).

    Shapes are the static bucket (P_max, Q_max) and the loop runs
    n_panels_max panels; rows p >= P, columns q >= Q and panels k >=
    ceil(N/nb) are padding, masked so they never touch live lanes (padding
    rows fill no row class, the ring-broadcast permutation maps padding
    columns to themselves, the column-sync max and the final max are
    mask-reduced, and the loop carry freezes once k reaches the live
    panel count).

    The panels run in blocks of C = ``_block_size(n_panels_max)``.  Each
    block first builds, in one vectorised pass (named scope
    ``hpl.tables``), everything its C panels need that does not read the
    carry: widths, NUMROC row and column counts, the rows each row class
    stands for, broadcast hops, trsm, swap and next-panel factorization
    times, the lookahead gemm and the live flag, each a table with a
    leading axis of C.  A serial loop of C steps then runs only the
    chain that reads the carry ``(T, fact_done)``: ring arrival (prefix
    max), the update's gemm from the tables' row and column counts,
    column sync, the next panel's factorization anchor, the freeze and
    the ring re-base.  Every value is computed by the same float64
    operations in the same order as a single per-panel step would.

    The state holds a slot per row class, not a row per process.  Only
    the column max mixes process rows; every other value is row-local
    and reads its row p only through p's local rows, which NUMROC gives
    by one of three cases a panel (a whole extra block, the tail, none):
    the update's gemm reads panel k's, the next panel's factorization
    anchor panel k+1's, and the next step's broadcast hop panel k+1's.
    So after step k a row's ``(T, fact_done)`` is a function of the pair
    (case in panel k, case in panel k+1), and every row of one pair holds
    the same values: the state is (S, Q_max, B), S = 9 slots, slot
    3 * a + b for the pair (a, b).  A step starts from the slots of the
    pair (k-1, k), writes those of (k, k+1), and takes the column max
    over the slots some live row p < P fills (``n_rows`` > 0, a table of
    the pass); max is exact and idempotent, so the answer is the per-row
    program's, bitwise.  Panel 0 starts from the pair (-1, 0): zeros and
    the factorization of panel 0's case.  Frozen panels keep the slots of
    the last live panel, and the final max reads those.  The derivative
    of a max shares a tie among the tied process rows (``_row_max``), so
    gradients are the per-row program's too, to rounding.

    ``prm`` leaves are (B,)-vectors: the whole recurrence carries a
    *trailing* scenario-batch axis — grid state is (S, Q_max, B) —
    so a hardware what-if grid runs as one program whose gathers and
    ring permutations move contiguous B-sized blocks (a leading vmap
    axis would make every gather element-strided; measured ~4x slower).
    Geometry (N, nb, P, Q) is scalar per call; mixed-geometry sweeps
    vmap over this core with B=1 (see ``_compiled``).

    ``nodes``, where given, is the traced ``(rows, cols, alpha0)``: the
    block of the grid that one node holds (``node_block``) and the
    latency of a message inside a node, (B,) (``intra_latency``); the
    program is then the node-aware one (named scope ``hpl.nodes`` in the
    tables).  A broadcast hop into a column that starts a node's block
    leaves the node: it costs ``alpha`` plus the panel's bytes over the
    link, shared by the block's ``rows`` ranks, which forward through the
    one NIC at about the same time; a hop inside the block costs
    ``alpha0`` (MPI overhead + fabric base latency) with no bandwidth
    term, as the DES charges a message between two ranks of one node.
    The ring's prefix sum of hop costs, ``hop * i`` on one rank a node,
    becomes ``hop * n_inter + alpha0 * n_intra``, where
    ``n_inter`` counts the node-leaving hops among the first i from the
    root.  A swap round ends on its slowest pair: a pair that crosses
    nodes exists where a node holds fewer rows than the column, and then
    moves its bytes over the link shared by the block's ``cols`` ranks;
    a pair inside a node of several rows costs ``alpha0``, which a
    what-if of the network's latency alone can make the larger.  With
    a block of (1, 1) every value is the node-blind one, bitwise.
    """
    f64 = jnp.float64
    N = jnp.asarray(N, jnp.int64)
    nb = jnp.asarray(nb, jnp.int64)
    P = jnp.asarray(P, jnp.int64)
    Q = jnp.asarray(Q, jnp.int64)
    B = jnp.shape(prm.peak_flops)[0]
    peak = prm.peak_flops * prm.gemm_eff                 # (B,)
    mem_bw = prm.mem_bw
    theta = prm.theta
    alpha = prm.net_latency
    bcast_bw = prm.link_bw * prm.bcast_bw_scale
    swap_bw = prm.link_bw * prm.swap_bw_scale
    lookahead = prm.lookahead

    # exact ceil-log2 via static lookup tables (float log2 can be off by
    # one ulp at powers of two, which would flip a whole latency round)
    ar2 = jnp.asarray([2.0 * math.ceil(math.log2(max(p, 2)))
                       for p in range(P_max + 1)], f64)
    swr = jnp.asarray([float(max(math.ceil(math.log2(p)), 1)) if p > 1
                       else 0.0 for p in range(P_max + 1)], f64)
    ar_lat = ar2[P] * alpha                              # (B,)
    sw_rounds = swr[P]

    if nodes:
        node_rows, node_cols = (jnp.asarray(g, jnp.int64)
                                for g in nodes[:2])
        alpha0 = jnp.asarray(nodes[2], f64)                      # (B,)
        # a swap pair leaves its node only if a node holds part of a column
        sw_cross = node_rows < P
        swap_lat = jnp.where(sw_cross, alpha, alpha0)            # (B,)
        swap_share = jnp.where(sw_cross, node_cols, 0).astype(f64)
        # a node of several rows holds pairs that stay on it, at alpha0
        swap_floor = jnp.where(node_rows > 1, alpha0, 0.0)       # (B,)

    col_on = jnp.arange(Q_max) < Q
    iq = jnp.arange(Q_max)

    # whole nb-blocks and trailing columns of the matrix: panel k has
    # max(n_blocks - k, 0) whole blocks and the tail (while k <= n_blocks)
    # left, so the per-panel tables divide only by P and Q
    n_blocks = N // nb
    n_tail = N - n_blocks * nb
    # ceil: a trailing N % nb panel is simulated at its true width
    n_panels = n_blocks + (n_tail > 0)

    def geometry(ks):
        return _geometry(n_blocks, n_tail, nb, P, P_max, ks)

    def fact_time(wf, mloc):
        """Factorization cost per row rank of panels of width ``wf`` (K,)
        over ``mloc`` (K, S) local rows (SimBLAS closed forms):
        dger/dscal/idamax are Level-1/2 memory-bound.  Returns (K, S, B)."""
        with jax.named_scope("hpl.fact"):
            wf = wf[:, None]
            pf_bytes = 8.0 * (jnp.maximum(mloc * wf * wf - wf ** 3 / 3.0,
                                          0.0) + 3.0 * mloc * wf)
            wf = wf[:, :, None]
            return (pf_bytes[:, :, None] / mem_bw + wf * (3 * theta)
                    + wf * ar_lat)

    C = _block_size(n_panels_max)

    def tables(k0):
        """What panels k0 .. k0+C-1 need that does not read the carry,
        each with a leading axis of C."""
        # one geometry pass over the block's panels, the one before (the
        # state a panel starts from holds its row classes) and the one
        # after (panel k+1's widths and rows feed panel k's lookahead)
        ks = k0 - 1 + jnp.arange(C + 2)
        blocks, tail, w_all, rows, case = geometry(ks)
        wf, w_next = w_all[1:C + 1], w_all[2:]                   # (C,)
        rows_k, rows_n = rows[1:C + 1], rows[2:]                 # (C, 3)
        # the update's columns: those left after the panel, ord space
        nloc = _by_case(*_numroc(blocks[2:], tail[2:], nb, 1, Q, Q_max)
                        ).astype(f64)                            # (C, Q)
        w = wf[:, None]
        # the slot a step starts from has panel k's case second; the
        # slot it writes has it first, and panel k+1's second
        panel_bytes = 8.0 * (_to_slots(rows_k, True) + w) * w    # (C, S)
        if nodes:
            # the block's rows share the NIC on a node-leaving hop
            panel_bytes = panel_bytes * node_rows.astype(f64)
        mloc_n = _to_slots(rows_n, True)                         # (C, S)
        n_rows = _slot_rows(case[:C + 1], P)                     # (C, S)
        tb = {"wf": wf, "mloc": _to_slots(rows_k, False), "nloc": nloc,
              "n_rows": n_rows, "present": n_rows > 0,
              "hop": alpha + panel_bytes[:, :, None] / bcast_bw,  # (C, S, B)
              "trsm": (w * w * nloc)[:, :, None] / peak + theta,  # (C, Q, B)
              "ft": fact_time(w_next, mloc_n),                   # (C, S, B)
              "gemm_nb": (2.0 * mloc_n[:, :, None] * w_next[:, None, None]
                          * wf[:, None, None]) / peak + theta,   # (C, S, B)
              "live": ks[1:C + 1] < n_panels}
        if P_max > 1:
            u_bytes = 8.0 * w * nloc                             # (C, Q)
            if not nodes:
                tb["swap"] = jnp.where(
                    u_bytes[:, :, None] > 0,
                    sw_rounds * (alpha + (u_bytes[:, :, None]
                                          / jnp.maximum(sw_rounds, 1.0))
                                 / swap_bw)
                    + (4.0 * 8.0 * w * nloc)[:, :, None] / mem_bw,
                    0.0)                                         # (C, Q, B)
            else:
                # a round's pairs that cross nodes share the NIC
                tb["swap"] = jnp.where(
                    u_bytes[:, :, None] > 0,
                    sw_rounds * jnp.maximum(
                        swap_lat + (u_bytes[:, :, None]
                                    / jnp.maximum(sw_rounds, 1.0)
                                    * swap_share) / swap_bw, swap_floor)
                    + (4.0 * 8.0 * w * nloc)[:, :, None] / mem_bw,
                    0.0)                                         # (C, Q, B)
        if nodes:
            with jax.named_scope("hpl.nodes"):
                tb.update(ring_hops(ks[1:C + 1]))
        return tb

    def ring_hops(ks):
        """Node-leaving hops among the first i of each panel's ring from
        its root qk = k % Q (the hops reach absolute columns qk+1 ..
        qk+i mod Q, and one leaves a node where it reaches a multiple of
        the block's width), as a (K, Q_max) table, and the latency of the
        hops that stay on a node (K, Q_max, B)."""
        qk = ks - ks // Q * Q
        t = qk[:, None] + iq[None, :]

        def upto(x):                     # multiples of node_cols in [0, x]
            return jnp.where(x >= 0, x // node_cols + 1, 0)
        n_inter = (upto(jnp.minimum(t, Q - 1)) - upto(qk)[:, None]
                   + jnp.where(t >= Q, upto(t - Q), 0))
        # a node holding whole rows keeps the ring, its wrap included
        n_inter = jnp.where(node_cols < Q, n_inter, 0).astype(f64)
        n_intra = iq.astype(f64)[None, :] - n_inter
        return {"n_inter": n_inter,
                "intra": n_intra[:, :, None] * alpha0[None, None, :]}

    # The T carry lives in *ring-order* space: stored column i holds the
    # absolute column (qk + i) % Q, so the broadcast root is always index
    # 0 and the prefix-max chain never gathers.  Each panel advances the
    # ring by exactly one column (qk = k % Q), so re-basing the carry for
    # the next panel is the static-roll-plus-select below — padding
    # columns (i >= Q) map to themselves throughout.  XLA CPU runs
    # dynamic gathers and cumulative scans orders of magnitude slower
    # than fusable elementwise chains on batched shapes, so both are
    # expressed with static slices + selects (bitwise-identical: max is
    # exact and the shifts are pure selection).
    #
    # ord-space NUMROC is panel-invariant: stored column i belongs to
    # proc (i - 1) % Q of the *next* panel's distribution, every panel.
    # bucket(1) == 1, so Q_max > 1 implies Q >= 2: the ord index of
    # column (k+1) % Q — i.e. 1 % Q — is static.
    idx1 = 1 if Q_max > 1 else 0
    qcol = iq[None, :, None]
    roll_on = qcol < Q - 1
    root_on = qcol == Q - 1

    def cummax_cols(x):
        """Inclusive prefix-max along axis 1 (Kogge-Stone shift-max)."""
        s = 1
        while s < Q_max:
            shifted = jnp.concatenate(
                [jnp.full_like(x[:, :s, :], -jnp.inf), x[:, :-s, :]],
                axis=1)
            x = jnp.maximum(x, shifted)
            s *= 2
        return x

    def ring_rebase(T):
        """Stored col i <- stored col (i+1)%Q on live cols, identity on
        padding: one static roll plus two selects."""
        if Q_max == 1:
            return T
        roll = jnp.concatenate([T[:, 1:, :], T[:, :1, :]], axis=1)
        return jnp.where(
            roll_on, roll,
            jnp.where(root_on, jnp.broadcast_to(T[:, :1, :], T.shape), T))

    def chain(carry, tb):
        """One panel's carry-dependent step, given its tables ``tb``."""
        # each phase's ops carry a named scope (hpl.bcast, hpl.swap,
        # hpl.update, hpl.lookahead), so a profile sorts the ops of a
        # step by phase
        T, fact_done = carry
        wf, mloc, nloc = tb["wf"], tb["mloc"], tb["nloc"]

        # 2. 1-ring broadcast along each row: prefix-max recurrence.
        # fact_done was computed in the previous iteration (lookahead):
        # the owning column factored panel k right after updating the
        # panel-k columns of step k-1, overlapping the rest of the update.
        with jax.named_scope("hpl.bcast"):
            if not nodes:
                hi = tb["hop"][:, None, :] * iq.astype(f64)[None, :, None]
            else:
                hi = (tb["hop"][:, None, :] * tb["n_inter"][None, :, None]
                      + tb["intra"][None, :, :])
            d = (T - hi).at[:, 0, :].set(fact_done)      # chain readiness
            a = hi + cummax_cols(d)
            arrival = a.at[:, 0, :].set(fact_done)       # root holds panel

        # 4. update: dtrsm + dgemm on the local tile
        with jax.named_scope("hpl.update"):
            gemm = (2.0 * mloc[:, None, None] * nloc[None, :, None] * wf
                    + 2.0 * mloc[:, None, None] * nloc[None, :, None]) \
                / peak + theta                           # (S, Q, B)
        # 3. row swaps: column ranks exchange the U strip, and every rank
        # of a column proceeds from the column max over the slots the
        # live rows fill, so after_swap is row-independent — a (Q, B)
        # row vector.  bucket(1) == 1, so P_max > 1 exactly when P > 1:
        # only then is there a swap to price
        with jax.named_scope("hpl.swap"):
            after_swap = _row_max(jnp.maximum(arrival, T),
                                  tb["present"][:, None, None],
                                  tb["n_rows"][:, None, None], 0)  # (Q, B)
            if P_max > 1:
                after_swap = after_swap + tb["swap"]
        with jax.named_scope("hpl.update"):
            T_new = (after_swap + tb["trsm"])[None, :, :] + gemm
        as_next = after_swap[idx1]                       # (B,) static slice

        # 1'. (lookahead) factor panel k+1 on its owning column, anchored
        # right after that column updates just the next panel's columns.
        with jax.named_scope("hpl.lookahead"):
            ft = tb["ft"]
            fact_next_overlap = as_next + tb["gemm_nb"] + ft
            fact_next_serial = T_new[:, idx1, :] + ft
            fact_next = (lookahead * jnp.minimum(fact_next_overlap,
                                                 fact_next_serial)
                         + (1.0 - lookahead) * fact_next_serial)
        # the panel column cannot broadcast before finishing its own step
        # only when overlapping is off; with lookahead the bcast may start
        # mid-update (HPL posts it asynchronously).
        #
        # freeze once past the live panel count, then re-base the ring
        # (frozen values must keep rotating with qk to stay column-stable;
        # the final masked max is invariant under the live-column cycle)
        live = tb["live"]
        return (ring_rebase(jnp.where(live, T_new, T)),
                jnp.where(live, fact_next, fact_done)), None

    def block(j, carry):
        with jax.named_scope("hpl.tables"):
            tb = tables(j * C)
        return jax.lax.scan(chain, carry, tb)[0]

    T0 = jnp.zeros((_SLOTS, Q_max, B), f64)
    with jax.named_scope("hpl.tables"):
        # panel 0: nothing to overlap with
        _, _, w0, rows0, _ = geometry(jnp.zeros(1, jnp.int64))
        F0 = fact_time(w0, _to_slots(rows0, True))[0]
        # the slots the last live panel wrote, frozen with the state
        last = _slot_rows(geometry(n_panels - 1 + jnp.arange(2))[4], P)[0]
    T, _ = jax.lax.fori_loop(0, n_panels_max // C, block, (T0, F0))
    total = _row_max(T, (last > 0)[:, None, None] & col_on[None, :, None],
                     last[:, None, None], (0, 1))                # (B,)
    # back substitution: ~2 N^2 flops + N broadcasts (minor)
    total = total + 2.0 * N * N / (peak * P * Q) + N / nb * alpha
    return total


# ------------------------------------------------------- lane sharding
# Device-sharded batch dispatch (DESIGN.md §20): the sweep engine's
# trailing/leading scenario axis is embarrassingly parallel (every lane
# is an independent recurrence), so when more than one local device is
# available the padded lane axis can be split across them.  It is an
# argument of the sweep (``shard=``), off by default.  A sharded sweep
# pads its lane batches to a multiple of the device count, so every
# batched dispatch splits over every device; with one device the
# dispatch is the unsharded one, bitwise.
def shard_device_count() -> int:
    """How many local devices a sharded dispatch would split over."""
    return len(jax.devices())


def _shard_lanes(shard: bool, n_lanes: int, *trees):
    """Place ``(B,)``-leading pytrees across local devices along the
    lane axis.  Returns ``(trees, sharded)``; identity (and False) unless
    ``shard`` is set and more than one device exists.  ``_pad_lanes``
    sized the batch, so a lane count that does not divide the device
    count is a bug and raises."""
    if not shard:
        return trees, False
    devs = jax.devices()
    if len(devs) <= 1:
        return trees, False
    if n_lanes % len(devs):
        raise ValueError(f"sharded dispatch of {n_lanes} lanes over "
                         f"{len(devs)} devices: pad with _pad_lanes")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.asarray(devs), ("lanes",))
    sharding = NamedSharding(mesh, PartitionSpec("lanes"))

    def put(x):
        return jax.device_put(jnp.asarray(x), sharding)

    return tuple(jax.tree_util.tree_map(put, t) for t in trees), True


def _record_shard(m, sharded: bool, prefix: str = "fastsim") -> None:
    if m.enabled and sharded:
        m.counter(f"{prefix}.sharded_dispatches").inc()
        m.gauge(f"{prefix}.shard_devices").set(shard_device_count())


# --------------------------------------------------------- compile cache
_TRACE_COUNT = 0


def trace_count() -> int:
    """How many times a simulator core has been (re)traced so far — a
    compile counter for cache-hit assertions in tests and benchmarks."""
    return _TRACE_COUNT


def _sim_core_scalar(N, nb, P, Q, prm: FastSimParams,
                     n_panels_max: int, P_max: int, Q_max: int, *nodes):
    """Scalar-params entry over the trailing-batch core (B=1); ``nodes``
    as ``_sim_core``'s, with a scalar latency."""
    def lane(x):
        return jnp.asarray(x, jnp.float64)[None]
    prm1 = jax.tree_util.tree_map(lane, prm)
    nodes1 = nodes[:2] + tuple(map(lane, nodes[2:]))
    return _sim_core(N, nb, P, Q, prm1, n_panels_max, P_max, Q_max,
                     *nodes1)[0]


@functools.lru_cache(maxsize=128)
def _compiled(n_panels_max: int, P_max: int, Q_max: int, mode: str,
              nodes: bool = False):
    """mode: 'single' (scalar in/out) | 'params' (shared geometry, (B,)
    params leaves — the trailing-batch fast path for what-if grids) |
    'batch' (vmap over geometry and params for mixed-config sweeps).

    ``nodes`` compiles the node-aware program, which takes
    ``_node_args``' (rows, cols, alpha0) after the params: the block per
    call in 'single' and 'params' mode and per lane in 'batch' mode, the
    latency per lane; the node-blind program takes no such argument."""
    core = _sim_core if mode == "params" else _sim_core_scalar

    def fn(N, nb, P, Q, prm, *node_args):
        global _TRACE_COUNT
        _TRACE_COUNT += 1
        return core(N, nb, P, Q, prm, n_panels_max, P_max, Q_max,
                    *node_args)
    # the program's name in HLO and profiles: jit_hpl_recurrence_<mode>,
    # or jit_hpl_recurrence_nodes_<mode>
    fn.__name__ = fn.__qualname__ = \
        f"hpl_recurrence_{'nodes_' if nodes else ''}{mode}"
    return jax.jit(jax.vmap(fn) if mode == "batch" else fn)


def _call(fn, args, key: Tuple, live: int, lanes: int,
          sharded: bool = False, prefix: str = "fastsim",
          traces=trace_count, node_aware: bool = False,
          rows: int = 0) -> np.ndarray:
    """Run one compiled program and bring its answer to the host.

    With the global metrics registry on, the call is two spans on the
    profiler's clock: ``<prefix>.launch`` (``fn(*args)`` until it
    returns: argument conversion, host-to-device transfer, enqueue) and
    ``<prefix>.wait`` (until the answer is on the host).  The dispatch is
    recorded per shape bucket ``key`` as a compile-cache hit or miss
    (``traces`` is the model's trace counter), with its lane occupancy
    — padding lanes are pure waste — the live lanes that ran the
    node-aware program (``node_aware``), and, on hits only, the launch,
    wait and whole-dispatch wall times (a miss's wall is the compile's).
    A panel recurrence gives ``rows``, its bucket's process rows P_max:
    the state rows its lanes carry and the bucket's rows are counted."""
    m = get_global_metrics()
    if not m.enabled:
        return np.asarray(fn(*args))
    pre = traces()
    with Timer(span=f"{prefix}.launch") as launch:
        out = fn(*args)
    with Timer(span=f"{prefix}.wait") as wait:
        out = np.asarray(out)
    dt = launch.elapsed + wait.elapsed
    bucket = "x".join(str(b) for b in key)
    misses = traces() - pre
    if misses:
        m.counter(f"{prefix}.compile_misses", bucket=bucket).inc(misses)
        m.histogram(f"{prefix}.compile_wall_s", bucket=bucket).observe(dt)
    else:
        m.counter(f"{prefix}.compile_hits", bucket=bucket).inc()
        m.histogram(f"{prefix}.launch_s").observe(launch.elapsed)
        m.histogram(f"{prefix}.wait_s").observe(wait.elapsed)
        m.histogram(f"{prefix}.dispatch_wall_s").observe(dt)
    m.counter(f"{prefix}.lanes_live").inc(live)
    m.counter(f"{prefix}.lanes_padded").inc(lanes - live)
    if node_aware:
        m.counter(f"{prefix}.lanes_node_aware").inc(live)
    if rows:
        m.counter(f"{prefix}.rows_carried").inc(lanes * _SLOTS)
        m.counter(f"{prefix}.rows_bucket").inc(lanes * rows)
    _record_shard(m, sharded, prefix)
    return out


def _single_args(cfg: HPLConfig, prm: FastSimParams, nodes: tuple) -> tuple:
    # every node layout shares one program: the node fields ride as
    # arguments of their own (``nodes``, from ``_node_args``)
    return (np.int64(cfg.N), np.int64(cfg.nb), np.int64(cfg.P),
            np.int64(cfg.Q),
            dataclasses.replace(_f64_params(prm), ranks_per_node=1,
                                intra_latency=None)) + nodes


def _run_single(cfg: HPLConfig, prm: FastSimParams) -> float:
    key = bucket_key(cfg)
    nodes = _node_args(cfg, prm)
    return float(_call(_compiled(*key, "single", bool(nodes)),
                       _single_args(cfg, prm, nodes), key, 1, 1,
                       node_aware=bool(nodes), rows=key[1]))


def _stack_params(prm_list: Sequence[FastSimParams],
                  lanes: Sequence[int]) -> FastSimParams:
    # numpy leaves: jit converts them on dispatch, ~10x cheaper than
    # building device arrays one field at a time
    return FastSimParams(**{
        n: np.asarray([float(getattr(prm_list[i], n)) for i in lanes],
                      np.float64)
        for n in _PARAM_FIELDS})


def _pad_lanes(idxs: List[int], shard: bool) -> List[int]:
    """Pad a lane batch to a power of two (so repeat sweeps of any size
    reuse the compile cache); a ``shard``-ed batch is rounded up to a
    multiple of the local devices it splits over."""
    pad = 1 << (len(idxs) - 1).bit_length()
    if shard:
        n_dev = shard_device_count()
        pad = -(-pad // n_dev) * n_dev
    return idxs + [idxs[-1]] * (pad - len(idxs))


def simulate_time_traced(cfg: HPLConfig, prm: FastSimParams):
    """Differentiable scalar HPL time for traced ``prm`` leaves (call
    under ``jax.enable_x64(True)``; config stays concrete).  This
    is the autodiff surface used by ``calibrate.fit_fastsim_params``."""
    return _sim_core_scalar(np.int64(cfg.N), np.int64(cfg.nb),
                            np.int64(cfg.P), np.int64(cfg.Q), prm,
                            *bucket_key(cfg), *_node_args(cfg, prm))


def _result(cfg: HPLConfig, t: float) -> dict:
    return {"time_s": t, "gflops": cfg.flops() / t / 1e9,
            "tflops": cfg.flops() / t / 1e12}


def simulate_hpl_fast(cfg: HPLConfig, prm: FastSimParams) -> dict:
    with jax.enable_x64(True):
        t = _run_single(cfg, prm)
    return _result(cfg, t)


# ---------------------------------------------------------- sweep engine
Configs = Union[HPLConfig, Sequence[HPLConfig]]
Params = Union[FastSimParams, Sequence[FastSimParams]]


def sweep_hpl(configs: Configs, params: Params, *,
              bucket: Optional[Tuple[int, int, int]] = None,
              shard: bool = False) -> List[dict]:
    """Run a scenario sweep in as few compiled programs as possible.

    ``configs`` and ``params`` are zipped; a single ``HPLConfig`` or
    ``FastSimParams`` on either side broadcasts against the other.
    Scenarios sharing an exact ``(N, nb, P, Q)`` run as one params-only
    vmap (geometry stays scalar — the fast path for hardware what-if
    grids); the remaining scenarios are grouped by shape bucket
    (``bucket_key``) and each bucket runs as one fully-vmapped call.
    Batches are padded to a power of two so repeat sweeps of any size
    reuse the compile cache.  Results come back as one
    ``simulate_hpl_fast``-style dict per scenario, in input order.

    ``bucket=(n_panels_max, P_max, Q_max)`` forces every scenario into
    ONE padded shape bucket: the whole sweep runs as a single compiled
    vmapped program regardless of geometry mix (the TOP500 fleet path —
    one compile for a whole list).  One-row and one-column grids run in
    that bucket with the side cut to 1, a call of their own, so every
    answer is the one its own bucket gives.  Each component is rounded
    up to a cache-friendly bucket size; a config that doesn't fit raises.

    ``shard=True`` splits each batched call's padded lane axis across
    the local devices (lane batches pad to a multiple of the device
    count); with one device it is the unsharded call, bitwise.
    """
    cfg_list = [configs] if isinstance(configs, HPLConfig) else list(configs)
    prm_list = [params] if isinstance(params, FastSimParams) else list(params)
    if len(cfg_list) == 1 and len(prm_list) > 1:
        cfg_list = cfg_list * len(prm_list)
    if len(prm_list) == 1 and len(cfg_list) > 1:
        prm_list = prm_list * len(cfg_list)
    if len(cfg_list) != len(prm_list):
        raise ValueError(
            f"sweep_hpl: {len(cfg_list)} configs vs {len(prm_list)} params "
            "(must match, or one side must be a single scenario)")
    m = get_global_metrics()
    pre = trace_count()
    times = np.empty(len(cfg_list), np.float64)
    with jax.enable_x64(True):
        with (Timer(span="fastsim.prepare") if m.enabled
              else contextlib.nullcontext()) as prep:
            nodes = [_node_args(c, p) for c, p in zip(cfg_list, prm_list)]
            plan = (_plan(cfg_list, prm_list, nodes, shard=shard)
                    if bucket is None else
                    _plan_forced(cfg_list, prm_list, nodes, bucket,
                                 shard=shard))
        for fn, args, idxs, key, lanes, sharded, aware in plan:
            out = _call(fn, args, key, len(idxs), lanes, sharded,
                        node_aware=aware, rows=key[1])
            times[idxs] = np.reshape(out, -1)[:len(idxs)]
    if m.enabled and trace_count() == pre:
        m.histogram("fastsim.prepare_s").observe(prep.elapsed)
    return [_result(cfg, float(t)) for cfg, t in zip(cfg_list, times)]


def _plan(cfg_list: Sequence[HPLConfig],
          prm_list: Sequence[FastSimParams],
          nodes: Sequence[tuple], *, shard: bool = False) -> List[tuple]:
    """The host's sweep assembly: the compiled calls ``(fn, args,
    scenario indices, shape bucket, lanes, sharded, node-aware)`` that
    answer every scenario — one params-mode call per shared geometry and
    node block, one batch-mode call per shape bucket of the rest, single
    calls for loners.  ``nodes`` holds each scenario's ``_node_args``;
    ``shard`` splits the batched calls' lanes across local devices."""
    by_cfg: Dict[tuple, List[int]] = {}
    for idx, cfg in enumerate(cfg_list):
        by_cfg.setdefault((cfg.N, cfg.nb, cfg.P, cfg.Q, nodes[idx][:2]),
                          []).append(idx)

    plan: List[tuple] = []
    mixed: Dict[Tuple[int, int, int], List[int]] = {}
    for (N, nb, P, Q, block), idxs in by_cfg.items():
        key = bucket_key(cfg_list[idxs[0]])
        if len(idxs) == 1:
            mixed.setdefault(key, []).append(idxs[0])
            continue
        lanes = _pad_lanes(idxs, shard)
        per_lane = [_stack_params(prm_list, lanes)]
        if block:    # the intra-node latency is a lane's, as the params
            per_lane.append(np.asarray([nodes[i][2] for i in lanes]))
        (stacked, *alpha0), sharded = _shard_lanes(shard, len(lanes),
                                                   *per_lane)
        args = (np.int64(N), np.int64(nb), np.int64(P), np.int64(Q),
                stacked) + block + tuple(alpha0)
        plan.append((_compiled(*key, "params", bool(block)), args,
                     idxs, key, len(lanes), sharded, bool(block)))
    for key, idxs in mixed.items():
        if len(idxs) == 1:
            i = idxs[0]
            plan.append((_compiled(*key, "single", bool(nodes[i])),
                         _single_args(cfg_list[i], prm_list[i], nodes[i]),
                         idxs, key, 1, False, bool(nodes[i])))
        else:
            plan.append(_batch_call(cfg_list, prm_list, nodes, idxs, key,
                                    shard=shard))
    return plan


def _batch_call(cfg_list: Sequence[HPLConfig],
                prm_list: Sequence[FastSimParams],
                nodes: Sequence[tuple],
                idxs: List[int], key: Tuple[int, int, int], *,
                shard: bool = False) -> tuple:
    """One batch-mode call over scenarios ``idxs`` in shape bucket
    ``key``: geometry and params both ride the padded lane axis, and the
    node arguments too where any lane has them (a node-blind lane rides
    the node-aware program as the block (1, 1), which prices every hop
    and swap round as the node-blind program does, bitwise)."""
    lanes = _pad_lanes(idxs, shard)
    geom = np.asarray([[cfg_list[i].N, cfg_list[i].nb,
                        cfg_list[i].P, cfg_list[i].Q]
                       for i in lanes], np.int64)
    cols = [geom[:, 0], geom[:, 1], geom[:, 2], geom[:, 3],
            _stack_params(prm_list, lanes)]
    aware = any(nodes[i] for i in idxs)
    if aware:
        lane_nodes = [nodes[i] or (1, 1, 0.0) for i in lanes]
        cols += [np.asarray([n[0] for n in lane_nodes], np.int64),
                 np.asarray([n[1] for n in lane_nodes], np.int64),
                 np.asarray([n[2] for n in lane_nodes], np.float64)]
    args, sharded = _shard_lanes(shard, len(lanes), *cols)
    return (_compiled(*key, "batch", aware), args, idxs, key, len(lanes),
            sharded, aware)


def _plan_forced(cfg_list: Sequence[HPLConfig],
                 prm_list: Sequence[FastSimParams],
                 nodes: Sequence[tuple],
                 bucket: Tuple[int, int, int], *,
                 shard: bool = False) -> List[tuple]:
    """Batch-mode calls for the whole sweep under a shared (rounded-up)
    bucket — one traced program per distinct forced bucket, however many
    two-dimensional geometries are mixed in.  The recurrence reads
    ``P_max > 1`` as ``P > 1`` and ``Q_max > 1`` as ``Q > 1`` (true of a
    config's own bucket, since bucket(1) == 1), so one-row and
    one-column grids run in the bucket with that side cut to 1: a call
    of their own, answering as their own bucket does."""
    n_panels_max, P_max, Q_max = (_bucket(b) for b in bucket)
    calls: Dict[Tuple[int, int], List[int]] = {}
    for idx, cfg in enumerate(cfg_list):
        if (cfg.n_panels > n_panels_max or cfg.P > P_max
                or cfg.Q > Q_max):
            raise ValueError(
                f"sweep_hpl: config (N={cfg.N}, nb={cfg.nb}, P={cfg.P}, "
                f"Q={cfg.Q}) exceeds forced bucket "
                f"({n_panels_max}, {P_max}, {Q_max})")
        calls.setdefault((P_max if cfg.P > 1 else 1,
                          Q_max if cfg.Q > 1 else 1), []).append(idx)
    return [_batch_call(cfg_list, prm_list, nodes, idxs,
                        (n_panels_max, P, Q), shard=shard)
            for (P, Q), idxs in calls.items()]
