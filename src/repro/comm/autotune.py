"""Cost-model-driven schedule autotuning for the collective engine.

The paper's core finding is that the best communication path is workload- and
topology-dependent: circuit-switched inter-FPGA routes beat the host-staged
MPI path for the communication-bound benchmarks, but the winner flips with
message size and node count (Figs. 4-7). This module makes that selection a
first-class subsystem instead of a static per-op default:

* **Analytic mode** — an alpha-beta model prices every registered schedule
  per ``(op, message bytes, axis topology)``. Each schedule is reduced to
  hop count and per-link wire bytes on the :class:`AxisTopology` it runs
  over, and priced with :func:`repro.roofline.alpha_beta_time` using the
  :class:`HardwareModel` link numbers (per-hop latency ``alpha``, link
  bandwidth ``beta``; the staging domain uses MPI latency and PCIe/DCN
  bandwidth, the paper's Eq. 2 path).

* **Measured mode** — :func:`autotune_mesh` microbenchmarks the registered
  schedules on the live mesh across a ladder of message sizes, derives
  per-size winners, and persists a :class:`TuningTable` to
  ``results/tuning.json`` (``benchmarks/run.py --autotune``). The table is
  loaded on startup by :func:`default_cost_model` and overrides the analytic
  ranking wherever it has an entry, turning the ``--sweep-schedules``
  infrastructure into a feedback loop.

``CollectiveEngine`` resolves ``schedule="auto"`` through
:meth:`CostModel.choose` per callsite (cached by op/size/axis signature);
:func:`derive_bucket_bytes` replaces the fixed 32 MiB ``allreduce_tree``
bucket with pipeline depth x per-hop latency-bandwidth product.

Model (single ring axis of n ranks, message of S bytes; ``sync`` is the XLA
collective dispatch/rendezvous overhead in hop units):

====================  =====================================================
op / schedule         hops x alpha                +  wire bytes / beta
====================  =====================================================
bcast/chain           (n-1)                          (n-1) S
bcast/native          sync + n/2                     (n-1) S / 2
bcast/ring2d          2(n-1)                         2 S (n-1)/n
bcast/chain_rooted    2(n-1)                         2(n-1) S
allreduce/chain       (n-1)                          (n-1) S
allreduce/chain_rooted  2(n-1)                       2(n-1) S
allreduce/native      sync + (n-1)                   (n-1)/n S
allreduce/rs_ag       2(n-1)                         2 S (n-1)/n
allreduce/ring2d      sum over torus dims of the per-dim rs_ag ring
allreduce/int8_ef     rs_ag hops                     rs_ag wire x ~0.27
a2a/native            sync + n/2                     (n-1)/n S / 2
a2a/chain             n(n-1)/2                       (n-1) S / 2
ring_exchange/direct  1                              S
transpose/direct      pg                             S
transpose/ring2d      2(pg-1)                        (pg-1)(1+pg) S
* /staged             2 (MPI latency)                (ranks+1) S (PCIe/DCN)
====================  =====================================================

``native`` rides both ring directions (XLA uses all torus links) but pays a
fixed dispatch/rendezvous overhead; the explicit ``chain`` pipeline has no
such overhead, so it wins the latency-bound small-message regime — exactly
the paper's CSN-vs-MPI flip. Lossy schedules (``int8_ef``) are priced but
never *chosen* by ``auto``: compression changes numerics and must stay an
explicit opt-in.
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.comm.topology import AxisTopology
from repro.comm.types import TPU_V5E, HardwareModel
from repro.roofline import alpha_beta_time, pipelined_alpha_beta_time

# XLA-native collectives pay a fixed dispatch/rendezvous cost that the
# hand-written ppermute pipelines do not; expressed in per-hop latency units
# so it scales with the hardware model.
NATIVE_SYNC_HOPS = 6.0

# int8_ef wire ratio vs its f32 payload: the quantized chunk plus the
# quantized requantization residual carried alongside on every hop
# (repro.comm.compression quantize_ef, BLOCK=256) =>
# 2 x (1 byte/elem + 4/BLOCK scale bytes) = 2 x (0.25 + 1/256) of f32.
INT8_WIRE_RATIO = 2.0 * (0.25 + 1.0 / 256.0)

# schedules auto must never select: they change numerics (explicit opt-in)
LOSSY_SCHEDULES = frozenset({"int8_ef"})

# software pipelining (engine.pipelined / chunked PTRANS / depth-d HPL):
# chunk-count search ceiling and lookahead-depth ceiling for the resolvers
MAX_PIPELINE_CHUNKS = 16
MAX_LOOKAHEAD_DEPTH = 3

# allreduce_tree pipelining: how many buckets should be in flight so bucket
# k+1's backward compute hides bucket k's ring hops (paper Fig. 5/7 depth)
PIPELINE_DEPTH = 4
MIN_BUCKET_BYTES = 1 << 18   # 256 KiB — below this, per-collective overhead
MAX_BUCKET_BYTES = 32 << 20  # the former fixed default, now the ceiling

_REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_TABLE_PATH = _REPO_ROOT / "results" / "tuning.json"


def default_table_path() -> Path:
    """``results/tuning.json``, overridable via ``REPRO_TUNING_TABLE``."""
    return Path(os.environ.get("REPRO_TUNING_TABLE", DEFAULT_TABLE_PATH))


def axis_signature(axes: Sequence[AxisTopology]) -> str:
    """Canonical topology key, e.g. ``ring[8]`` or
    ``torus_row[2]+torus_col[2]`` — what tuning-table entries are keyed by."""
    return "+".join(f"{a.kind}[{a.size}]" for a in axes)


def _ranks(axes: Sequence[AxisTopology]) -> int:
    n = 1
    for a in axes:
        n *= a.size
    return n


# ---------------------------------------------------------------------------
# per-(op, schedule) analytic shapes
#
# Every schedule is decomposed into *segments* — ``(hops, wire_bytes, kind)``
# triples with kind in {"ici", "staged", "sync"} — priced either monolithic
# (:meth:`CostModel.cost`) or software-pipelined into S chunks
# (:func:`pipelined_cost`). "sync" segments are pure latency (the XLA-native
# dispatch/rendezvous); under pipelining every chunk's collective pays them.
# ---------------------------------------------------------------------------

Segment = Tuple[float, float, str]


def _sync_seg(hw: HardwareModel) -> Segment:
    return (NATIVE_SYNC_HOPS, 0.0, "sync")


def _staged_segs(nbytes: float, axes, hw) -> List[Segment]:
    # every byte transits the staging domain: up to the host network once,
    # back fanned out to all ranks (paper Eq. 2's PCIe+MPI route)
    n = _ranks(axes)
    return [(2, (n + 1) * nbytes, "staged")]


def _ring_rs_ag_segs(nbytes: float, n: int) -> List[Segment]:
    if n <= 1:
        return []
    return [(2 * (n - 1), 2 * (n - 1) / n * nbytes, "ici")]


def _segs_bcast_chain(S, axes, hw):
    n = _ranks(axes)
    return [(n - 1, (n - 1) * S, "ici")]


def _segs_chain_rooted(S, axes, hw):
    # bidirectional rooted chain away from a ring break: both arms relay
    # from the source, worst-case n-1 hops each way, every surviving wire
    # carrying S once per direction. Priced above plain chain (2x hops and
    # wire) so it never wins on a healthy ring — it exists to stay finite
    # when one link is down.
    n = _ranks(axes)
    return [(2 * (n - 1), 2 * (n - 1) * S, "ici")]


def _segs_bcast_native(S, axes, hw):
    # bidirectional all-gather + select: half the hops, both link directions
    n = _ranks(axes)
    return [_sync_seg(hw), (math.ceil(n / 2), (n - 1) * S / 2, "ici")]


def _segs_bcast_ring2d(S, axes, hw):
    # scatter + ring all-gather: 2(n-1) hops of S/n chunks
    return _ring_rs_ag_segs(S, _ranks(axes))


def _segs_allreduce_chain(S, axes, hw):
    n = _ranks(axes)
    return [(n - 1, (n - 1) * S, "ici")]


def _segs_allreduce_native(S, axes, hw):
    # XLA ring reduce-scatter/all-gather over both directions
    n = _ranks(axes)
    return [_sync_seg(hw), (n - 1, (n - 1) / n * S, "ici")]


def _segs_allreduce_rs_ag(S, axes, hw):
    return _ring_rs_ag_segs(S, _ranks(axes))


def _segs_allreduce_ring2d(S, axes, hw):
    # one unidirectional ring pass per torus dimension
    out = []
    for a in axes:
        out += _ring_rs_ag_segs(S, a.size)
    return out


def _segs_allreduce_int8_ef(S, axes, hw):
    return _ring_rs_ag_segs(S * INT8_WIRE_RATIO, _ranks(axes))


def _segs_a2a_native(S, axes, hw):
    n = _ranks(axes)
    return [_sync_seg(hw), (math.ceil(n / 2), (n - 1) / n * S / 2, "ici")]


def _segs_a2a_chain(S, axes, hw):
    # tile at ring distance d travels d hops: sum d = n(n-1)/2 hops of S/n
    n = _ranks(axes)
    return [(n * (n - 1) / 2, (n - 1) / 2 * S, "ici")]


def _segs_exchange_direct(S, axes, hw):
    return [(1, S, "ici")]


def _pg(axes) -> int:
    # grid_transpose runs on a pg x pg torus; a single flattened axis entry
    # (or explicit pair) both reduce to sqrt(total ranks)
    return max(int(round(math.sqrt(_ranks(axes)))), 1)


def _segs_transpose_direct(S, axes, hw):
    # dimension-ordered route to the (r,c)<->(c,r) partner: <= pg links
    pg = _pg(axes)
    if pg <= 1:
        return []  # no exchange on a 1x1 grid
    return [(pg, S, "ici")]


def _segs_transpose_ring2d(S, axes, hw):
    # row-phase ring all-gather (pg-1 unit-block hops) + column-phase chain
    # of the pg-block relay stack (paper Fig. 8 two-phase route)
    pg = _pg(axes)
    if pg <= 1:
        return []
    return [(pg - 1, (pg - 1) * S, "ici"),
            (pg - 1, (pg - 1) * pg * S, "ici")]


_SEGS: Dict[Tuple[str, str], Callable] = {
    ("bcast", "chain"): _segs_bcast_chain,
    ("bcast", "chain_rooted"): _segs_chain_rooted,
    ("bcast", "native"): _segs_bcast_native,
    ("bcast", "ring2d"): _segs_bcast_ring2d,
    ("bcast", "staged"): _staged_segs,
    ("allreduce", "chain"): _segs_allreduce_chain,
    ("allreduce", "chain_rooted"): _segs_chain_rooted,
    ("allreduce", "native"): _segs_allreduce_native,
    ("allreduce", "rs_ag"): _segs_allreduce_rs_ag,
    ("allreduce", "ring2d"): _segs_allreduce_ring2d,
    ("allreduce", "int8_ef"): _segs_allreduce_int8_ef,
    ("allreduce", "staged"): _staged_segs,
    ("all_to_all_tiles", "native"): _segs_a2a_native,
    ("all_to_all_tiles", "chain"): _segs_a2a_chain,
    ("all_to_all_tiles", "staged"): _staged_segs,
    ("ring_exchange", "direct"): _segs_exchange_direct,
    ("ring_exchange", "chain"): _segs_exchange_direct,
    ("ring_exchange", "staged"): _staged_segs,
    ("grid_transpose", "direct"): _segs_transpose_direct,
    ("grid_transpose", "chain"): _segs_transpose_direct,
    ("grid_transpose", "ring2d"): _segs_transpose_ring2d,
    ("grid_transpose", "staged"): _staged_segs,
}


def segments(op: str, schedule: str, nbytes: float,
             axes: Sequence[AxisTopology],
             hw: HardwareModel = TPU_V5E) -> Optional[List[Segment]]:
    """The (hops, wire bytes, kind) decomposition of one schedule run, or
    None for schedules the model has no formula for."""
    fn = _SEGS.get((op, schedule))
    if fn is None:
        return None
    if any(a.kind == "staging" for a in axes):
        return _staged_segs(nbytes, axes, hw)
    return fn(float(nbytes), tuple(axes), hw)


def canonical_health(health: frozenset,
                     axes: Sequence[AxisTopology]) -> frozenset:
    """``health`` with every hop id mapped to its axis's canonical link id
    (:meth:`AxisTopology.canonical_hop`): on a size-2 ring hops 0 and 1
    name the same physical wire, so ``down_link(axis, 1)`` must exclude
    routes recorded as traversing hop 0 and vice versa. Entries naming
    axes outside ``axes`` pass through unchanged."""
    by_name = {a.name: a for a in axes}
    return frozenset(
        (nm, by_name[nm].canonical_hop(h)) if nm in by_name else (nm, h)
        for (nm, h) in health)


def route_links(op: str, schedule: str, axes: Sequence[AxisTopology], *,
                health: frozenset = frozenset()) -> Optional[frozenset]:
    """The set of ``(axis, hop)`` physical links one schedule run may
    traverse, or ``None`` for schedules the model has no formula for
    (nothing provable about their route).

    Links are canonical ids (:meth:`AxisTopology.links` — a size-2 ring
    has ONE wire, id 0, whichever hop name a fault used); ``health`` is
    canonicalized the same way before use.

    ``staged`` — and any run over a staging axis — touches no ICI link:
    its bytes ride PCIe + host MPI, the paper's escape-hatch network.
    ``chain_rooted`` cuts the ring at the down hop named in ``health``
    (the wraparound hop ``size-1`` when clean) and provably never crosses
    it; additional down hops on the same axis stay in its route, so a
    doubly-broken ring still prices as infinite. On a size-2 axis the
    rooted chain has nothing to cut away — every exchange rides the
    single wire — so that wire stays in its route and a down size-2 axis
    falls through to ``staged``. Every other priced ICI schedule is
    conservative: it may ride any link of its axes (XLA routes
    ``native``/``direct`` itself, and the ring pipelines touch every wire
    of the ring).
    """
    if (op, schedule) not in _SEGS:
        return None
    if schedule == "staged" or any(a.kind == "staging" for a in axes):
        return frozenset()
    health = canonical_health(health, axes)
    links = set()
    for a in axes:
        axis_links = set(a.links())
        if schedule == "chain_rooted" and a.n_links > 1:
            down = sorted(h for (nm, h) in health if nm == a.name)
            cut = down[0] if down else a.size - 1
            axis_links.discard((a.name, cut))
        links |= axis_links
    return frozenset(links)


def _seg_time(seg: Segment, hw: HardwareModel) -> float:
    hops, wire, kind = seg
    if kind == "sync":
        return hops * hw.ici_latency
    return alpha_beta_time(hops, wire, hw, staged=kind == "staged")


def _seg_time_pipelined(seg: Segment, nchunks: int, hw: HardwareModel) -> float:
    hops, wire, kind = seg
    if kind == "sync":
        # every chunk's collective pays the dispatch/rendezvous in full
        return nchunks * hops * hw.ici_latency
    return pipelined_alpha_beta_time(hops, wire, nchunks, hw,
                                     staged=kind == "staged")


def pipelined_cost(op: str, schedule: str, nbytes: float,
                   axes: Sequence[AxisTopology], nchunks: int,
                   hw: HardwareModel = TPU_V5E) -> float:
    """Predicted seconds for the schedule split into ``nchunks`` software-
    pipelined chunks (``nchunks=1`` equals :meth:`CostModel.cost`); ``inf``
    for schedules with no formula."""
    segs = segments(op, schedule, nbytes, axes, hw)
    if segs is None:
        return float("inf")
    return sum(_seg_time_pipelined(s, max(int(nchunks), 1), hw) for s in segs)


def best_nchunks(op: str, schedule: str, nbytes: float,
                 axes: Sequence[AxisTopology], hw: HardwareModel = TPU_V5E, *,
                 max_chunks: int = MAX_PIPELINE_CHUNKS) -> Tuple[int, float]:
    """The power-of-two chunk count minimizing :func:`pipelined_cost` —
    pipeline fill cost (S-1 extra stages of per-hop latency) against
    per-chunk wire time. Ties break toward fewer chunks. Returns
    ``(nchunks, predicted_seconds)``; (1, cost) when unpriceable."""
    best_s, best_c = 1, pipelined_cost(op, schedule, nbytes, axes, 1, hw)
    if not math.isfinite(best_c):
        return 1, best_c
    s = 2
    while s <= max_chunks:
        c = pipelined_cost(op, schedule, nbytes, axes, s, hw)
        if c < best_c:
            best_s, best_c = s, c
        s *= 2
    return best_s, best_c


def choose_hpl_depth(*, b: int, m: int, axes: Sequence[AxisTopology],
                     hw: HardwareModel = TPU_V5E, model=None, resolve=None,
                     max_depth: int = MAX_LOOKAHEAD_DEPTH) -> int:
    """Lookahead depth for HPL: how many panel pipelines to keep in flight.

    Per iteration the factorization broadcasts one b x b diagonal block along
    each torus dimension and one b x m panel along each; the bulk trailing
    GEMM offers ``2 m^2 b`` FLOPs of cover. Depth d hides d iterations'
    broadcast latency behind one bulk update, so::

        depth = clamp(ceil(T_bcast_iter / T_gemm_iter), 1, max_depth)

    — latency-bound small blocks on large tori go deep, compute-bound large
    local matrices stay at 1 (one iteration of cover already suffices).
    Each extra depth costs d thin strip GEMMs (~2b/m of the bulk FLOPs) and
    one more carried panel set, which is why the ceiling stays small.

    ``resolve(op, nbytes, ax, callsite)`` optionally names the schedule the
    *caller* will actually run per broadcast (an engine's
    ``schedule_for``, honoring engine-wide overrides and HOST_STAGED) —
    without it the broadcasts are priced on the model's own preferred
    schedule, which under-prices t_comm whenever a costlier schedule is
    forced (exactly the case deep lookahead exists for).
    """
    if model is None:
        model = default_cost_model()
    # keep both sides of the ratio on ONE hardware model: the model's, when
    # it carries one (an engine with a custom CostModel must not have its
    # comm side priced on that hw but its GEMM side on the v5e default)
    hw = getattr(model, "hw", None) or hw
    t_comm = 0.0
    for ax in tuple(axes):
        for nbytes, callsite in ((b * b * 4, "hpl.block"),
                                 (b * m * 4, "hpl.panel")):
            if resolve is not None:
                sched = resolve("bcast", nbytes, ax, callsite)
            else:
                sched = model.choose("bcast", nbytes, (ax,),
                                     callsite=callsite) or "chain"
            t_comm += model.cost("bcast", sched, nbytes, (ax,))
    t_gemm = 2.0 * float(m) * m * b / hw.peak_flops
    if t_gemm <= 0.0:
        return 1
    if not math.isfinite(t_comm):
        # an unpriceable (user-registered / measured-only) schedule: the
        # model can't size the ratio, but infinite comm is comm-bound —
        # clamp to the ceiling instead of overflowing on ceil(inf)
        return max_depth
    return max(1, min(int(math.ceil(t_comm / t_gemm)), max_depth))


# ---------------------------------------------------------------------------
# tuning table (measured mode)
# ---------------------------------------------------------------------------


@dataclass
class TuningTable:
    """Measured per-(op, topology) winners, bucketed by message size.

    ``entries[op][axis_sig]`` is an ascending list of ``[max_bytes, name]``
    pairs; a ``None`` max_bytes entry is the open-ended tail. Lookup returns
    the first entry whose bound covers ``nbytes``.

    The op key may carry a **callsite tag** — ``"bcast@hpl.panel"`` — for
    winners measured in a callsite-specific pattern (e.g. HPL's panel bcast
    issued back-to-back with the block bcast, vs an isolated bcast). Lookup
    with a callsite consults the tagged entry first and falls back to the
    untagged op.
    """
    hw: str = TPU_V5E.name
    entries: Dict[str, Dict[str, List[Tuple[Optional[int], str]]]] = \
        field(default_factory=dict)
    meta: Dict[str, object] = field(default_factory=dict)

    def lookup(self, op: str, sig: str, nbytes: int,
               callsite: Optional[str] = None) -> Optional[str]:
        keys = ([f"{op}@{callsite}", op] if callsite else [op])
        for key in keys:
            for bound, name in self.entries.get(key, {}).get(sig, ()):
                if bound is None or nbytes <= bound:
                    return name
        return None

    def set(self, op: str, sig: str,
            bounds: List[Tuple[Optional[int], str]]) -> None:
        self.entries.setdefault(op, {})[sig] = list(bounds)

    def to_json(self) -> Dict:
        return {"hw": self.hw, "meta": self.meta,
                "entries": {op: {sig: [[b, n] for b, n in rows]
                                 for sig, rows in sigs.items()}
                            for op, sigs in self.entries.items()}}

    @classmethod
    def from_json(cls, data: Dict) -> "TuningTable":
        entries = {
            op: {sig: [(None if b is None else int(b), str(n))
                       for b, n in rows]
                 for sig, rows in sigs.items()}
            for op, sigs in data.get("entries", {}).items()}
        return cls(hw=data.get("hw", TPU_V5E.name), entries=entries,
                   meta=data.get("meta", {}))

    def merge(self, other: "TuningTable") -> "TuningTable":
        """A new table with ``other``'s bands overlaid on this one's —
        ``other`` wins wherever both cover an ``(op, signature)`` pair. The
        in-run retune path (:mod:`repro.comm.retune`) merges its narrow
        re-measurement over the persisted full table this way, so cold
        callsites keep their winners."""
        out = TuningTable(
            hw=other.hw or self.hw,
            entries={op: {sig: list(rows) for sig, rows in sigs.items()}
                     for op, sigs in self.entries.items()},
            meta={**self.meta, **other.meta})
        for op, sigs in other.entries.items():
            for sig, rows in sigs.items():
                out.set(op, sig, rows)
        return out

    def save(self, path=None) -> Path:
        path = Path(path or default_table_path())
        path.parent.mkdir(parents=True, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1)
        return path

    @classmethod
    def load(cls, path=None) -> Optional["TuningTable"]:
        path = Path(path or default_table_path())
        try:
            with open(path) as f:
                return cls.from_json(json.load(f))
        except (OSError, ValueError, KeyError, TypeError):
            return None


# ---------------------------------------------------------------------------
# the cost model
# ---------------------------------------------------------------------------


@dataclass(eq=False)
class CostModel:
    """Prices registered schedules and picks one per (op, bytes, topology).

    A measured :class:`TuningTable` (when present) overrides the analytic
    alpha-beta ranking for the (op, axis signature) pairs it covers; the
    analytic model covers everything else, so ``auto`` always resolves.
    Choices are memoized by ``(op, nbytes, axis signature, callsite)`` —
    resolution is a pure function of static data, hence identical across
    processes.

    ``health`` is the link-health mask — ``(axis, hop)`` pairs that are
    hard-down (:meth:`repro.comm.faults.FaultInjector.down_links`). Any
    schedule whose provable route (:func:`route_links`) crosses a down
    link prices as infinite, so resolution excludes it; a down ring falls
    through to ``chain_rooted`` (finite away from the cut) and, failing
    that, the host-``staged`` path, which touches no ICI link at all.
    """
    hw: HardwareModel = TPU_V5E
    table: Optional[TuningTable] = None
    health: frozenset = frozenset()
    _cache: Dict[Tuple[str, int, str, Optional[str]], str] = \
        field(default_factory=dict, repr=False)

    def cost(self, op: str, schedule: str, nbytes: float,
             axes: Sequence[AxisTopology]) -> float:
        """Predicted seconds; ``inf`` for schedules the model cannot price
        (e.g. user-registered ones with no formula — never chosen by auto)
        and for any schedule whose route crosses a link in ``health``."""
        if self.health:
            health = canonical_health(self.health, axes)
            links = route_links(op, schedule, axes, health=health)
            if links is None or links & health:
                return float("inf")
        segs = segments(op, schedule, nbytes, axes, self.hw)
        if segs is None:
            return float("inf")
        return sum(_seg_time(s, self.hw) for s in segs)

    def pipelined_cost(self, op: str, schedule: str, nbytes: float,
                       axes: Sequence[AxisTopology], nchunks: int) -> float:
        """Predicted seconds with the payload split into ``nchunks``
        software-pipelined chunks (:func:`pipelined_cost`)."""
        return pipelined_cost(op, schedule, nbytes, axes, nchunks, self.hw)

    def best_nchunks(self, op: str, schedule: str, nbytes: float,
                     axes: Sequence[AxisTopology], *,
                     max_chunks: int = MAX_PIPELINE_CHUNKS
                     ) -> Tuple[int, float]:
        return best_nchunks(op, schedule, nbytes, axes, self.hw,
                            max_chunks=max_chunks)

    def rank(self, op: str, nbytes: float, axes: Sequence[AxisTopology], *,
             include_lossy: bool = False) -> List[Tuple[str, float]]:
        """Registered schedules for ``op`` sorted by predicted cost. Ties
        break toward the op's static default, then by name, so the ranking
        is deterministic (aliases like ``chain``-for-``direct`` price
        identically). Lossy schedules are excluded unless requested — auto
        must never change numerics."""
        from repro.comm.engine import _AUTO, schedules_for
        default = _AUTO.get(op)
        rows = []
        for name in schedules_for(op):
            if name in LOSSY_SCHEDULES and not include_lossy:
                continue
            c = self.cost(op, name, nbytes, axes)
            if math.isfinite(c):
                rows.append((name, c))
        return sorted(rows, key=lambda r: (r[1], r[0] != default, r[0]))

    def choose(self, op: str, nbytes: int, axes: Sequence[AxisTopology],
               callsite: Optional[str] = None) -> Optional[str]:
        """The schedule ``auto`` resolves to, or None if nothing is priced.

        ``callsite`` is an optional tag (``"hpl.panel"``, ``"ptrans.
        exchange"``) naming the call pattern; measured tuning-table entries
        keyed ``op@callsite`` override the untagged op entry for it. The
        analytic ranking is callsite-independent."""
        sig = axis_signature(axes)
        key = (op, int(nbytes), sig, callsite)
        if key in self._cache:
            return self._cache[key]
        name = None
        if self.table is not None:
            name = self.table.lookup(op, sig, int(nbytes), callsite)
            if name is not None:
                from repro.comm.engine import schedules_for
                if name not in schedules_for(op) or name in LOSSY_SCHEDULES:
                    name = None  # stale table entry: fall back to analytic
            if name is not None and self.health and not math.isfinite(
                    self.cost(op, name, nbytes, axes)):
                name = None  # measured winner routes through a down link
        if name is None:
            ranked = self.rank(op, nbytes, axes)
            name = ranked[0][0] if ranked else None
        self._cache[key] = name
        return name


_DEFAULT_MODEL: Optional[CostModel] = None


def _table_matches_runtime(table: Optional[TuningTable]) -> bool:
    """A measured table only applies to the backend it was measured on —
    a tuning.json produced on the simulated CPU mesh (e.g. the CI artifact)
    must not override the analytic model on real TPU."""
    if table is None:
        return False
    recorded = table.meta.get("backend")
    if recorded is None:
        return True  # hand-written table: caller's responsibility
    import jax
    return recorded == jax.default_backend()


def default_cost_model(refresh: bool = False) -> CostModel:
    """Process-wide model the engine uses for ``schedule="auto"``: analytic
    alpha-beta on :data:`TPU_V5E`, overlaid with ``results/tuning.json``
    when a measured table exists *for this backend*. ``refresh=True``
    re-reads the table (after ``benchmarks/run.py --autotune``)."""
    global _DEFAULT_MODEL
    if _DEFAULT_MODEL is None or refresh:
        table = TuningTable.load()
        if not _table_matches_runtime(table):
            table = None
        _DEFAULT_MODEL = CostModel(hw=TPU_V5E, table=table)
    return _DEFAULT_MODEL


# ---------------------------------------------------------------------------
# derived bucket size for allreduce_tree
# ---------------------------------------------------------------------------


def derive_bucket_bytes(axes: Sequence[AxisTopology],
                        hw: HardwareModel = TPU_V5E, *,
                        depth: int = PIPELINE_DEPTH) -> int:
    """Bucket size for the bucketed tree reduction, from topology + link
    numbers instead of a fixed constant.

    A bucket's ring reduction occupies ``2(n-1)`` hops; with ``depth``
    buckets in flight the per-bucket payload should cover that hop latency
    at link bandwidth — ``depth x 2(n-1) x (alpha x beta)`` (the per-hop
    latency-bandwidth product). Rounded up to a power of two and clamped to
    [:data:`MIN_BUCKET_BYTES`, :data:`MAX_BUCKET_BYTES`] (the former fixed
    default is now the ceiling)."""
    n = _ranks(axes)
    if n <= 1:
        return MIN_BUCKET_BYTES
    raw = depth * 2 * (n - 1) * hw.ici_latency * hw.ici_link_bw
    raw = max(raw, MIN_BUCKET_BYTES)
    return int(min(1 << math.ceil(math.log2(raw)), MAX_BUCKET_BYTES))


# ---------------------------------------------------------------------------
# measured mode: microbenchmark the registered schedules on the live mesh
# ---------------------------------------------------------------------------


def _winner_bounds(sizes: Sequence[int],
                   winners: Sequence[str]) -> List[Tuple[Optional[int], str]]:
    """Collapse per-size winners into [max_bytes, name] bands; boundaries
    sit at the geometric mean of adjacent measured sizes."""
    bounds: List[Tuple[Optional[int], str]] = []
    for i, name in enumerate(winners):
        last = i == len(winners) - 1
        if bounds and bounds[-1][1] == name:
            bounds.pop()  # extend the previous band
        edge = None if last else int(math.sqrt(sizes[i] * sizes[i + 1]))
        bounds.append((edge, name))
    if bounds and bounds[-1][0] is not None:
        bounds[-1] = (None, bounds[-1][1])
    return bounds


def _measure_op(mesh, op: str, nbytes: int, schedule: str,
                reps: int) -> float:
    """Best-of-reps seconds for one (op, schedule, size) on the live mesh,
    plus the active fault injector's modeled delay for that exact run
    (:func:`repro.comm.faults.measured_extra_time`) — a degraded link
    perturbs the measured winners consistently with the analytic view."""
    t = _measure_op_clean(mesh, op, nbytes, schedule, reps)
    from repro.comm import faults
    if faults.active_injector() is not None:
        from repro.comm.topology import MeshTopology
        topo = MeshTopology.from_mesh(mesh)
        if "@" in op:
            # tagged patterns run along one axis (see autotune_mesh)
            axes = (topo.axis(topo.names()[0]),)
        else:
            axes = tuple(topo.axis(a) for a in topo.names())
        t += faults.measured_extra_time(op.split("@", 1)[0], schedule,
                                        nbytes, axes)
    return t


def _measure_op_clean(mesh, op: str, nbytes: int, schedule: str,
                      reps: int) -> float:
    """Best-of-reps seconds for one (op, schedule, size) on the live mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.comm.engine import CollectiveEngine
    from jax import shard_map
    from repro.core.hpcc import timeit

    engine = CollectiveEngine.for_mesh(mesh, schedule=schedule)
    names = tuple(mesh.shape)
    nranks = int(np.prod([mesh.shape[a] for a in names]))
    elems = max(nbytes // 4, 1)

    if op == "bcast@hpl.panel":
        # HPL's paired broadcasts on the torus row axis: a b x b diagonal
        # block bcast immediately followed by the dependent panel bcast being
        # measured — the callsite pattern an isolated bcast misses.
        rows = mesh.shape[names[0]]
        blk = jnp.asarray(np.ones((rows, 64 * 64), np.float32))
        x = jnp.asarray(np.ones((rows, elems), np.float32))
        spec = P(names[0], None)

        def body(vb, vp):
            b0 = engine.bcast(vb[0], names[0], 0)
            panel = vp[0] * b0[0]  # the trsm dependency block -> panel
            return engine.bcast(panel, names[0], 0)[None]

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec, spec),
                               out_specs=spec, check_vma=False))
        _, t = timeit(fn, blk, x, reps=reps, warmup=1)
        return t

    if op == "all_to_all_tiles@moe.dispatch":
        # MoE's paired exchanges on the ring: the dispatch all-to-all
        # (experts split across ranks, batch shards gathered), the expert
        # compute touching every landed tile, and the inverse combine
        # exchange — measured back-to-back, the pattern an isolated
        # all-to-all misses (the second exchange departs while the first's
        # rendezvous state is still warm).
        L = max(elems // nranks, 1)
        x = jnp.asarray(np.ones((nranks, nranks, L), np.float32))
        spec = P(names[0], None, None)

        def body(v):
            # v is the local (B_loc=1, E=nranks, L) dispatch buffer
            buf = engine.all_to_all_tiles(v, names[0], split_axis=1,
                                          concat_axis=0)  # dispatch
            buf = jax.nn.silu(buf) * buf  # stand-in expert FFN
            return engine.all_to_all_tiles(buf, names[0], split_axis=0,
                                           concat_axis=1)  # combine

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                               out_specs=spec, check_vma=False))
        _, t = timeit(fn, x, reps=reps, warmup=1)
        return t

    if op == "all_to_all_tiles@tp.qkv":
        # whole-model head-parallel attention pattern: THREE back-to-back
        # head-gathering exchanges (q, k, v), the attention compute touching
        # every landed tile, then the inverse batch-restoring exchange — the
        # four-exchange burst an isolated all-to-all misses.
        L = max(elems // nranks, 1)
        x = jnp.asarray(np.ones((nranks, nranks, L), np.float32))
        spec = P(names[0], None, None)

        def body(t):
            # t is the local (B_loc=1, H=nranks, L) activation
            def gather(a):  # heads split out, batch gathered
                return engine.all_to_all_tiles(a, names[0], split_axis=1,
                                               concat_axis=0)
            q, k, v = gather(t), gather(t * 0.5), gather(t * 0.25)
            o = jax.nn.softmax(q * k, axis=-1) * v  # attention stand-in
            return engine.all_to_all_tiles(o, names[0], split_axis=0,
                                           concat_axis=1)

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                               out_specs=spec, check_vma=False))
        _, t = timeit(fn, x, reps=reps, warmup=1)
        return t

    if op == "all_to_all_tiles@sp.qkv":
        # whole-model sequence-parallel ring attention pattern: the seq-
        # gathering exchanges for q/k/v, the k/v block circulating the ring
        # (~n/2 bidirectional hops) with the online-softmax fold between
        # hops, then the inverse exchange — the a2a's rendezvous interleaves
        # with the ring traffic, which an isolated all-to-all misses.
        L = max(elems // nranks, 1)
        x = jnp.asarray(np.ones((nranks, nranks, L), np.float32))
        spec = P(names[0], None, None)

        def body(v):
            def gather(a):  # sequence split out, batch gathered
                return engine.all_to_all_tiles(a, names[0], split_axis=1,
                                               concat_axis=0)
            q, k, kv = gather(v), gather(v * 0.5), gather(v * 0.25)
            acc = jax.nn.softmax(q * k, axis=-1) * kv  # local block fold
            fwd = bwd = kv
            for _ in range(max(nranks // 2, 1)):
                fwd, bwd = engine.ring_exchange(fwd, bwd, names[0])
                acc = acc + jax.nn.softmax(q * fwd, axis=-1) * bwd
            return engine.all_to_all_tiles(acc, names[0], split_axis=0,
                                           concat_axis=1)

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                               out_specs=spec, check_vma=False))
        _, t = timeit(fn, x, reps=reps, warmup=1)
        return t

    if op == "all_to_all_tiles@decode.qkv":
        # per-token decode pattern: q and the token's k/v ride three tiny
        # head-gathering exchanges, single-query attention against the page
        # pool runs between them, the inverse exchange restores the batch
        # layout, and the MoE dispatch/FFN/combine pair follows — six
        # back-to-back latency-bound exchanges, the serving burst an
        # isolated (training-sized) all-to-all measurement misses. Sized by
        # the decode ladder in :func:`autotune_mesh`, not the default one.
        L = max(elems // nranks, 1)
        x = jnp.asarray(np.ones((nranks, nranks, L), np.float32))
        pool = jnp.asarray(np.ones((nranks, 8, L), np.float32))
        spec = P(names[0], None, None)

        def body(t, pg_):
            def gather(a):  # heads split out, batch gathered
                return engine.all_to_all_tiles(a, names[0], split_axis=1,
                                               concat_axis=0)
            q, k, v = gather(t), gather(t * 0.5), gather(t * 0.25)
            s = jax.nn.softmax(q * pg_[:, :1] + k, axis=-1)  # paged attn
            o = engine.all_to_all_tiles(s * v, names[0], split_axis=0,
                                        concat_axis=1)
            buf = engine.all_to_all_tiles(o, names[0], split_axis=1,
                                          concat_axis=0)  # moe dispatch
            buf = jax.nn.silu(buf) * buf  # stand-in expert FFN
            return engine.all_to_all_tiles(buf, names[0], split_axis=0,
                                           concat_axis=1)  # moe combine

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec, spec),
                               out_specs=spec, check_vma=False))
        _, t = timeit(fn, x, pool, reps=reps, warmup=1)
        return t

    if op == "all_to_all_tiles@ra.updates":
        # GUPS update routing on the ring: the bucketed (n_dev, L, 2) int32
        # (local_index, value) exchange followed by the receiving
        # scatter-add — the latency-corner pattern (small irregular int
        # payloads, a serialized scatter on landing) an isolated float
        # all-to-all misses.
        L = max(elems // (2 * nranks), 1)  # nranks*L*2 int32 = nbytes
        tbl = jnp.asarray(np.zeros((nranks, 4096), np.int32))
        buf = jnp.asarray(np.ones((nranks, nranks, L, 2), np.int32))
        spec_t = P(names[0], None)
        spec_b = P(names[0], None, None, None)

        def body(t, b):
            recv = engine.all_to_all_tiles(b[0], names[0], split_axis=0,
                                           concat_axis=0)
            out = t[0].at[recv[..., 0].reshape(-1)].add(
                recv[..., 1].reshape(-1), mode="drop")
            return out[None]

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec_t, spec_b),
                               out_specs=spec_t, check_vma=False))
        _, t = timeit(fn, tbl, buf, reps=reps, warmup=1)
        return t

    if op == "all_to_all_tiles@fft.transpose":
        # pencil-FFT global transpose on the ring: the signal-gathering
        # exchange, the local full-signal FFT, and the inverse scatter
        # back-to-back — paired exchanges with the transform between them
        # (direction-symmetric, so one tag covers both directions).
        ns = max(elems // (2 * nranks), 1)  # complex64: 8 B per element
        x = jnp.asarray(np.ones((nranks, nranks, 1, ns), np.complex64))
        spec = P(names[0], None, None, None)

        def body(v):
            b = v[0]  # (B=nranks, 1, ns) local pencils
            g = engine.all_to_all_tiles(b, names[0], split_axis=0,
                                        concat_axis=1)
            s = jnp.fft.fft(g.reshape(g.shape[0], -1), axis=-1)
            s = s.reshape(g.shape)
            return engine.all_to_all_tiles(s, names[0], split_axis=1,
                                           concat_axis=0)[None]

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                               out_specs=spec, check_vma=False))
        _, t = timeit(fn, x, reps=reps, warmup=1)
        return t

    if op == "grid_transpose":
        pg = mesh.shape[names[0]]
        side = max(int(math.sqrt(elems)), 1)
        x = jnp.asarray(np.ones((nranks, side, side), np.float32))
        spec = P(tuple(names), None, None)
        body = (lambda v: engine.grid_transpose(v[0], tuple(names), pg)[None])
    elif op == "ring_exchange":
        x = jnp.asarray(np.ones((nranks, elems), np.float32))
        spec = P(names[0], None)
        body = (lambda v: engine.ring_exchange(v[0], v[0], names[0])[0][None])
    elif op == "bcast":
        x = jnp.asarray(np.ones((nranks, elems), np.float32))
        spec = P(names[0], None)
        body = (lambda v: engine.bcast(v[0], names[0], 0)[None])
    elif op == "allreduce":
        ax = tuple(names) if len(names) > 1 else names[0]
        x = jnp.asarray(np.ones((nranks, elems), np.float32))
        spec = P(tuple(names) if len(names) > 1 else names[0], None)
        body = (lambda v: engine.allreduce(v[0], ax)[None])
    else:  # all_to_all_tiles
        x = jnp.asarray(np.ones((nranks, nranks * max(elems // nranks, 1)),
                                np.float32))
        spec = P(names[0], None)
        body = (lambda v: engine.all_to_all_tiles(
            v[0], names[0], split_axis=0, concat_axis=0)[None])

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec,
                           check_vma=False))
    _, t = timeit(fn, x, reps=reps, warmup=1)
    return t


# callsite patterns that time *both* directions of a paired exchange: the
# measured winner applies to every tag of the pair
PAIRED_ALIASES: Dict[str, Tuple[str, ...]] = {
    "all_to_all_tiles@moe.dispatch": ("all_to_all_tiles@moe.combine",),
    "all_to_all_tiles@tp.qkv": ("all_to_all_tiles@tp.out",),
    "all_to_all_tiles@sp.qkv": ("all_to_all_tiles@sp.out",),
    "all_to_all_tiles@decode.qkv": ("all_to_all_tiles@decode.out",
                                    "all_to_all_tiles@decode.moe"),
}

# the per-token decode pattern is measured at decode-sized payloads (one
# token's q/k/v across the whole batch is a few KiB) instead of the
# training-sized default ladder — serving lives in the latency band
DECODE_SIZES = (1 << 8, 1 << 11, 1 << 14)
DECODE_SIZES_QUICK = (1 << 8, 1 << 12)

# callsite patterns measured on the square torus (HPL's row/column
# broadcasts); everything else — including the MoE paired exchange — runs
# on the all-device ring
_TORUS_OPS = ("grid_transpose", "bcast@hpl.panel")


def autotune_mesh(*, ops: Sequence[str] = ("bcast", "allreduce",
                                           "all_to_all_tiles",
                                           "ring_exchange", "grid_transpose",
                                           "bcast@hpl.panel",
                                           "all_to_all_tiles@moe.dispatch",
                                           "all_to_all_tiles@tp.qkv",
                                           "all_to_all_tiles@sp.qkv",
                                           "all_to_all_tiles@decode.qkv",
                                           "all_to_all_tiles@ra.updates",
                                           "all_to_all_tiles@fft.transpose"),
                  sizes: Optional[Sequence[int]] = None, reps: int = 3,
                  quick: bool = False, verbose: bool = True
                  ) -> Tuple[TuningTable, Dict]:
    """Measure every registered exact schedule per op on the live devices and
    build a :class:`TuningTable` of per-size winners.

    Ring ops run over a ring of all devices; ``grid_transpose`` over the
    largest square torus. An ``op@callsite`` entry measures the op inside
    that callsite's pattern and lands under the tagged tuning-table key,
    consulted first when the engine resolves with the matching callsite:
    ``"bcast@hpl.panel"`` times HPL's panel bcast back-to-back with the
    diagonal-block bcast on the torus row axis, and
    ``"all_to_all_tiles@moe.dispatch"`` times the MoE dispatch exchange,
    a stand-in expert FFN, and the inverse combine exchange back-to-back on
    the ring (the winner lands under both ``@moe.dispatch`` and
    ``@moe.combine`` — the pattern is direction-symmetric). The whole-model
    attention patterns measure the same way: ``"all_to_all_tiles@tp.qkv"``
    times the q/k/v head-gathering burst plus the inverse batch-restoring
    exchange (winner aliased to ``@tp.out``), and
    ``"all_to_all_tiles@sp.qkv"`` the seq-gathering exchanges interleaved
    with the ring-attention kv hops (winner aliased to ``@sp.out``; the
    hops themselves fall back to the untagged ``ring_exchange`` entry).
    ``"all_to_all_tiles@decode.qkv"`` times one serving decode step's
    six-exchange burst (q/k/v head gathers, paged attention, inverse, MoE
    dispatch/combine) at decode-sized payloads — its own size ladder
    (:data:`DECODE_SIZES`), since per-token messages sit far below the
    training sizes; the winner lands under ``@decode.out`` and
    ``@decode.moe`` too. ``"all_to_all_tiles@ra.updates"`` times the GUPS
    bucketed int32 update exchange plus the receiving scatter-add, and
    ``"all_to_all_tiles@fft.transpose"`` the pencil-FFT gather / local
    transform / inverse-scatter sandwich (both on the ring; each tag keys
    its own entry — no alias). Returns ``(table, record)`` where ``record``
    holds the raw per-(op, schedule, size) timings for the bench
    artifact."""
    import jax

    from repro.comm.engine import schedules_for
    from repro.comm.topology import MeshTopology
    from repro.launch.mesh import make_mesh

    default_sizes = sizes is None
    if sizes is None:
        sizes = ((1 << 10, 1 << 16) if quick
                 else (1 << 10, 1 << 14, 1 << 18, 1 << 22))
    reps = 2 if quick else reps

    ndev = len(jax.devices())
    ring = make_mesh((ndev,), ("x",))
    pg = int(math.isqrt(ndev))
    torus = make_mesh((pg, pg), ("rows", "cols")) if pg >= 2 else None

    table = TuningTable(meta={"devices": ndev, "sizes": list(sizes),
                              "backend": jax.default_backend()})
    record: Dict[str, Dict] = {}
    for op in ops:
        base_op = op.split("@", 1)[0]
        mesh = torus if op in _TORUS_OPS else ring
        if mesh is None:
            continue
        topo = MeshTopology.from_mesh(mesh)
        if "@" in op:
            # callsite patterns are measured along one axis; the HPL pattern
            # is row/column-symmetric, so the winner is stored under every
            # single-axis signature (the l_panel bcast on "cols", sig
            # torus_col[pg], matches too). On the ring there is one axis.
            sig = axis_signature([topo.axis(topo.names()[0])])
            extra_sigs = [axis_signature([topo.axis(a)])
                          for a in topo.names()[1:]]
        else:
            sig = axis_signature([topo.axis(a) for a in topo.names()])
            extra_sigs = []
        names = [s for s in schedules_for(base_op)
                 if s not in LOSSY_SCHEDULES]
        op_sizes = sizes
        if default_sizes and op.endswith("@decode.qkv"):
            op_sizes = DECODE_SIZES_QUICK if quick else DECODE_SIZES
        winners, measured_sizes = [], []
        for S in op_sizes:
            times = {}
            for name in names:
                try:
                    times[name] = _measure_op(mesh, op, S, name, reps)
                except Exception as e:  # noqa: BLE001 — skip broken combos
                    if verbose:
                        print(f"  [autotune] {op}/{name}@{S}B failed: {e}")
            if not times:
                continue  # winners stay aligned with measured_sizes
            best = min(sorted(times), key=times.get)
            winners.append(best)
            measured_sizes.append(S)
            record[f"{op}/{sig}/{S}"] = {"winner": best, "times_s": times}
            if verbose:
                ladder = " ".join(f"{n}={times[n]*1e3:.2f}ms"
                                  for n in sorted(times))
                print(f"  [autotune] {op:16s} {S:>9d}B -> {best:8s} ({ladder})")
        if winners:
            bounds = _winner_bounds(measured_sizes, winners)
            for key in (op,) + PAIRED_ALIASES.get(op, ()):
                for s in [sig] + extra_sigs:
                    table.set(key, s, bounds)
    return table, record
