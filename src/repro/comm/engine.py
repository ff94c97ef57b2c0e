"""Collective engine: named communication schedules behind one API.

The paper's core architecture is one set of benchmark kernels running over
interchangeable communication paths (circuit-switched inter-FPGA links vs
host-staged PCIe+MPI). ACCL-style engines show the productive way to express
that: a *schedule registry* — every collective op has named implementations
("schedules") registered against it, and a :class:`CollectiveEngine` selects
one per op from ``(CommunicationType, schedule name)`` plus per-axis topology
metadata (:class:`repro.comm.topology.MeshTopology`). Callers hold an engine
and never branch on comm/schedule themselves. ``schedule="auto"`` resolves
per callsite through the :mod:`repro.comm.autotune` cost model from the
payload size and axis topology (measured tuning table first, analytic
alpha-beta ranking otherwise).

Ops
---
``bcast(val, axis, src)``                     one-to-all along a ring/torus dim
``all_to_all_tiles(x, axis, split/concat)``   PTRANS / MoE dispatch exchange
``allreduce(x, axis)``                        gradient / scalar reduction
``allreduce_tree(tree, axis, bucket_bytes)``  bucketed pytree reduction — the
                                              overlap structure of the paper's
                                              Fig. 5/7 applied to gradients
``ring_exchange(fwd, bwd, axis)``             b_eff bidirectional neighbor swap
``grid_transpose(x, axes, pg)``               PTRANS partner exchange on a torus
``pipelined(op, x, axis, nchunks=...)``       software-pipelining transform:
                                              split any single-payload op
                                              (bcast / allreduce /
                                              grid_transpose /
                                              all_to_all_tiles) into S
                                              in-flight chunks whose
                                              per-chunk consumer compute
                                              overlaps the next chunk's wire
                                              hops (chunk count from the
                                              autotune fill-cost model)

Schedules
---------
``chain``   paper-faithful store-and-forward: hop-by-hop ``ppermute`` rounds
            (the CSN network kernels of Figs. 2/6/8).
``native``  XLA's native collective — all torus links, both directions.
``staged``  host-staged analogue: every byte transits the staging domain
            (all_gather + local select). Forced whenever the engine's comm
            type is ``HOST_STAGED``.
``ring2d``  torus-aware two-phase ring schedules: bcast = scatter +
            ring all-gather (2(n-1)/n wire vs chain's (n-1)); allreduce =
            per-torus-dimension ring reduce-scatter/all-gather, applied
            row-then-column for tuple axes; grid_transpose = dimension-
            ordered row-hop-then-column-hop route to the transpose partner
            (paper Fig. 8's two-phase torus route).
``rs_ag``   bandwidth-optimal ring reduce-scatter + all-gather allreduce;
            the per-hop accumulate is the Pallas-fused step in
            :mod:`repro.kernels.ring`.
``int8_ef`` int8 block-quantized allreduce wire format riding the ``rs_ag``
            ring, with the per-hop requantization residual carried alongside
            the payload (error feedback *inside* the ring; cross-step error
            feedback is carried by the caller — see
            :func:`repro.comm.compression.compressed_psum`).
``direct``  point-to-point ``ppermute`` (ring_exchange / grid_transpose).
``chain_rooted``  the dead-link escape route for bcast/allreduce: a
            bidirectional store-and-forward chain rooted so neither arm
            crosses the ring's cut hop (the hard-down link in the cost
            model's health mask; the wraparound hop when clean). Priced
            at 2x chain so it never wins on a healthy ring — resolution
            falls through to it when a down link prices everything else
            infinite (and to ``staged``, which touches no ICI link, when
            even the rooted chain cannot avoid the break).

Registering a new schedule::

    from repro.comm.engine import register_schedule

    @register_schedule("allreduce", "mytree")
    def _allreduce_mytree(engine, x, axis):
        ...  # runs inside shard_map; use lax/ppermute freely
        return reduced

    CollectiveEngine(schedule="mytree").allreduce(x, "x")

All schedule bodies run inside ``shard_map``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size

from repro.comm.overlap import DEFAULT_BUCKET_BYTES, pack_buckets
from repro.comm.topology import MeshTopology, ring_perm, transpose_perm
from repro.comm.types import CommunicationType, comm_type
from repro.scopes import ENGINE_OPS, engine_scope

OPS: Tuple[str, ...] = ("bcast", "all_to_all_tiles", "allreduce",
                        "ring_exchange", "grid_transpose")

_REGISTRY: Dict[str, Dict[str, Callable]] = {op: {} for op in OPS}

# static per-op fallbacks for schedule="auto" — used only when the cost
# model has nothing to go on (no topology, no payload size, unknown axis);
# with both available, auto resolves through repro.comm.autotune per callsite
_AUTO = {
    "bcast": "chain",
    "all_to_all_tiles": "native",
    "allreduce": "native",
    "ring_exchange": "direct",
    "grid_transpose": "direct",
}


def _payload_bytes(x) -> Optional[int]:
    """Static byte size of an array/tracer (shapes are static under jit)."""
    try:
        return int(x.size) * jnp.dtype(x.dtype).itemsize
    except (TypeError, AttributeError):
        return None


def _scoped(method):
    """Run an engine op's lowering under its scope,
    ``comm.<op>:<callsite>`` (:func:`repro.scopes.engine_scope`), so a
    profile attributes the op's device time to the op and its callsite."""
    op = method.__name__
    assert op in ENGINE_OPS, op

    @functools.wraps(method)
    def run(self, *args, callsite: Optional[str] = None, **kw):
        with jax.named_scope(engine_scope(op, callsite)):
            return method(self, *args, callsite=callsite, **kw)
    return run


class UnknownScheduleError(ValueError):
    """Raised for a schedule name no op has registered."""


def register_schedule(op: str, name: str):
    """Decorator: register ``fn(engine, *args, **kw)`` as schedule ``name``
    for collective ``op``."""
    if op not in OPS:
        raise ValueError(f"unknown collective op {op!r}; ops are {OPS}")

    def deco(fn):
        _REGISTRY[op][name] = fn
        return fn
    return deco


def schedules_for(op: str) -> Tuple[str, ...]:
    """Registered schedule names for ``op``, sorted."""
    return tuple(sorted(_REGISTRY[op]))


def known_schedules() -> Tuple[str, ...]:
    names = {"auto"}
    for op in OPS:
        names.update(_REGISTRY[op])
    return tuple(sorted(names))


# ---------------------------------------------------------------------------
# shared ring helpers (shard_map-body level)
# ---------------------------------------------------------------------------


def _ring_shift(x, axis, shift=1):
    n = axis_size(axis)
    return lax.ppermute(x, axis, ring_perm(n, shift))


def _pack_chunks(x, n):
    """Flatten + zero-pad ``x`` into an (n, L) chunk stack."""
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(n, -1)


def _chunk(stack, k):
    """Chunk ``k`` (traced ok) of an (n, L) stack."""
    return jnp.squeeze(lax.dynamic_slice_in_dim(stack, k, 1, 0), 0)


def _set_chunk(stack, k, val):
    return lax.dynamic_update_slice(stack, val[None], (k, 0))


def _fused_add(acc, recv):
    if jnp.issubdtype(acc.dtype, jnp.floating):
        from repro.kernels.ring import fused_chunk_add
        return fused_chunk_add(acc, recv)
    return acc + recv


# ---------------------------------------------------------------------------
# bcast schedules
# ---------------------------------------------------------------------------


@register_schedule("bcast", "chain")
def _bcast_chain(engine, val, axis, src):
    # (n-1)-hop store-and-forward pipeline: after k hops ranks src..src+k
    # hold the value (the paper's network-kernel forwarding).
    n = axis_size(axis)
    idx = lax.axis_index(axis)
    out = val
    for _ in range(n - 1):
        nxt = _ring_shift(out, axis, +1)
        out = jnp.where(idx == src, out, nxt)
    return out


@register_schedule("bcast", "native")
@register_schedule("bcast", "staged")
def _bcast_gather(engine, val, axis, src):
    # all_gather + select: (n-1)/n wire vs the masked-psum broadcast's
    # 2(n-1)/n; non-source ranks may hold inf/nan garbage (speculative local
    # factorizations), so a psum would need a zero-mask anyway. Under
    # HOST_STAGED this is also the staging-domain route: every byte transits
    # the gather.
    allv = lax.all_gather(val, axis)
    return jnp.take(allv, src, axis=0)


def _cut_hop(engine, axis, n: int) -> int:
    """The hop the rooted chain must not cross — static at trace time.

    The smallest hard-down hop of ``axis`` in the engine's cost-model
    health mask (:meth:`repro.comm.autotune.CostModel`), else the
    wraparound hop ``n-1``: a clean rooted chain simply avoids the
    wraparound wire. With several down hops on one axis the chain can
    only avoid the first; the others stay in its priced route, so
    resolution never picks it there (:func:`repro.comm.autotune
    .route_links`)."""
    model = engine._model()
    health = getattr(model, "health", None) or frozenset()
    down = sorted(h for (a, h) in health if a == axis)
    return down[0] if down else n - 1


@register_schedule("bcast", "chain_rooted")
def _bcast_chain_rooted(engine, val, axis, src):
    # Bidirectional chain rooted at ``src``, re-indexed so path position 0
    # sits just past the cut and position n-1 just before it: the forward
    # arm relays src -> tail, the backward arm src -> head, and the masks
    # make the two cut-crossing adoptions impossible (pos 0 never takes a
    # forward hop, pos n-1 never a backward one) — so no adopted value
    # ever traversed the down link, provably.
    n = axis_size(axis)
    if n == 1:
        return val
    cut = _cut_hop(engine, axis, n)
    idx = lax.axis_index(axis)
    pos = (idx - (cut + 1)) % n
    spos = (src - (cut + 1)) % n
    f = b = val
    for _ in range(n - 1):
        nf = _ring_shift(f, axis, +1)
        f = jnp.where(pos > spos, nf, f)
        nb = _ring_shift(b, axis, -1)
        b = jnp.where(pos < spos, nb, b)
    return jnp.where(pos < spos, b, f)


@register_schedule("bcast", "ring2d")
def _bcast_ring2d(engine, val, axis, src):
    # torus-aware two-phase ring bcast (scatter + ring all-gather): the
    # value is split into n chunks; the scatter pipeline injects chunk d at
    # step n-1-d so every chunk reaches its owner by step n-2, then a ring
    # all-gather circulates the owned chunks. Wire: 2(n-1)/n of the payload
    # per link vs chain's (n-1) — each of HPL's row/column broadcasts uses
    # only its own torus dimension, so both dimensions stream concurrently.
    n = axis_size(axis)
    if n == 1:
        return val
    idx = lax.axis_index(axis)
    chunks = _pack_chunks(val, n)
    L = chunks.shape[1]
    dist = (idx - src) % n

    # phase 1 — scatter: src injects chunks n-1, n-2, ..., 0; everyone else
    # forwards. After step s, the rank at distance d carries the chunk src
    # injected at step s-(d-1); at the final step that is chunk d.
    carry = _chunk(chunks, (n - 1) % n)
    for s in range(n - 1):
        recv = _ring_shift(carry, axis, +1)
        inject = _chunk(chunks, (n - 2 - s) % n)
        carry = jnp.where(idx == src, inject, recv)
    own = jnp.where(dist == 0, _chunk(chunks, 0), carry)

    # phase 2 — ring all-gather of the owned chunks
    out = jnp.zeros((n, L), val.dtype)
    out = _set_chunk(out, dist, own)
    cur = own
    for s in range(n - 1):
        cur = _ring_shift(cur, axis, +1)
        out = _set_chunk(out, (dist - 1 - s) % n, cur)
    return out.reshape(-1)[: val.size].reshape(val.shape)


# ---------------------------------------------------------------------------
# all_to_all_tiles schedules
# ---------------------------------------------------------------------------


@register_schedule("all_to_all_tiles", "native")
def _a2a_native(engine, x, axis, *, split_axis, concat_axis):
    return lax.all_to_all(x, axis, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


@register_schedule("all_to_all_tiles", "chain")
def _a2a_chain(engine, x, axis, *, split_axis, concat_axis):
    # n-1 ppermute rounds, one ring distance per round (paper CSN schedule:
    # every tile travels hop-by-hop through the ring).
    n = axis_size(axis)
    idx = lax.axis_index(axis)
    chunk = x.shape[split_axis] // n
    received = []
    for dist in range(n):
        # the tile this rank owes the rank ``dist`` hops to its right is
        # split index (idx + dist) mod n; forward it ``dist`` hops.
        send = lax.dynamic_slice_in_dim(
            x, ((idx + dist) % n) * chunk, chunk, split_axis)
        recv = send
        for _ in range(dist):
            recv = _ring_shift(recv, axis, +1)
        received.append(recv)  # tile from source rank (idx - dist) mod n
    stacked = jnp.stack(received, axis=0)  # indexed by dist
    # output position s holds the tile from source s = (idx - dist) mod n,
    # i.e. dist = (idx - s) mod n
    perm = (idx - jnp.arange(n)) % n
    by_src = jnp.take(stacked, perm, axis=0)
    return jnp.concatenate([by_src[s] for s in range(n)], axis=concat_axis)


@register_schedule("all_to_all_tiles", "staged")
def _a2a_staged(engine, x, axis, *, split_axis, concat_axis):
    # every byte transits the staging domain (gather + local slice)
    n = axis_size(axis)
    idx = lax.axis_index(axis)
    chunk = x.shape[split_axis] // n
    gathered = lax.all_gather(x, axis)  # (n, ...): every rank's buffer
    outs = []
    for s in range(n):  # tile ``idx`` from each source rank s, in order
        row = jnp.squeeze(lax.dynamic_slice_in_dim(gathered, s, 1, 0), 0)
        outs.append(lax.dynamic_slice_in_dim(row, idx * chunk, chunk,
                                             split_axis))
    return jnp.concatenate(outs, axis=concat_axis)


# ---------------------------------------------------------------------------
# allreduce schedules
# ---------------------------------------------------------------------------


@register_schedule("allreduce", "native")
def _allreduce_native(engine, x, axis):
    return lax.psum(x, axis)


@register_schedule("allreduce", "chain")
def _allreduce_chain(engine, x, axis):
    # ring reduce: n-1 full-payload hops, paper-style store-and-forward
    n = axis_size(axis)
    acc = x
    buf = x
    for _ in range(n - 1):
        buf = _ring_shift(buf, axis, +1)
        acc = acc + buf
    return acc


@register_schedule("allreduce", "chain_rooted")
def _allreduce_chain_rooted(engine, x, axis):
    # Dead-link allreduce: reduce along the open path to its head, then
    # chain-broadcast the total back. Path position 0 sits just past the
    # cut (see _cut_hop); backward shifts bring pos p the payload of pos
    # p+r, masked to zero whenever p+r walked off the path end — i.e.
    # whenever that contribution would have crossed the down link — so
    # the head's accumulator is the exact left-to-right path-order sum
    # and nothing adopted ever traversed the cut. The return broadcast is
    # the forward arm of the rooted chain (pos 0 never adopts), leaving
    # every rank with the head's bitwise-identical total.
    if isinstance(axis, (tuple, list)):
        for ax in axis:
            x = _allreduce_chain_rooted(engine, x, ax)
        return x
    n = axis_size(axis)
    if n == 1:
        return x
    cut = _cut_hop(engine, axis, n)
    idx = lax.axis_index(axis)
    pos = (idx - (cut + 1)) % n
    zeros = jnp.zeros_like(x)
    acc = x
    buf = x
    for r in range(1, n):
        buf = _ring_shift(buf, axis, -1)
        acc = acc + jnp.where(pos + r <= n - 1, buf, zeros)
    f = acc
    for _ in range(n - 1):
        nf = _ring_shift(f, axis, +1)
        f = jnp.where(pos > 0, nf, f)
    return f


@register_schedule("allreduce", "staged")
def _allreduce_staged(engine, x, axis):
    return jnp.sum(lax.all_gather(x, axis), axis=0)


@register_schedule("allreduce", "rs_ag")
def _allreduce_rs_ag(engine, x, axis):
    # bandwidth-optimal ring allreduce: reduce-scatter then all-gather,
    # 2(n-1)/n of the payload per link. The per-hop accumulate is the
    # Pallas-fused step (repro.kernels.ring) — receive buffer and local
    # chunk stream through VMEM once.
    if isinstance(axis, (tuple, list)):
        # torus: one ring pass per dimension (row-then-column)
        for ax in axis:
            x = _allreduce_rs_ag(engine, x, ax)
        return x
    n = axis_size(axis)
    if n == 1:
        return x
    idx = lax.axis_index(axis)
    stack = _pack_chunks(x, n)

    # reduce-scatter: step s sends chunk (idx-s) right, accumulates the
    # incoming chunk (idx-1-s). After n-1 steps rank i owns chunk (i+1)%n.
    for s in range(n - 1):
        send = _chunk(stack, (idx - s) % n)
        recv = _ring_shift(send, axis, +1)
        local = _chunk(stack, (idx - 1 - s) % n)
        stack = _set_chunk(stack, (idx - 1 - s) % n,
                           _fused_add(local, recv))

    # all-gather: circulate the owned chunk around the ring
    cur = _chunk(stack, (idx + 1) % n)
    for s in range(n - 1):
        cur = _ring_shift(cur, axis, +1)
        stack = _set_chunk(stack, (idx - s) % n, cur)
    return stack.reshape(-1)[: x.size].reshape(x.shape)


@register_schedule("allreduce", "ring2d")
def _allreduce_ring2d(engine, x, axis):
    # torus-aware row/column schedule: a ring reduce-scatter/all-gather per
    # torus dimension. For a single axis this is exactly rs_ag.
    return _allreduce_rs_ag(engine, x, axis)


@register_schedule("allreduce", "int8_ef")
def _allreduce_int8_ef(engine, x, axis):
    # int8 block-quantized wire format over the bandwidth-optimal ring, with
    # the quantization applied *per ring chunk, per hop* and the per-hop
    # requantization residual carried ALONGSIDE the payload: every ppermute
    # moves the int8 chunk plus the int8-quantized residual of that same
    # quantization (2 bytes/elem + 8/BLOCK bytes/elem of scales per hop),
    # never a whole-bucket fp32 buffer. The receiver reconstructs
    # payload + residual, so the error each hop leaks is only the residual's
    # *own* requantization — second-order, O(1/127^2) of the chunk magnitude
    # per hop — tightening the lossy bound from O(hops/127) to O(hops/127^2)
    # ~ O(1/127) overall (the ROADMAP in-ring error-feedback item).
    # Reduce-scatter hops quantize the outgoing partial-sum chunk right
    # before the shift; the all-gather half quantizes each owner's reduced
    # chunk (and its residual) once and forwards both int8 payloads
    # unchanged around the ring, so all ranks agree bitwise. Accumulation
    # stays in fp32 via the fused Pallas step. Exact whenever every hop's
    # chunk is block-representable — see tests/dist/test_overlap.py. The
    # schedule stays stateless: error feedback *across steps* is carried by
    # the caller, see :func:`repro.comm.compression.compressed_psum`.
    from repro.comm.compression import dequantize_ef, quantize_ef
    if isinstance(axis, (tuple, list)):
        for ax in axis:
            x = _allreduce_int8_ef(engine, x, ax)
        return x
    n = axis_size(axis)
    if n == 1:
        return x
    idx = lax.axis_index(axis)
    stack = _pack_chunks(x.astype(jnp.float32), n)

    def _shift_q(chunk):
        # one ring hop of the quantized wire format: payload chunk plus its
        # requantization-residual chunk travel together
        wire = quantize_ef(chunk)
        wire = tuple(_ring_shift(w, axis, +1) for w in wire)
        return dequantize_ef(*wire, chunk.shape, chunk.size)

    # reduce-scatter: same chunk walk as rs_ag, int8+residual per hop
    for s in range(n - 1):
        send = _chunk(stack, (idx - s) % n)
        recv = _shift_q(send)
        local = _chunk(stack, (idx - 1 - s) % n)
        stack = _set_chunk(stack, (idx - 1 - s) % n,
                           _fused_add(local, recv))

    # all-gather: quantize the owned chunk (and its residual) once; every
    # rank (owner included) keeps the reconstructed wire value so all ranks
    # agree bitwise
    own = _chunk(stack, (idx + 1) % n)
    wire = quantize_ef(own)
    stack = _set_chunk(stack, (idx + 1) % n,
                       dequantize_ef(*wire, own.shape, own.size))
    for s in range(n - 1):
        wire = tuple(_ring_shift(w, axis, +1) for w in wire)
        stack = _set_chunk(stack, (idx - s) % n,
                           dequantize_ef(*wire, own.shape, own.size))
    return stack.reshape(-1)[: x.size].reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# ring_exchange schedules
# ---------------------------------------------------------------------------


@register_schedule("ring_exchange", "direct")
@register_schedule("ring_exchange", "chain")
def _exchange_direct(engine, x_fwd, x_bwd, axis):
    # one circuit-switched hop in each direction (b_eff message pattern)
    recv_l = _ring_shift(x_fwd, axis, +1)  # left neighbor's fwd buffer
    recv_r = _ring_shift(x_bwd, axis, -1)  # right neighbor's bwd buffer
    return recv_l, recv_r


@register_schedule("ring_exchange", "staged")
def _exchange_staged(engine, x_fwd, x_bwd, axis):
    # both buffers transit the staging domain (gather + select)
    n = axis_size(axis)
    idx = lax.axis_index(axis)
    all_f = lax.all_gather(x_fwd, axis)  # (n, ...)
    all_b = lax.all_gather(x_bwd, axis)
    recv_l = jnp.take(all_f, (idx - 1) % n, axis=0)
    recv_r = jnp.take(all_b, (idx + 1) % n, axis=0)
    return recv_l, recv_r


# ---------------------------------------------------------------------------
# grid_transpose schedules (PTRANS partner exchange)
# ---------------------------------------------------------------------------


@register_schedule("grid_transpose", "direct")
@register_schedule("grid_transpose", "chain")
def _transpose_direct(engine, x, axes, pg):
    # pure point-to-point circuit-switched exchange with the grid-transpose
    # partner (paper §2.2.2)
    return lax.ppermute(x, axes, transpose_perm(pg))


@register_schedule("grid_transpose", "staged")
def _transpose_staged(engine, x, axes, pg):
    # all_gather over the full grid + local selection: every block transits
    # the staging domain (paper §2.2.1 via PCIe+MPI)
    row_ax, col_ax = axes
    g = lax.all_gather(x, axes)  # (P*P, ...)
    r = lax.axis_index(row_ax)
    c = lax.axis_index(col_ax)
    return jnp.squeeze(lax.dynamic_slice_in_dim(g, c * pg + r, 1, 0), 0)


@register_schedule("grid_transpose", "ring2d")
def _transpose_ring2d(engine, x, axes, pg):
    # dimension-ordered two-phase torus route (paper Fig. 8): the block from
    # (r, c) reaches its transpose partner (c, r) over row links only, then
    # column links only, relayed by the diagonal rank (r, r) — the common
    # intermediate of every (r, *) -> (*, r) route.
    #
    # Phase 1 (row hops): hop-by-hop ring all-gather along the column axis,
    # so each diagonal rank ends up holding all of its grid row. Phase 2
    # (column hops): chain-forward the relay stack down each column from its
    # diagonal rank; rank (r, c) finally keeps the block whose source is
    # (c, r). Wire: (pg-1) unit-block row hops + (pg-1) stacked column hops,
    # vs ``direct``'s single (XLA-routed) partner ppermute.
    row_ax, col_ax = axes
    if pg == 1:
        return x
    r = lax.axis_index(row_ax)
    c = lax.axis_index(col_ax)
    zeros = (0,) * x.ndim

    stack = jnp.zeros((pg,) + x.shape, x.dtype)
    stack = lax.dynamic_update_slice(stack, x[None], (c,) + zeros)
    cur = x
    for s in range(pg - 1):
        cur = _ring_shift(cur, col_ax, +1)  # now from column (c - 1 - s)
        stack = lax.dynamic_update_slice(stack, cur[None],
                                         ((c - 1 - s) % pg,) + zeros)

    # each column's ring runs the chain independently; src row index == c
    out = stack
    for _ in range(pg - 1):
        nxt = _ring_shift(out, row_ax, +1)
        out = jnp.where(r == c, out, nxt)
    return jnp.squeeze(lax.dynamic_slice(out, (r,) + zeros, (1,) + x.shape), 0)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CollectiveEngine:
    """Selects one registered schedule per collective op.

    ``comm``      the paper's Fig. 1 backend selector. ``HOST_STAGED`` forces
                  the ``staged`` schedule for every op (all bytes through the
                  staging domain), matching the paper's PCIe+MPI bitstreams.
    ``schedule``  a registered schedule name, or ``"auto"`` to resolve per
                  callsite through the cost model (:mod:`repro.comm.autotune`)
                  from the payload size and the axis topology — analytic
                  alpha-beta ranking overlaid with the measured tuning table
                  when ``results/tuning.json`` exists. Without topology or
                  payload information auto falls back to static per-op
                  defaults. A name registered for *some* ops only (e.g.
                  ``chain`` has no dedicated ring_exchange variant) resolves
                  like auto for the uncovered ops — so ``--schedule chain``
                  applies suite-wide without per-op plumbing.
    ``topology``  optional :class:`MeshTopology` for axis validation, cost-
                  model resolution, and result provenance (``describe()``).
    ``cost_model`` optional explicit :class:`repro.comm.autotune.CostModel`;
                  None uses the process-wide default (analytic + persisted
                  tuning table).
    """
    comm: CommunicationType = CommunicationType.ICI_DIRECT
    schedule: str = "auto"
    topology: Optional[MeshTopology] = None
    cost_model: Optional[object] = None

    def __post_init__(self):
        object.__setattr__(self, "comm", comm_type(self.comm))
        if self.schedule != "auto" and self.schedule not in known_schedules():
            raise UnknownScheduleError(
                f"unknown schedule {self.schedule!r}; registered schedules "
                f"are {sorted(known_schedules())}")

    @classmethod
    def for_mesh(cls, mesh, comm=CommunicationType.ICI_DIRECT,
                 schedule: str = "auto", **kw) -> "CollectiveEngine":
        return cls(comm=comm_type(comm), schedule=schedule,
                   topology=MeshTopology.from_mesh(mesh), **kw)

    # -- schedule resolution ------------------------------------------------

    def schedule_for(self, op: str, override: Optional[str] = None, *,
                     nbytes: Optional[int] = None, axis=None,
                     callsite: Optional[str] = None) -> str:
        """The schedule name this engine runs ``op`` with.

        With ``nbytes`` (message payload) and ``axis`` (a topology axis name
        or tuple), ``auto`` resolves through the cost model; without them it
        falls back to the static per-op default, so provenance queries keep
        working outside any callsite. The returned name is always a
        registered schedule, never the literal ``"auto"`` — benchmarks call
        this with the per-callsite payload to *report* what actually ran.

        ``callsite`` is an optional tag from the central registry
        (:mod:`repro.comm.callsites` — ``"hpl.panel"``, ``"moe.dispatch"``,
        ``"tp.qkv"``, ``"dp.grads"``, ...) letting measured tuning-table
        entries distinguish call patterns: HPL's back-to-back bcasts tune
        independently of an isolated bcast, and the paired attention
        exchanges inherit the entry measured for their forward tag (the
        ``PAIRED_ALIASES`` mapping in :mod:`repro.comm.autotune`).

        An explicit ``override`` must be registered for ``op``
        (:class:`UnknownScheduleError` otherwise — checked before the
        HOST_STAGED short-circuit so typos fail under every comm type);
        HOST_STAGED always resolves to ``"staged"``; an engine-wide name
        that does not cover ``op`` falls back to auto-resolution rather
        than erroring, so one engine can drive ops with disjoint schedule
        sets."""
        if op not in OPS:
            raise ValueError(f"unknown collective op {op!r}; ops are {OPS}")
        if override is not None and override != "auto" \
                and override not in _REGISTRY[op]:
            # explicit per-call override must exist for the op — checked
            # before the HOST_STAGED short-circuit so a typo'd override
            # fails under every comm type, not only ICI_DIRECT
            raise UnknownScheduleError(
                f"schedule {override!r} is not registered for op {op!r}; "
                f"available: {sorted(_REGISTRY[op])}")
        if self.comm is CommunicationType.HOST_STAGED:
            return "staged"
        name = override or self.schedule
        if name != "auto" and name in _REGISTRY[op]:
            return name
        # "auto", or an engine-wide name that doesn't cover this op
        return self._auto_choice(op, nbytes, axis, callsite)

    def _axes_for(self, axis) -> Optional[Tuple]:
        if axis is None or self.topology is None:
            return None
        try:
            names = axis if isinstance(axis, (tuple, list)) else (axis,)
            return tuple(self.topology.axis(a) for a in names)
        except KeyError:
            return None

    def _model(self):
        if self.cost_model is not None:
            return self.cost_model
        from repro.comm.autotune import default_cost_model
        return default_cost_model()

    def invalidate_resolutions(self, *, table=None, hw=None,
                               health=None) -> None:
        """Drop every memoized ``(op, nbytes, axis, callsite)`` resolution
        so the next ``schedule="auto"`` lookup re-prices — the adaptive
        retune hook (:mod:`repro.comm.retune`).

        ``table`` optionally swaps a refreshed
        :class:`~repro.comm.autotune.TuningTable` into the cost model first
        (an in-run re-measurement); ``hw`` swaps the
        :class:`~repro.comm.types.HardwareModel` the analytic ranking
        prices on (a degraded-link view from
        :meth:`repro.comm.faults.FaultInjector.hardware_view`); ``health``
        swaps the link-health mask (``(axis, hop)`` pairs that are hard
        down, from :meth:`repro.comm.faults.FaultInjector.down_links` —
        pass ``frozenset()`` to declare every link healthy again), so
        resolution excludes any route crossing a down link. Mutates the
        engine's cost model — the process default when no explicit
        ``cost_model`` was given — never the frozen engine, so in-flight
        references stay valid. Already-traced jitted programs keep the
        schedule they were traced with; the swap lands on the next trace.
        """
        model = self._model()
        if table is not None:
            model.table = table
        if hw is not None:
            model.hw = hw
        if health is not None:
            model.health = frozenset(health)
        model._cache.clear()

    def _auto_choice(self, op: str, nbytes: Optional[int], axis,
                     callsite: Optional[str] = None) -> str:
        """Cost-model resolution; static default when the model has nothing
        to price (no topology / payload / unknown axis)."""
        axes = self._axes_for(axis)
        if nbytes is None or axes is None:
            return _AUTO[op]
        choice = self._model().choose(op, int(nbytes), axes,
                                      callsite=callsite)
        if choice is not None and choice in _REGISTRY[op]:
            return choice
        return _AUTO[op]

    def _resolve(self, op: str, override: Optional[str], *,
                 nbytes: Optional[int] = None, axis=None,
                 callsite: Optional[str] = None) -> Callable:
        return _REGISTRY[op][self.schedule_for(op, override, nbytes=nbytes,
                                               axis=axis, callsite=callsite)]

    def pipeline_chunks(self, op: str, *, nbytes: Optional[int] = None,
                        axis=None, schedule: Optional[str] = None,
                        callsite: Optional[str] = None) -> int:
        """The chunk count ``pipelined`` resolves ``nchunks="auto"`` to:
        :func:`repro.comm.autotune.best_nchunks` on the resolved schedule's
        hop/wire decomposition — pipeline fill cost against per-chunk
        latency. 1 (monolithic) when the model has nothing to price."""
        axes = self._axes_for(axis)
        if nbytes is None or axes is None:
            return 1
        name = self.schedule_for(op, schedule, nbytes=nbytes, axis=axis,
                                 callsite=callsite)
        model = self._model()
        if hasattr(model, "best_nchunks"):  # CostModel: carries its own hw
            return model.best_nchunks(op, name, int(nbytes), axes)[0]
        from repro.comm.autotune import best_nchunks
        return best_nchunks(op, name, int(nbytes), axes)[0]

    def _check_axis(self, axis):
        if self.topology is None:
            return
        for name in (axis if isinstance(axis, (tuple, list)) else (axis,)):
            self.topology.axis(name)  # raises KeyError with the known axes

    # -- ops (all run inside shard_map bodies) ------------------------------

    @_scoped
    def bcast(self, val, axis, src, *, schedule: Optional[str] = None,
              callsite: Optional[str] = None):
        """Broadcast ``val`` from rank ``src`` (traced scalar ok) along
        ``axis``."""
        self._check_axis(axis)
        fn = self._resolve("bcast", schedule, nbytes=_payload_bytes(val),
                           axis=axis, callsite=callsite)
        return fn(self, val, axis, src)

    @_scoped
    def all_to_all_tiles(self, x, axis, *, split_axis: int, concat_axis: int,
                         schedule: Optional[str] = None,
                         callsite: Optional[str] = None):
        """Exchange tiles so rank i's j-th split lands on rank j, ordered by
        source rank on ``concat_axis``.

        ``x`` is cut into ``axis``-size equal tiles along ``split_axis``;
        the output concatenates the tiles received from ranks 0..n-1 along
        ``concat_axis``, so running the exchange again with the two axes
        swapped is an exact inverse — the round-trip every paired caller
        relies on (``@moe.dispatch``/``@moe.combine`` for the expert
        exchange, ``@tp.qkv``/``@tp.out`` and ``@sp.qkv``/``@sp.out`` for
        the whole-model attention reshardings; tags and owners in
        :mod:`repro.comm.callsites`). ``schedule`` must name a registered
        ``all_to_all_tiles`` schedule (else :class:`UnknownScheduleError`);
        ``None`` defers to the engine-wide resolution, with ``auto`` priced
        on this call's payload and ``callsite``-tagged table entries taking
        precedence."""
        self._check_axis(axis)
        fn = self._resolve("all_to_all_tiles", schedule,
                           nbytes=_payload_bytes(x), axis=axis,
                           callsite=callsite)
        return fn(self, x, axis, split_axis=split_axis,
                  concat_axis=concat_axis)

    @_scoped
    def allreduce(self, x, axis, *, schedule: Optional[str] = None,
                  callsite: Optional[str] = None):
        """Sum ``x`` over all ranks of ``axis`` (a name or tuple of names)."""
        self._check_axis(axis)
        fn = self._resolve("allreduce", schedule, nbytes=_payload_bytes(x),
                           axis=axis, callsite=callsite)
        return fn(self, x, axis)

    def bucket_bytes_for(self, axis) -> int:
        """Model-derived bucket size for :meth:`allreduce_tree` over
        ``axis``: pipeline depth x ring hops x per-hop latency-bandwidth
        product (:func:`repro.comm.autotune.derive_bucket_bytes`), replacing
        the former fixed 32 MiB constant. Falls back to that constant when
        the engine has no topology for ``axis``."""
        if self.topology is None:
            return DEFAULT_BUCKET_BYTES
        try:
            names = axis if isinstance(axis, (tuple, list)) else (axis,)
            axes = tuple(self.topology.axis(a) for a in names)
        except KeyError:
            return DEFAULT_BUCKET_BYTES
        from repro.comm.autotune import default_cost_model, derive_bucket_bytes
        model = self.cost_model
        hw = getattr(model, "hw", None) or default_cost_model().hw
        return derive_bucket_bytes(axes, hw)

    @_scoped
    def allreduce_tree(self, tree, axis, *,
                       bucket_bytes: Optional[int] = None,
                       schedule: Optional[str] = None,
                       callsite: Optional[str] = None):
        """Sum a pytree over ``axis`` in independent ~``bucket_bytes`` buckets.

        Leaves are greedily packed in order (reverse-mode autodiff emits
        gradients in backward order, so early buckets finish first); each
        bucket's same-dtype leaves are flattened into one payload and routed
        through the registered allreduce schedule. Independent buckets give
        XLA the paper's Fig. 5/7 overlap structure: reduction of finished
        buckets runs concurrently with the compute still producing later
        leaves. Zero-size leaves pass through untouched.

        ``bucket_bytes=None`` (default) derives the size from the topology
        and hardware model via :meth:`bucket_bytes_for`. ``callsite``
        (e.g. ``"dp.grads"``) tags every bucket's allreduce so measured
        tuning-table entries for the bucketed-gradient pattern win over the
        isolated-allreduce entry.
        """
        self._check_axis(axis)
        if bucket_bytes is None:
            bucket_bytes = self.bucket_bytes_for(axis)
        leaves, treedef = jax.tree.flatten(tree)
        out = list(leaves)
        for bucket in pack_buckets(leaves, bucket_bytes):
            groups: Dict = {}
            for i in bucket:
                if leaves[i].size:
                    groups.setdefault(jnp.dtype(leaves[i].dtype), []).append(i)
            for idxs in groups.values():
                flat = jnp.concatenate([leaves[i].reshape(-1) for i in idxs])
                red = self.allreduce(flat, axis, schedule=schedule,
                                     callsite=callsite)
                off = 0
                for i in idxs:
                    n = leaves[i].size
                    out[i] = red[off:off + n].reshape(leaves[i].shape)
                    off += n
        return jax.tree.unflatten(treedef, out)

    @_scoped
    def ring_exchange(self, x_fwd, x_bwd, axis, *,
                      schedule: Optional[str] = None,
                      callsite: Optional[str] = None):
        """Bidirectional neighbor exchange (b_eff pattern). Returns
        (recv_from_left, recv_from_right)."""
        self._check_axis(axis)
        fn = self._resolve("ring_exchange", schedule,
                           nbytes=_payload_bytes(x_fwd), axis=axis,
                           callsite=callsite)
        return fn(self, x_fwd, x_bwd, axis)

    @_scoped
    def grid_transpose(self, x, axes, pg: int, *,
                       schedule: Optional[str] = None,
                       callsite: Optional[str] = None):
        """Exchange with the (r,c)<->(c,r) partner on a ``pg`` x ``pg``
        torus flattened over ``axes`` (PTRANS §2.2.2)."""
        self._check_axis(axes)
        fn = self._resolve("grid_transpose", schedule,
                           nbytes=_payload_bytes(x), axis=axes,
                           callsite=callsite)
        return fn(self, x, axes, pg)

    # -- pipelined transform ------------------------------------------------

    @_scoped
    def pipelined(self, op: str, x, axis, *, nchunks="auto",
                  split_axis: int = 0, concat_axis: Optional[int] = None,
                  consume: Optional[Callable] = None,
                  schedule: Optional[str] = None,
                  callsite: Optional[str] = None, **opkw):
        """Software-pipeline any single-payload collective.

        ``x`` is split into ``nchunks`` near-equal strips along
        ``split_axis``; each strip routes through ``op``'s registered
        schedule *independently*, and ``consume(strip_out, start)`` (if
        given) is applied to each strip as it lands — the strips carry no
        data dependence on each other, so XLA overlaps strip i's consumer
        compute with strip i+1's wire hops (the chunked in-flight pipeline
        of the ACCL latency studies). Results are concatenated along
        ``concat_axis`` (default ``split_axis``; pass a different axis when
        ``consume`` reorients the strip, e.g. PTRANS's transpose-add).
        For ``all_to_all_tiles`` the strip axis indexes positions that ride
        along unchanged through the exchange (e.g. the MoE capacity slots),
        so the concatenated strips equal the monolithic exchange bitwise.

        ``nchunks="auto"`` resolves through :meth:`pipeline_chunks` (the
        alpha-beta fill-cost model); any value is clamped to the strip count
        available along ``split_axis``, so over-chunking degrades gracefully
        to one row per strip. ``nchunks=1`` is exactly the monolithic op —
        and every chunking is *bit-identical* to it for data-movement ops
        (bcast / grid_transpose / all_to_all_tiles), since chunk boundaries
        only partition the payload.

        Extra op operands ride ``opkw``: ``src=`` for bcast, ``pg=`` for
        grid_transpose, ``tile_split_axis=`` / ``tile_concat_axis=`` for
        all_to_all_tiles (the *tile* axes of the exchange, distinct from the
        pipeline's own ``split_axis``/``concat_axis`` strip axes — the strip
        axis must name a third axis, since slicing along a tile axis would
        change the tile boundaries the exchange moves).
        """
        supported = ("bcast", "allreduce", "grid_transpose",
                     "all_to_all_tiles")
        if op not in supported:
            raise ValueError(
                f"pipelined supports single-payload ops {supported}, "
                f"got {op!r}")
        required = {"bcast": ("src",), "grid_transpose": ("pg",),
                    "all_to_all_tiles": ("tile_split_axis",
                                         "tile_concat_axis")}.get(op, ())
        for name in required:
            if name not in opkw:
                raise ValueError(
                    f"pipelined({op!r}) requires the {name}= operand")
        if op == "all_to_all_tiles":
            tiles = {int(opkw["tile_split_axis"]) % x.ndim,
                     int(opkw["tile_concat_axis"]) % x.ndim}
            if int(split_axis) % x.ndim in tiles:
                raise ValueError(
                    "pipelined('all_to_all_tiles') strip split_axis "
                    f"{split_axis} collides with a tile axis {sorted(tiles)}; "
                    "strips must partition an axis the exchange leaves alone")
        self._check_axis(axis)
        size = x.shape[split_axis]
        nbytes = _payload_bytes(x)
        if nchunks == "auto":
            nchunks = self.pipeline_chunks(op, nbytes=nbytes, axis=axis,
                                           schedule=schedule,
                                           callsite=callsite)
        # resolve the schedule ONCE at the full payload: a per-strip
        # resolution could cross a cost-model / tuning-table band boundary
        # and run a different schedule than the one the chunk count was
        # priced for (and than callers record as provenance)
        resolved = self.schedule_for(op, schedule, nbytes=nbytes, axis=axis,
                                     callsite=callsite)
        s = max(min(int(nchunks), size), 1)
        base, extra = divmod(size, s)
        outs, start = [], 0
        for i in range(s):
            stop = start + base + (1 if i < extra else 0)
            strip = lax.slice_in_dim(x, start, stop, axis=split_axis)
            if op == "bcast":
                out = self.bcast(strip, axis, opkw["src"], schedule=resolved,
                                 callsite=callsite)
            elif op == "allreduce":
                out = self.allreduce(strip, axis, schedule=resolved,
                                     callsite=callsite)
            elif op == "all_to_all_tiles":
                out = self.all_to_all_tiles(
                    strip, axis, split_axis=opkw["tile_split_axis"],
                    concat_axis=opkw["tile_concat_axis"], schedule=resolved,
                    callsite=callsite)
            else:
                out = self.grid_transpose(strip, axis, opkw["pg"],
                                          schedule=resolved,
                                          callsite=callsite)
            if consume is not None:
                out = consume(out, start)
            outs.append(out)
            start = stop
        if len(outs) == 1:
            return outs[0]
        cat = split_axis if concat_axis is None else concat_axis
        return jnp.concatenate(outs, axis=cat)

    # -- provenance ---------------------------------------------------------

    def describe(self) -> Dict[str, object]:
        """Static record of what this engine runs, for benchmark results."""
        d = {
            "comm": self.comm.value,
            "schedule": self.schedule,
            # static (payload-free) resolution; callsites with a payload may
            # refine these through the cost model — benchmarks record the
            # per-callsite resolved name in their own results
            "resolved": {op: self.schedule_for(op) for op in OPS},
        }
        if self.schedule == "auto" \
                and self.comm is not CommunicationType.HOST_STAGED:
            d["auto_resolver"] = ("cost_model" if self.topology is not None
                                  else "static")
        if self.topology is not None:
            d["topology"] = self.topology.describe()
        return d
