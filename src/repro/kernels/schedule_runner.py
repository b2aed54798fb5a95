"""Execute a certified :class:`LoweredSchedule` on a jax mesh.

The generalized counterpart of :mod:`repro.kernels.ring_collective`:
instead of a hand-derived ring, this runner interprets the per-round
``PermuteStep``\\ s the :class:`~repro.collective.JaxExecutor` lowering
produced — so *any* registered algorithm (trees, halving-doubling,
bcube, recursive-doubling...) runs on real devices through
``jax.lax.ppermute``, with the reduce accumulation fused by the same
Pallas :func:`~repro.kernels.ring_collective.fused_add` kernel.

Execution semantics mirror the translation validator exactly
(:mod:`repro.analysis.equiv`):

* the mesh-axis index IS the schedule's position space; device p holds
  logical rank ``schedule.rank_of[p]``'s buffer;
* rounds are barriers: every step's payload is gathered from the
  round-entry buffer, all receives are staged, and applied together at
  the round boundary;
* a link ``(s, d)`` fires iff ``send_mask[s] and recv_mask[d]``;
* ``reduce`` accumulates into the destination chunk row, ``copy``
  overwrites it;
* ``chunk_factor`` k pipelines the body serially over k payload
  slices.

The local buffer is ``[n_chunks + 1, chunk_len]`` per device — row
``n_chunks`` is a zero scratch row that absorbs the gather/scatter of
non-participating positions, keeping every step a static dense
``ppermute`` (no per-device control flow, so the whole schedule jits
to one XLA program).

This module never certifies anything itself: callers obtain schedules
through ``Session.lower`` / ``JaxExecutor.lower`` where
:func:`repro.analysis.equiv.require_certified` has already proven the
artifact, which is the whole point of translation validation — the
runner can be this trusting *because* the schedule carries a proof.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.collective.executors import LoweredSchedule

from .ring_collective import accumulate

__all__ = ["run_schedule", "schedule_body", "check_postcondition",
           "schedule_tables", "land_receives", "PERMUTE_SCOPE",
           "TABLE_SCOPE", "ADD_SCOPE"]

#: device scopes of the certified paths (here and in
#: :mod:`repro.kernels.overlap`): every ``ppermute``; the gather and
#: scatter through the SEND/RECV tables and the scratch row's upkeep;
#: the reduce
PERMUTE_SCOPE = "certified.permute"
TABLE_SCOPE = "certified.table"
ADD_SCOPE = "certified.add"


def _step_tables(step, n: int, n_chunks: int):
    """Static gather/scatter tables of one PermuteStep.

    Returns ``(eff_links, SEND, RECV)``: the mask-filtered ppermute
    link list and ``[n, m]`` int32 chunk-row tables (pad entries point
    at the zero scratch row ``n_chunks``).
    """
    m = max((len(c) for c in step.chunks), default=0)
    m = max(m, 1)
    send = np.full((n, m), n_chunks, dtype=np.int32)
    recv = np.full((n, m), n_chunks, dtype=np.int32)
    eff_links: List[Tuple[int, int]] = []
    for (s, d), chunks in zip(step.links, step.chunks):
        if not (step.send_mask[s] and step.recv_mask[d]):
            continue
        eff_links.append((int(s), int(d)))
        send[s, :len(chunks)] = chunks
        recv[d, :len(chunks)] = chunks
    return eff_links, send, recv


@functools.lru_cache(maxsize=256)
def schedule_tables(schedule: LoweredSchedule):
    """Static per-round ``(eff_links, SEND, RECV)`` tables + op tags.

    Schedules are hot-path constants: a train step re-runs the same
    certified artifact every call, so the tables are memoised on the
    schedule *value* (frozen dataclasses hash by content — two lowerings
    of the same program share one entry).  Returns
    ``(tables, ops)`` where ``tables[r][s]`` is :func:`_step_tables` of
    round ``r``'s step ``s`` and ``ops[r][s]`` its reduce/copy tag.
    The cached arrays are read-only by convention — every consumer
    gathers from them without mutation.
    """
    tables = tuple(
        tuple(_step_tables(step, schedule.n, schedule.n_chunks)
              for step in rnd)
        for rnd in schedule.rounds)
    ops = tuple(tuple(step.op for step in rnd) for rnd in schedule.rounds)
    return tables, ops


def _initial_buffers(schedule: LoweredSchedule,
                     x: np.ndarray) -> Tuple[np.ndarray, int]:
    """Rank-space ``[n, n_chunks + 1, chunk_len]`` buffers from inputs.

    ``x`` is rank-major: row r is logical rank r's contribution, shaped
    by the schedule's declared init (``replicated``: the full local
    vector; ``sharded``: rank r's own chunk; ``addressed``: the n
    outgoing pieces).
    """
    n, n_chunks = schedule.n, schedule.n_chunks
    x = np.asarray(x)
    assert x.ndim == 2 and x.shape[0] == n, x.shape
    if schedule.init == "replicated":
        assert x.shape[1] % n_chunks == 0, (x.shape, n_chunks)
        chunk_len = x.shape[1] // n_chunks
        buf = np.zeros((n, n_chunks + 1, chunk_len), dtype=x.dtype)
        buf[:, :n_chunks] = x.reshape(n, n_chunks, chunk_len)
    elif schedule.init == "sharded":
        chunk_len = x.shape[1]
        buf = np.zeros((n, n_chunks + 1, chunk_len), dtype=x.dtype)
        for r in range(n):
            buf[r, r] = x[r]
    elif schedule.init == "addressed":
        assert n_chunks == n * n and x.shape[1] % n == 0, (x.shape, n_chunks)
        chunk_len = x.shape[1] // n
        buf = np.zeros((n, n_chunks + 1, chunk_len), dtype=x.dtype)
        for s in range(n):
            buf[s, s * n:(s + 1) * n] = x[s].reshape(n, chunk_len)
    else:
        raise ValueError(f"unknown init {schedule.init!r}")
    return buf, chunk_len


def land_receives(buf, me, recv, cols: slice, op: str, received,
                  n_chunks: int, use_pallas_add: bool) -> jnp.ndarray:
    """Land one step's receives in this device's ``buf``: ``reduce``
    accumulates into the RECV table's rows, ``copy`` overwrites them.

    Non-receiving positions land in the scratch row, which is re-zeroed
    so every later gather still reads zeros."""
    with jax.named_scope(TABLE_SCOPE):
        my_recv = jnp.asarray(recv)[me]                      # [m]
    if op == "reduce":
        with jax.named_scope(TABLE_SCOPE):
            current = buf[my_recv, cols]
        with jax.named_scope(ADD_SCOPE):
            received = accumulate(current, received, use_pallas_add)
    with jax.named_scope(TABLE_SCOPE):
        buf = buf.at[my_recv, cols].set(received)
        return buf.at[n_chunks].set(jnp.zeros_like(buf[n_chunks]))


def schedule_body(mesh: Mesh, axis: str, schedule: LoweredSchedule,
                  use_pallas_add: bool = True
                  ) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """The traceable body of :func:`run_schedule`, over ``mesh[axis]``.

    Maps position-major ``[n, n_chunks + 1, chunk_len]`` buffers (the
    device at axis position p holds logical rank ``rank_of[p]``'s
    buffer, row ``n_chunks`` the zero scratch row) to the same layout
    after the last round.  It depends only on the buffers' shape, so it
    can be lowered from a ``ShapeDtypeStruct``.
    """
    n = schedule.n
    if mesh.shape[axis] != n:
        raise ValueError(f"mesh axis {axis!r} has {mesh.shape[axis]} "
                         f"devices, schedule wants {n}")
    k = schedule.chunk_factor
    # static per-step tables, resolved once per schedule (memoised —
    # repeated calls on the same certified artifact skip the rebuild)
    tables, ops = schedule_tables(schedule)

    def per_device(rows):
        buf = rows[0]                              # [n_chunks+1, chunk_len]
        chunk_len = buf.shape[-1]
        if chunk_len % k:
            raise ValueError(
                f"chunk_len {chunk_len} not divisible by chunk_factor {k}")
        piece_len = chunk_len // k
        me = jax.lax.axis_index(axis)
        for piece in range(k):
            # a piece is a static column window of every chunk row
            cols = slice(piece * piece_len, (piece + 1) * piece_len)
            for rnd_tables, rnd_ops in zip(tables, ops):
                entry = buf                        # round-entry snapshot
                staged = []
                for eff_links, send, recv in rnd_tables:
                    if not eff_links:
                        staged.append(None)
                        continue
                    with jax.named_scope(TABLE_SCOPE):
                        my_send = jnp.asarray(send)[me]      # [m]
                        payload = entry[my_send, cols]
                    with jax.named_scope(PERMUTE_SCOPE):
                        staged.append(
                            jax.lax.ppermute(payload, axis, eff_links))
                for (eff_links, send, recv), op, received in zip(
                        rnd_tables, rnd_ops, staged):
                    if received is None:
                        continue
                    buf = land_receives(buf, me, recv, cols, op, received,
                                        schedule.n_chunks, use_pallas_add)
        return buf[None]

    return jax.shard_map(per_device, mesh=mesh, in_specs=(P(axis),),
                         out_specs=P(axis), check_vma=False)


def run_schedule(
    x,
    mesh: Mesh,
    axis: str,
    schedule: LoweredSchedule,
    use_pallas_add: bool = True,
) -> jnp.ndarray:
    """Run ``schedule`` over ``mesh[axis]``; returns final rank buffers.

    ``x``: ``[n, D]`` rank-major inputs (see :func:`_initial_buffers`
    for D per init).  Returns ``[n, n_chunks, chunk_len]`` rank-major —
    row r is logical rank r's final chunk buffer, against which the
    declared postcondition can be checked
    (:func:`check_postcondition`).
    """
    body = schedule_body(mesh, axis, schedule, use_pallas_add)
    buf0, _ = _initial_buffers(schedule, x)
    # device at axis position p plays logical rank rank_of[p]
    rank_of = np.asarray(schedule.rank_of, dtype=np.int64)
    out_pos = body(jnp.asarray(buf0[rank_of]))     # position-major
    # back to rank space, scratch row dropped
    order = np.asarray(schedule.order, dtype=np.int64)
    return jnp.asarray(out_pos)[order][:, :schedule.n_chunks]


def check_postcondition(schedule: LoweredSchedule, x,
                        out, atol: float = 1e-5) -> List[str]:
    """Numerically verify ``out`` satisfies the declared postcondition.

    ``x``/``out`` as in :func:`run_schedule`.  Returns human-readable
    mismatch descriptions (empty list = postcondition holds) — the
    end-to-end complement of the symbolic bisimulation proof.
    """
    n, n_chunks = schedule.n, schedule.n_chunks
    x = np.asarray(x, dtype=np.float64)
    out = np.asarray(out, dtype=np.float64)
    post = schedule.postcondition
    bad: List[str] = []

    def close(a, b) -> bool:
        return bool(np.allclose(a, b, atol=atol, rtol=1e-5))

    if post in ("allreduce", "reduce"):
        want = x.sum(axis=0).reshape(n_chunks, -1)   # replicated init
        if post == "allreduce":
            for r in range(n):
                if not close(out[r], want):
                    bad.append(f"rank {r}: allreduce result diverges")
        else:
            if not any(close(out[r], want) for r in range(n)):
                bad.append("no rank holds the fully-reduced vector")
    elif post == "reduce_scatter":
        want = x.sum(axis=0).reshape(n_chunks, -1)
        for r in range(n):
            if not close(out[r, r], want[r]):
                bad.append(f"rank {r}: chunk {r} not fully reduced")
    elif post == "all_gather":
        for r in range(n):
            for c in range(n_chunks):
                if not close(out[r, c], x[c]):
                    bad.append(f"rank {r}: chunk {c} not gathered")
    elif post == "all_to_all":
        piece = x.reshape(n, n, -1)                  # [src, dst, len]
        for s in range(n):
            for d in range(n):
                if not close(out[d, s * n + d], piece[s, d]):
                    bad.append(f"piece {s}→{d} undelivered")
    elif post != "none":
        bad.append(f"unknown postcondition {post!r}")
    return bad
