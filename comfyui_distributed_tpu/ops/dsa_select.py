"""The exact top-k of every row of an indexer's scores, as positions,
without a sort.

`models/dsa.select`'s gathered form picks, for each of a part's queries,
the `index_topk` best of up to 32,896 float32 scores. Left to XLA that
is `lax.top_k`, on a TPU a full bitonic sort of the row: 120
compare-exchange stages, 111 ms a part of 8,192 queries at 32,768 keys on
a v5e against 14.4 ms for the scores themselves (PERF.md section 6,
PR 52). `dsa_select` finds the same set in one Pallas kernel that holds a
block of 128 queries' scores in VMEM throughout, the queries on the lanes
and the positions on the sublanes and the major axis, so that a count
over positions is a sum of registers and a shift along positions is an
address:

  (a) **threshold**: the scores as 32-bit keys that order as the floats
      do, and the k-th largest key of every query by 32 passes of compare
      and count, a bit a pass (`models/dsa.above_threshold`'s bisection);
      `wanted` = min(k, the query's visible positions).
  (b) **mask**: every key above the threshold, and of those equal to it
      the lowest positions still needed: `lax.top_k`'s set exactly.
  (c) **rank**: the exclusive prefix count of the mask along the
      positions: a register's 8 positions by three rotate-and-add steps
      over its sublanes, and a running count from register to register.
      The counts of keys above and of keys equal share a word. (Eight
      runs read by loads with a sublane stride cost eight loads a
      register: 20 bundles a register for both passes, 10 this way.)
  (d) **compress**: every chosen position is given its displacement
      d = position - rank and the word `d << 16 | position` (0 where
      nothing was chosen; position 0 at rank 0 is 0 too and is where it
      belongs). Stage b of log2(S) stages moves the words whose bit b of
      d is set down by 2^b, lowest bit first (the parallel-suffix
      `compress` of Hacker's Delight 7-4): the routes of an
      order-preserving compaction never collide, and after the last
      stage the word of rank r lies at position r. Only the first k
      places are read, so the last stages write only the pieces those
      read (`needed_pieces`).
  (e) the first k words' low halves are the chosen positions, ascending.

Positions and displacements are 16 bits each, so S is at most 65,536
(`plan`). Elsewhere, and there, `lax.top_k` stays (`route`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .attention import _ROUTE_LOG, ROUTE_MULTIPLE
from .dsa_attend import VMEM_HEADROOM, VMEM_RESIDENT_BUDGET, _up

# Positions a trip of a loop over the positions takes: 16 registers of 8
# positions x 128 queries. A block's positions are padded to whole trips.
CHUNK = 128
# Positions a trip of a counting pass takes: long enough that the trip's
# tail of dependent adds is a small part of it (69 bundles for 64
# registers, 22 for 16). A stage of the network gains nothing from a longer
# trip: at 256 positions its 64 loads come before the first store and spill.
COUNT_TRIP = 512
# A late stage of the network walks only the pieces its successors read, if
# they are this many at most (each is a loop of the program).
MOST_PIECES = 4
# The most positions a word's half can name.
MOST_POSITIONS = 1 << 16
_SIGN = np.int32(-2**31)
# The key of minus infinity: below every score's.
_UNSEEN = np.int32(np.uint32(0xFF800000) ^ np.uint32(0x7FFFFFFF))


class Plan(NamedTuple):
    """What a call pads its operands to, and what it holds: the queries
    to whole lane tiles, the positions to whole trips (`CHUNK`; padding
    is minus infinity and never chosen), the positions written out to
    whole trips, the stages of the compress network."""

    rows: int
    positions: int
    chosen: int
    stages: int
    vmem_bytes: int


def plan(rows: int, positions: int, k: int) -> Plan | None:
    """The padded sizes of a call that picks `k` of `positions` scores
    for each of `rows` queries, from the shape alone. None where the
    kernel does not apply: more positions than a word's half can name
    (`MOST_POSITIONS`), or a block of queries' keys (two buffers of the
    pipeline) and words that do not fit `VMEM_RESIDENT_BUDGET`."""
    if min(rows, positions, k) <= 0 or positions > MOST_POSITIONS:
        return None
    padded = _up(positions, CHUNK)
    chosen = _up(min(k, positions), CHUNK)
    block = ROUTE_MULTIPLE * 4
    vmem_bytes = (2 * padded + padded + CHUNK + 2 * chosen + 2 * 8) * block
    if vmem_bytes > VMEM_RESIDENT_BUDGET:
        return None
    return Plan(_up(rows, ROUTE_MULTIPLE), padded, chosen, (padded - 1).bit_length(), vmem_bytes)


def needed_pieces(total: int, chosen: int, stages: int) -> list[list[tuple[int, int]]]:
    """For each stage of the network over `total` positions, the pieces
    [(start, stop)] it has to write for the first `chosen` positions to be
    right after the last: the last stage those alone, a stage before it
    what its successor writes and what that reads, `shift` further on.
    Everything, where that is more than `MOST_PIECES` pieces."""
    pieces, by_stage = [(0, chosen)], []
    for b in reversed(range(stages)):
        by_stage.append(pieces if len(pieces) <= MOST_PIECES else [(0, total)])
        further = [(start + (1 << b), min(stop + (1 << b), total)) for start, stop in pieces]
        merged = []
        for start, stop in sorted(pieces + [p for p in further if p[0] < p[1]]):
            if merged and start <= merged[-1][1]:
                merged[-1] = (merged[-1][0], max(merged[-1][1], stop))
            else:
                merged.append((start, stop))
        pieces = merged
    return by_stage[::-1]


def route(rows: int, positions: int, k: int) -> str:
    """"kernel" on a TPU for a shape `plan` takes, else "sort"
    (`models/dsa.top`, `lax.top_k`)."""
    if jax.default_backend() != "tpu":
        return "sort"
    return "kernel" if plan(rows, positions, k) else "sort"


def log_route(form: str, queries: int, positions: int, k: int) -> None:
    """One entry in `ops/attention.route_log` a traced selection of a
    part's queries: `dsa-select-kernel 8192x32768 k2048` (queries x the
    positions scored, the positions a query keeps at most) or
    `dsa-select-sort ...`."""
    log = _ROUTE_LOG.get()
    if log is not None:
        log.append(f"dsa-select-{form} {queries}x{positions} k{k}")


def ordered_keys(index: jax.Array) -> jax.Array:
    """int32 keys that order as the float32 scores do (`scores` lets
    only +0 through, so equal floats are equal keys)."""
    bits = jax.lax.bitcast_convert_type(index, jnp.int32)
    return jnp.where(bits >= 0, bits, bits ^ jnp.int32(0x7FFFFFFF))


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def dsa_select(index: jax.Array, *, k: int, interpret: bool = False):
    """The `k` largest of each row of I [T, S] float32 (as
    `models/dsa.scores` gives it: minus infinity what a query may not
    see, +0 only) as `(chosen [T, k'] int32, counts [T, k'] bool)`, k' =
    min(k, S): a query's positions **ascending**, the first min(k,
    visible) of them counting, the rest at position 0 and not counting.
    The set is `lax.top_k`'s, ties to the lower position.

    Grid: blocks of 128 queries. A block's keys [S, 128] are one VMEM
    operand; its words are a scratch buffer of the same size, and nothing
    but the chosen positions [k, 128] and `wanted` leaves the chip.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    queries, positions = index.shape
    sizes = plan(queries, positions, k)
    if sizes is None or index.dtype != jnp.float32:
        raise ValueError(f"dsa_select: no plan for the {k} largest of {index.shape} {index.dtype}")
    k = min(k, positions)
    total = sizes.positions
    keys = jnp.pad(
        ordered_keys(index).T, ((0, total - positions), (0, sizes.rows - queries)),
        constant_values=_UNSEEN)

    def kernel(keys_ref, chosen_ref, wanted_ref, words):
        lanes = (8, ROUTE_MULTIPLE)
        sublane = jax.lax.broadcasted_iota(jnp.int32, lanes, 0)

        def sweep(start, stop, body, carry=None, most=CHUNK):
            """`body(at, size, carry)` over the positions [start, stop), `most` a trip
            and what is left a trip of `CHUNK`; the bounds are the program's."""
            for size in (most, CHUNK):
                trips = max(stop - start, 0) // size

                def trip(t, carry, size=size, start=start):
                    return body(pl.multiple_of(start + t * size, CHUNK), size, carry)

                if trips:
                    carry = jax.lax.fori_loop(0, trips, trip, carry)
                    start += trips * size
            return carry

        def count(test):
            def trip(at, size, acc):
                hit = test(keys_ref[pl.ds(at, size), :]).astype(jnp.int32)
                return acc + hit.reshape(size // 8, *lanes).sum(axis=0)

            acc = sweep(0, total, trip, jnp.zeros(lanes, jnp.int32), most=COUNT_TRIP)
            return jnp.sum(acc, axis=0, keepdims=True)                      # [1, 128]

        # (a) the k-th largest key of every query
        wanted = jnp.minimum(k, count(lambda x: x > _UNSEEN))

        def narrow(i, tau):
            higher = tau | jnp.left_shift(jnp.int32(1), 31 - i)
            enough = count(lambda x: x >= (higher ^ _SIGN)) >= wanted
            return jnp.where(enough, higher, tau)

        tau = jax.lax.fori_loop(0, 32, narrow, jnp.zeros((1, ROUTE_MULTIPLE), jnp.int32))
        threshold = jnp.broadcast_to(tau ^ _SIGN, lanes)
        # of the keys equal to the threshold, how many are still needed
        left = jnp.broadcast_to(wanted - count(lambda x: x > threshold[:1]), lanes)

        # (b), (c): a running count a register of 8 positions, the keys above the
        # threshold in a word's low half and the keys equal to it in its high half
        def rank(at, size, before):
            for j in range(size // 8):
                x = keys_ref[pl.ds(at + 8 * j, 8), :]
                higher, equal = x > threshold, x == threshold
                mark = jnp.where(higher, 1, jnp.where(equal, 1 << 16, 0))
                upto = mark
                for step in (1, 2, 4):
                    upto = upto + jnp.where(sublane >= step, pltpu.roll(upto, step, 0), 0)
                ahead = before + upto - mark
                before = before + jnp.broadcast_to(upto[7:], lanes)
                level = jax.lax.shift_right_logical(ahead, 16)
                place = at + 8 * j + sublane
                displaced = place - (ahead & 0xFFFF) - jnp.minimum(level, left)
                taken = higher | (equal & (level < left))
                words[pl.ds(at + 8 * j, 8), :] = jnp.where(taken, (displaced << 16) | place, 0)
            return before

        sweep(0, total, rank, jnp.zeros(lanes, jnp.int32))
        words[pl.ds(total, CHUNK), :] = jnp.zeros((CHUNK, ROUTE_MULTIPLE), jnp.int32)

        # (d) the compress network, in place: a trip reads what no earlier trip wrote
        for b, pieces in enumerate(needed_pieces(total, sizes.chosen, sizes.stages)):
            shift, bit = 1 << b, np.int32(np.uint32(1 << (16 + b)))

            def move(at, size, carry, shift=shift, bit=bit):
                here, there = words[pl.ds(at, size), :], words[pl.ds(at + shift, size), :]
                stays = jnp.where((here & bit) != 0, 0, here)
                words[pl.ds(at, size), :] = jnp.where((there & bit) != 0, there, stays)

            def leave(at, size, carry, bit=bit):
                here = words[pl.ds(at, size), :]
                words[pl.ds(at, size), :] = jnp.where((here & bit) != 0, 0, here)

            # past `total - shift` nothing arrives (a shift under a trip reads the zeros
            # kept behind the words instead)
            arriving = total if shift < CHUNK else total - shift
            for start, stop in pieces:
                sweep(start, min(stop, arriving), move)
                sweep(max(start, arriving), stop, leave)

        # (e) the first k words' positions
        for c in range(sizes.chosen // CHUNK):
            place = c * CHUNK + jax.lax.broadcasted_iota(jnp.int32, (CHUNK, ROUTE_MULTIPLE), 0)
            chosen_ref[pl.ds(c * CHUNK, CHUNK), :] = jnp.where(
                place < wanted, words[pl.ds(c * CHUNK, CHUNK), :] & 0xFFFF, 0)
        wanted_ref[...] = jnp.broadcast_to(wanted, lanes)

    by_lanes = lambda length: pl.BlockSpec((length, ROUTE_MULTIPLE), lambda i: (0, i))
    chosen, wanted = pl.pallas_call(
        kernel,
        grid=(sizes.rows // ROUTE_MULTIPLE,),
        in_specs=[by_lanes(total)],
        out_specs=[by_lanes(sizes.chosen), by_lanes(8)],
        out_shape=[jax.ShapeDtypeStruct((sizes.chosen, sizes.rows), jnp.int32),
                   jax.ShapeDtypeStruct((8, sizes.rows), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((total + CHUNK, ROUTE_MULTIPLE), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=sizes.vmem_bytes + VMEM_HEADROOM),
        interpret=interpret,
        name="dsa_select",  # the kernel's name in a device trace
    )(keys)
    # kept a call of its own: fused with the write into a caller's stacked result (a
    # `lax.map` over blocks) the call is compiled under the default 16 MiB of VMEM
    chosen, wanted = jax.lax.optimization_barrier((chosen, wanted))
    counts = jnp.arange(k)[None, :] < wanted[0, :queries, None]
    return chosen[:k, :queries].T, counts
