"""User-level collective algorithms as explicit ppermute schedules.

Paper §4.7 builds an allreduce *in user space* from point-to-point sends
plus the progress engine, and shows it matches (even beats) the native
implementation because it can exploit context the library cannot.

The TPU analogue: inside an SPMD program the "native collective" is the
opaque ``psum``/``all_gather`` HLO op scheduled by XLA; the "user-level"
version is the same algorithm written as explicit ``ppermute`` steps in
``shard_map``.  The poll-function state machine of Listing 1.8 becomes
the unrolled dataflow of the schedule — each ``mask <<= 1`` round is one
ppermute+combine step.

Implemented schedules (validated against the native op in tests):

* ``recursive_doubling_allreduce`` — the paper's Listing 1.8 algorithm
  (log2 P steps, full vector each step; latency-optimal for small data).
* ``ring_reduce_scatter`` / ``ring_all_gather`` / ``ring_allreduce`` —
  bandwidth-optimal on torus ICI (2(P-1)/P × bytes on the slowest link).
* ``bidirectional_ring_allreduce`` — both ICI directions at once, halving
  per-link traffic (v5e-torus-friendly variant).
* ``recursive_halving_doubling_allreduce`` — ring bandwidth in 2 log2 P
  latency (small-message cross-pod reductions).
* ``bruck_alltoall`` — log2 P-step all-to-all for MoE dispatch.

All functions run INSIDE ``shard_map`` over the given axis.  Rank-
dependent chunk selection uses one-hot arithmetic (every rank executes
the same SPMD program; `axis_index` is a traced value).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat


def _axis_size(axis: str) -> int:
    return compat.axis_size(axis)


def _axis_index(axis: str):
    return jax.lax.axis_index(axis)


def ring_perm(n: int, *, reverse: bool = False) -> list:
    """The ppermute permutation for one ring hop over ``n`` ranks.

    Forward is ``[(i, (i+1) % n)]`` (each rank sends to its successor);
    ``reverse=True`` is the opposite ICI direction.  Shared by the ring
    collective schedules, the pipeline stage handoff, and the p2p layer.
    """
    d = -1 if reverse else 1
    return [(i, (i + d) % n) for i in range(n)]


def _take_chunk(chunks: jax.Array, pos, n: int) -> jax.Array:
    """chunks: [..., n, d]; pos: traced scalar -> [..., d]."""
    oh = jax.nn.one_hot(pos, n, dtype=chunks.dtype)
    shape = (1,) * (chunks.ndim - 2) + (n, 1)
    return jnp.sum(chunks * oh.reshape(shape), axis=-2)


def _set_chunk(out: jax.Array, cur: jax.Array, pos, n: int) -> jax.Array:
    """out: [..., n, d]; write cur at block pos (one-hot masked add)."""
    oh = jax.nn.one_hot(pos, n, dtype=cur.dtype)
    shape = (1,) * (out.ndim - 2) + (n, 1)
    return out + oh.reshape(shape) * cur[..., None, :]


# ---------------------------------------------------------------------------
# Recursive doubling (paper Listing 1.8)
# ---------------------------------------------------------------------------

def recursive_doubling_allreduce(x: jax.Array, axis: str) -> jax.Array:
    """The paper's user-level allreduce: XOR-partner exchange, log2 P
    rounds.  Requires power-of-two axis size (as the paper asserts)."""
    n = _axis_size(axis)
    if n & (n - 1):
        raise ValueError(f"recursive doubling requires power-of-two size, got {n}")
    mask = 1
    while mask < n:
        perm = [(i, i ^ mask) for i in range(n)]
        partner = jax.lax.ppermute(x, axis, perm)
        x = x + partner
        mask <<= 1
    return x


# ---------------------------------------------------------------------------
# Ring schedules (bandwidth-optimal on torus ICI)
# ---------------------------------------------------------------------------

def _pad_last(x: jax.Array, n: int):
    D = x.shape[-1]
    if D % n:
        pad = n - D % n
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]), D
    return x, D


def ring_reduce_scatter(x: jax.Array, axis: str, *, reverse: bool = False) -> jax.Array:
    """P-1 neighbour steps; returns this rank's reduced [..., D/P] chunk."""
    n = _axis_size(axis)
    if n == 1:
        return x
    idx = _axis_index(axis)
    D = x.shape[-1]
    assert D % n == 0, (D, n)
    chunks = jnp.reshape(x, x.shape[:-1] + (n, D // n))
    direction = -1 if reverse else 1
    perm = ring_perm(n, reverse=reverse)
    # Invariant: after step s, rank r holds the partial sum of chunk
    # (r - d·(1+s)) ... i.e. start with own chunk (r - d) and add chunk
    # (r - d·(1+s)) each step; after n-1 steps rank r holds chunk r fully
    # reduced — which is where ring_all_gather expects it.
    acc = _take_chunk(chunks, (idx - direction) % n, n)
    for step in range(1, n):
        acc = jax.lax.ppermute(acc, axis, perm)
        acc = acc + _take_chunk(chunks, (idx - direction * (1 + step)) % n, n)
    return acc


def ring_all_gather(x: jax.Array, axis: str, *, reverse: bool = False) -> jax.Array:
    """All-gather local chunk [..., d] -> [..., P*d] in P-1 ring steps."""
    n = _axis_size(axis)
    if n == 1:
        return x
    idx = _axis_index(axis)
    d = x.shape[-1]
    direction = -1 if reverse else 1
    perm = ring_perm(n, reverse=reverse)
    out = jnp.zeros(x.shape[:-1] + (n, d), x.dtype)
    cur, pos = x, idx
    for step in range(n):
        out = _set_chunk(out, cur, pos, n)
        if step != n - 1:
            cur = jax.lax.ppermute(cur, axis, perm)
            pos = (pos - direction) % n
    return jnp.reshape(out, x.shape[:-1] + (n * d,))


def ring_allreduce(x: jax.Array, axis: str, *, reverse: bool = False) -> jax.Array:
    """reduce-scatter + all-gather: the bandwidth-optimal allreduce."""
    n = _axis_size(axis)
    if n == 1:
        return x
    xp, D = _pad_last(x, n)
    red = ring_reduce_scatter(xp, axis, reverse=reverse)
    full = ring_all_gather(red, axis, reverse=reverse)
    return full[..., :D]


def bidirectional_ring_allreduce(x: jax.Array, axis: str) -> jax.Array:
    """Split the vector and run opposing rings concurrently, using both
    ICI directions of the torus axis — per-link traffic halves."""
    n = _axis_size(axis)
    if n == 1:
        return x
    D = x.shape[-1]
    half = D // 2
    lo = ring_allreduce(x[..., :half], axis, reverse=False)
    hi = ring_allreduce(x[..., half:], axis, reverse=True)
    return jnp.concatenate([lo, hi], axis=-1)


# ---------------------------------------------------------------------------
# Recursive halving/doubling (latency-optimal at ring bandwidth)
# ---------------------------------------------------------------------------

def recursive_halving_doubling_allreduce(x: jax.Array, axis: str) -> jax.Array:
    """Reduce-scatter by recursive halving, then all-gather by recursive
    doubling: total traffic 2·(P-1)/P·bytes (like the ring) in 2·log2 P
    steps (like the tree) — the right schedule for latency-sensitive
    medium-size cross-pod reductions."""
    n = _axis_size(axis)
    if n & (n - 1):
        raise ValueError("requires power-of-two size")
    if n == 1:
        return x
    xp, D = _pad_last(x, n)
    idx = _axis_index(axis)
    cur = xp
    mask = n >> 1
    while mask >= 1:
        width = cur.shape[-1] // 2
        perm = [(i, i ^ mask) for i in range(n)]
        lo, hi = cur[..., :width], cur[..., width:]
        keep_hi = ((idx // mask) % 2) == 1          # bit `mask` set
        send = jnp.where(keep_hi, lo, hi)           # ship the half we drop
        recv = jax.lax.ppermute(send, axis, perm)
        mine = jnp.where(keep_hi, hi, lo)
        cur = mine + recv
        mask >>= 1
    # all-gather by doubling (inverse order)
    mask = 1
    while mask < n:
        perm = [(i, i ^ mask) for i in range(n)]
        recv = jax.lax.ppermute(cur, axis, perm)
        keep_hi = ((idx // mask) % 2) == 1
        lo = jnp.where(keep_hi, recv, cur)
        hi = jnp.where(keep_hi, cur, recv)
        cur = jnp.concatenate([lo, hi], axis=-1)
        mask <<= 1
    return cur[..., :D]


def recursive_halving_reduce_scatter(x: jax.Array, axis: str) -> jax.Array:
    """Reduce-scatter by recursive halving — the first phase of
    :func:`recursive_halving_doubling_allreduce` standing alone: log2 P
    rounds, the payload halves each round.  The halving order consumes
    rank bits MSB-first, so the block a rank finishes with is exactly its
    own contiguous rank-r block — the same output placement as
    ``ring_reduce_scatter`` / tiled ``psum_scatter``."""
    n = _axis_size(axis)
    if n & (n - 1):
        raise ValueError("requires power-of-two size")
    if n == 1:
        return x
    assert x.shape[-1] % n == 0, (x.shape[-1], n)
    idx = _axis_index(axis)
    cur = x
    mask = n >> 1
    while mask >= 1:
        width = cur.shape[-1] // 2
        perm = [(i, i ^ mask) for i in range(n)]
        lo, hi = cur[..., :width], cur[..., width:]
        keep_hi = ((idx // mask) % 2) == 1
        send = jnp.where(keep_hi, lo, hi)
        recv = jax.lax.ppermute(send, axis, perm)
        mine = jnp.where(keep_hi, hi, lo)
        cur = mine + recv
        mask >>= 1
    return cur


def recursive_doubling_all_gather(x: jax.Array, axis: str) -> jax.Array:
    """All-gather by recursive doubling — the second phase of
    :func:`recursive_halving_doubling_allreduce` standing alone.
    Starting from rank r holding block r, log2 P concat rounds reassemble
    the full vector in native rank order (matches tiled
    ``all_gather``)."""
    n = _axis_size(axis)
    if n & (n - 1):
        raise ValueError("requires power-of-two size")
    if n == 1:
        return x
    idx = _axis_index(axis)
    cur = x
    mask = 1
    while mask < n:
        perm = [(i, i ^ mask) for i in range(n)]
        recv = jax.lax.ppermute(cur, axis, perm)
        keep_hi = ((idx // mask) % 2) == 1
        lo = jnp.where(keep_hi, recv, cur)
        hi = jnp.where(keep_hi, cur, recv)
        cur = jnp.concatenate([lo, hi], axis=-1)
        mask <<= 1
    return cur


# ---------------------------------------------------------------------------
# Bruck all-to-all (MoE dispatch)
# ---------------------------------------------------------------------------

def bruck_alltoall(x: jax.Array, axis: str) -> jax.Array:
    """All-to-all over the leading block dim in ceil(log2 P) rounds.

    x: [P, ...] of blocks; returns y with y[j] on rank i = x[i] of rank j
    (the standard MPI_Alltoall block transpose).
    """
    n = _axis_size(axis)
    if n == 1:
        return x
    idx = _axis_index(axis)
    # Phase 1: local rotation — block k moves to slot (k - idx) mod n
    x = jnp.take(x, (jnp.arange(n) + idx) % n, axis=0)
    # Phase 2: log rounds; round `step` ships blocks with bit set in slot id
    step = 1
    while step < n:
        # a block in slot t must travel +t hops total; round `step` moves
        # slots whose bit `step` is set one hop of +step.
        perm = [(i, (i + step) % n) for i in range(n)]
        move = ((jnp.arange(n) // step) % 2).astype(bool)
        moved = jax.lax.ppermute(x, axis, perm)
        x = jnp.where(move.reshape((n,) + (1,) * (x.ndim - 1)), moved, x)
        step <<= 1
    # Phase 3: inverse rotation — slot k receives block (idx - k) mod n
    x = jnp.take(x, (idx - jnp.arange(n)) % n, axis=0)
    return x


# ---------------------------------------------------------------------------
# Algorithm registry
# ---------------------------------------------------------------------------

ALGORITHMS = {
    "ring": ring_allreduce,
    "bidir": bidirectional_ring_allreduce,
    "recursive_doubling": recursive_doubling_allreduce,
    "halving_doubling": recursive_halving_doubling_allreduce,
}

# algorithms whose XOR-partner exchange only works for power-of-two sizes
POW2_ONLY = frozenset({"recursive_doubling", "halving_doubling"})


def resolve_algorithm(algorithm: str, axis_size: int, *,
                      fallback: str = "ring") -> str:
    """Eager validation of (algorithm, axis size) — call *before* tracing.

    Unknown names raise immediately; power-of-two-only algorithms on a
    non-power-of-two axis fall back to ``fallback`` with a clear warning
    instead of raising an opaque ``ValueError`` from inside jit."""
    if algorithm not in ALGORITHMS:
        raise ValueError(f"unknown allreduce algorithm {algorithm!r}; "
                         f"options: {sorted(ALGORITHMS)}")
    if algorithm in POW2_ONLY and axis_size & (axis_size - 1):
        import warnings
        warnings.warn(
            f"{algorithm} allreduce requires a power-of-two axis size, "
            f"got {axis_size}; falling back to {fallback!r}",
            RuntimeWarning, stacklevel=3)
        return fallback
    return algorithm


# rs/ag only decompose for the algorithms that *contain* a reduce-scatter
# or all-gather phase: the ring, and recursive halving/doubling (the
# halving phase IS a reduce-scatter, the doubling phase IS an
# all-gather).  The others are allreduce-shaped end to end.
RS_AG_ALGORITHMS = frozenset({"ring", "halving_doubling"})


def resolve_rs_ag_algorithm(algorithm: str, axis_size: int, *,
                            op: str = "reduce_scatter") -> str:
    """Eager (algorithm, axis size) validation for reduce-scatter /
    all-gather decompositions: unknown names raise, power-of-two-only
    algorithms fall back to ring on other sizes, and algorithm names with
    no rs/ag phase (``bidir``, ``recursive_doubling``) fall back to ring
    with a warning rather than failing deep inside a round program."""
    algorithm = resolve_algorithm(algorithm, axis_size)
    if algorithm not in RS_AG_ALGORITHMS:
        import warnings
        warnings.warn(
            f"{algorithm} has no {op} decomposition (options: "
            f"{sorted(RS_AG_ALGORITHMS)}); falling back to 'ring'",
            RuntimeWarning, stacklevel=3)
        return "ring"
    return algorithm


# ---------------------------------------------------------------------------
# Round batching (persistent schedules; see collectives/nonblocking.py)
# ---------------------------------------------------------------------------

# Payload-size breakpoints for the automatic round-batch factor.  Below
# SMALL the per-dispatch latency dominates total time (the fig-14 small-
# payload gap), so every round of a chunk fuses into ONE program; up to
# LARGE two dispatches keep a little pipelining; above it the bandwidth
# regime needs per-round dispatch so chunk c+1's round r can overlap
# chunk c's round r+1 on the collective stream.
ROUND_BATCH_SMALL_BYTES = 4 << 20        # <= 4 MiB: fuse everything
ROUND_BATCH_LARGE_BYTES = 64 << 20       # <= 64 MiB: two dispatches


def fuse_rounds(fns):
    """Compose consecutive round bodies into one program body.

    Each ``fn`` is a carry -> carry function written to run inside
    ``shard_map``; the fusion is plain sequential composition, so the
    fused program executes the exact same op sequence in the exact same
    order as the unfused rounds — per-algorithm chunk layouts (and float
    summation order) are preserved bit-identically, only the dispatch
    count changes."""
    fns = tuple(fns)
    if not fns:
        raise ValueError("fuse_rounds on empty round list")
    if len(fns) == 1:
        return fns[0]

    def fused(carry):
        for fn in fns:
            carry = fn(carry)
        return carry

    return fused


def auto_round_batch(payload_bytes: int, num_rounds: int) -> int:
    """Pick the round-batch factor from the payload size.

    Small payloads collapse to 1–2 dispatches per chunk (per-operation
    setup cost is the whole story); large payloads keep per-round
    dispatch so the chunk pipeline can overlap rounds across chunks."""
    if num_rounds <= 1:
        return 1
    if payload_bytes <= ROUND_BATCH_SMALL_BYTES:
        return num_rounds                       # one dispatch per chunk
    if payload_bytes <= ROUND_BATCH_LARGE_BYTES:
        return -(-num_rounds // 2)              # two dispatches per chunk
    return 1                                    # full per-round pipelining


def allreduce_under_shard_map(x, mesh, axis: str, algorithm: str = "ring"):
    """Allreduce `x` (sharded on `axis`'s data dim) with a user schedule;
    output is the allreduced value, still sharded the same way — directly
    comparable to ``jax.lax.psum`` in tests and the Fig-13 benchmark.

    The (algorithm, axis size) pair is validated eagerly: power-of-two-
    only algorithms fall back to ring with a warning on other sizes."""
    algorithm = resolve_algorithm(algorithm, dict(mesh.shape)[axis])
    fn = ALGORITHMS[algorithm]

    def body(xs):
        return fn(xs, axis)

    return compat.shard_map(body, mesh=mesh, in_specs=P(axis),
                            out_specs=P(axis))(x)
