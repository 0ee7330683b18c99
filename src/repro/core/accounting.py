"""Byte-exact communication accounting (paper, Sec. 3).

The paper measures cumulative communication C(T, m) = sum_t c(f_t) in
bytes, under a designated-coordinator topology with the *trivial
communication-reduction strategy*:

  upload  (learner i -> coordinator):  |S_t^i| B_alpha  +  |S_t^i \\ Sbar_{t'}| B_x
  download(coordinator -> learner i):  |Sbar_t| B_alpha +  |Sbar_t \\ S_t^i| B_x

where t' is the last synchronization time, B_x in O(d) bytes per
support vector and B_alpha in O(1) bytes per coefficient.  Support
vectors already known to the receiving side are never re-sent; identity
is tracked through the unique ``sv_id`` tags of rkhs.SVModel.

For linear models a synchronization costs m uploads + m downloads of a
fixed-size weight vector.

Beyond the paper (DESIGN.md Sec. 3 hardware-adaptation): on a TPU mesh
there is no coordinator; averaging is a ring all-reduce in which each
of m participants moves 2 (m-1)/m |theta| bytes, i.e. a ring TOTAL of
2 (m-1) |theta| bytes.  ``allreduce_bytes`` and ``allgather_bytes``
price that topology — both return ring *totals*, the same semantics as
``sync_bytes_linear`` / ``sync_bytes_kernel`` on the coordinator side —
so every experiment can report the two topologies side by side
(``engine.run(..., topology="allreduce")``, DESIGN.md Sec. 9).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class ByteModel:
    """B_x = bytes per support vector (O(d)); B_alpha per coefficient."""

    dim: int
    dtype_bytes: int = 4
    id_bytes: int = 4

    @property
    def B_x(self) -> int:
        # vector payload + its id tag
        return self.dim * self.dtype_bytes + self.id_bytes

    @property
    def B_alpha(self) -> int:
        # coefficient + the id it belongs to
        return self.dtype_bytes + self.id_bytes


def idset(ids: np.ndarray) -> set:
    """Active sv_id set of an id array (negative = empty slot)."""
    ids = np.asarray(ids).reshape(-1)
    return set(int(i) for i in ids if i >= 0)


_idset = idset


def sync_bytes_kernel(
    bm: ByteModel,
    local_ids: Sequence[np.ndarray],
    coordinator_known: set,
) -> tuple[int, set]:
    """Bytes for one synchronization of kernel models.

    local_ids: per-learner arrays of active sv_ids at sync time.
    coordinator_known: ids of Sbar_{t'} cached at the coordinator.

    Returns (bytes, new_coordinator_known = Sbar_t ids).
    """
    sets = [_idset(a) for a in local_ids]
    union = set().union(*sets) if sets else set()
    total = 0
    for s in sets:
        # upload: all coefficients, only new support vectors
        total += len(s) * bm.B_alpha + len(s - coordinator_known) * bm.B_x
        # download: all average coefficients, only unknown-to-i vectors
        total += len(union) * bm.B_alpha + len(union - s) * bm.B_x
    return total, union


def sync_bytes_linear(num_params: int, m: int, dtype_bytes: int = 4) -> int:
    """m uploads + m downloads of a fixed-size weight vector.

    This is also the RFF substrate's cost with num_params = D + 1: a
    random-feature model is a fixed-size primal vector, so per-sync
    bytes are independent of the rounds seen (Cor. 8 strict
    adaptivity — the paper's Sec. 4 'future work' case).
    """
    return 2 * m * num_params * dtype_bytes


# -- per-message payload sizing (used by the async transport and the
#    substrate layer's upload/download accounting) --------------------------


def kernel_payload_bytes(bm: ByteModel, send_ids: set,
                         receiver_known: set) -> int:
    """Bytes to ship an expansion over ``send_ids`` to a receiver that
    already caches ``receiver_known``: every coefficient, only novel
    support vectors (the Sec. 3 delta encoding per link)."""
    return (len(send_ids) * bm.B_alpha
            + len(send_ids - receiver_known) * bm.B_x)


def linear_payload_bytes(num_params: int, dtype_bytes: int = 4) -> int:
    """Dense weight vectors have no identity structure: full re-send."""
    return num_params * dtype_bytes


def allreduce_bytes(num_params: int, m: int, dtype_bytes: int = 4) -> int:
    """TOTAL ring bytes of one all-reduce of a |theta|-parameter vector:
    ``2 (m-1) |theta| B`` (reduce-scatter + all-gather; each of the m
    participants moves ``2 (m-1)/m |theta| B`` of that total).

    The total semantics match the coordinator-side accounting
    (``sync_bytes_linear`` = ``2 m |theta| B`` total), so the two
    topologies compare directly: per direction the ring moves a
    ``(m-1)/m`` fraction of the coordinator's bytes
    (tests/test_accounting.py pins the ratio)."""
    if m <= 1:
        return 0
    return int(2 * (m - 1) * num_params * dtype_bytes)


def allgather_bytes(shard_bytes: int, m: int) -> int:
    """TOTAL ring bytes of one all-gather where each of m participants
    contributes a ``shard_bytes``-sized shard: every participant
    receives the other m-1 shards, so the ring moves
    ``m (m-1) shard_bytes`` in total.

    This prices the SV substrate's mesh synchronization
    (``topology="allreduce"``, DESIGN.md Sec. 9): support-vector
    expansions have no slot alignment across learners, so the mesh
    average is an all-gather of the m budget-tau expansions rather
    than a reduce-scatter."""
    if m <= 1:
        return 0
    return int(m * (m - 1) * shard_bytes)


# ---------------------------------------------------------------------------
# Device-resident ledger (DESIGN.md Sec. 7)
# ---------------------------------------------------------------------------
#
# ``CommunicationLedger`` below runs the Sec. 3 set algebra in numpy on
# the host — one Python call per round, which is what caps the serial
# simulation driver at host speed.  ``DeviceLedger`` is the same
# accounting expressed over fixed-shape sorted id arrays so it can live
# inside a jitted ``lax.scan`` (core/engine.py): sets become
# ID_SENTINEL-padded sorted arrays, distinctness a neighbour
# comparison, membership one stable sort of the cache with the queried
# ids (rkhs.sorted_unique / rkhs.count_known).  tests/test_engine.py
# proves the two ledgers agree byte-for-byte on randomized sync
# sequences.


class DeviceLedger(NamedTuple):
    """Jit-compatible coordinator cache: ``known`` is the sorted-unique
    id array of Sbar_{t'} (the support set shipped at the last sync),
    padded with rkhs.ID_SENTINEL.  Capacity is fixed at m * tau — the
    union of m budget-tau expansions can never exceed it."""

    known: "jnp.ndarray"


def device_ledger_init(capacity: int) -> DeviceLedger:
    """Fresh coordinator cache (nothing known — first sync ships all)."""
    import jax.numpy as jnp

    from .rkhs import ID_SENTINEL

    return DeviceLedger(known=jnp.full((capacity,), ID_SENTINEL, jnp.int32))


def device_sync_bytes_kernel(
    bm: ByteModel, stacked_ids: "jnp.ndarray", ledger: DeviceLedger,
    mask: "jnp.ndarray | None" = None,
) -> "tuple[jnp.ndarray, DeviceLedger]":
    """``sync_bytes_kernel`` under jit: bytes for one kernel-model sync.

    stacked_ids: (m, tau) int32 active sv_ids at sync time (-1 = empty
    slot; duplicated ids — support vectors shared after an earlier sync
    — are transmitted / stored once, exactly as the host ledger's set
    semantics).  Returns (bytes, ledger with known = Sbar_t).

    Per learner i with distinct active set s_i, known cache K and union
    U = ∪_i s_i (note s_i ⊆ U, so |U \\ s_i| = |U| - |s_i|):

      upload   |s_i| B_alpha + |s_i \\ K| B_x
      download |U| B_alpha + (|U| - |s_i|) B_x

    Summed over i, the upload needs only sum_i |s_i ∩ K|: one count over
    all rows at once, taken by a single stable sort of K with the rows
    (``rkhs.count_known``), with no per-row search.

    ``mask`` (m,) bool restricts the synchronization to a participating
    cohort (DESIGN.md Sec. 15): non-participating learners neither
    upload nor download, contribute nothing to the union, and the new
    coordinator cache ``known`` is the cohort union only — exactly the
    Sec. 3 formulas evaluated over the sampled learner subset (the
    host-side oracle is ``sync_bytes_kernel`` over the filtered id
    lists, pinned by tests/test_population.py).  ``mask=None`` is the
    full-participation case with ``m`` a static constant, unchanged.
    """
    import jax
    import jax.numpy as jnp

    from . import rkhs

    m, tau = stacked_ids.shape
    # The arithmetic below runs in int32 (x64 is disabled by default).
    # Worst case per sync: every learner ships tau distinct vectors and
    # downloads a full m*tau union — refuse shapes that could wrap.
    # A mask only shrinks the cohort, so the full-m worst case covers it.
    worst = m * tau * (bm.B_alpha + bm.B_x) * (m + 1)
    if worst >= 2**31:
        raise ValueError(
            f"per-sync bytes can reach {worst} for m={m}, tau={tau}, "
            f"d={bm.dim}, which overflows the device ledger's int32; "
            "use the host CommunicationLedger at this scale")
    if mask is not None:
        # a non-participating learner's id row becomes the empty set:
        # n_i = 0, in_known_i = 0, and it adds nothing to the union
        stacked_ids = jnp.where(mask[:, None], stacked_ids, -1)
    uniq, n = jax.vmap(rkhs.sorted_unique)(stacked_ids)    # (m, tau), (m,)
    union, u = rkhs.sorted_unique(uniq)                    # (m*tau,), ()
    in_known = rkhs.count_known(uniq, ledger.known)  # sum_i |s_i ∩ K|
    n_total = jnp.sum(n)
    downloaders = (jnp.sum(
        # reprolint: allow[ACC01] int32 cohort count; the worst >= 2**31 guard above covers it
        mask.astype(jnp.int32)) if mask is not None
        else m)
    total = (
        n_total * bm.B_alpha
        + (n_total - in_known) * bm.B_x
        + downloaders * u * bm.B_alpha
        + (downloaders * u - n_total) * bm.B_x
    )
    cap = ledger.known.shape[0]
    if union.shape[0] != cap:
        raise ValueError(
            f"union capacity {union.shape[0]} != ledger capacity {cap}")
    # reprolint: allow[ACC01] int32 is safe here: the worst >= 2**31 guard above rejects overflow
    return total.astype(jnp.int32), DeviceLedger(known=union)


def device_rejoin_bytes_kernel(
    bm: ByteModel, ref_ids: "jnp.ndarray", stacked_ids: "jnp.ndarray",
    rejoin: "jnp.ndarray",
) -> "jnp.ndarray":
    """Sec. 3 download bytes of re-``adopt``-ing rejoining learners
    (DESIGN.md Sec. 15): a learner that recovers from churn downloads
    the coordinator's current reference model before its first round
    back.  Per rejoining learner i with current id set s_i and the
    reference's distinct id set R, the link is the standard per-message
    delta encoding (``kernel_payload_bytes`` on the host):

        |R| B_alpha + |R \\ s_i| B_x

    ``ref_ids``: the reference model's sv_id array; ``stacked_ids``:
    (m, tau) learner ids; ``rejoin``: (m,) bool.  Returns int32 total.
    """
    import jax
    import jax.numpy as jnp

    from . import rkhs

    m, tau = stacked_ids.shape
    # same static worst-case envelope as device_sync_bytes_kernel: at
    # most m learners each download a full budget of novel vectors
    worst = m * max(int(ref_ids.reshape(-1).shape[0]), tau) \
        * (bm.B_alpha + bm.B_x)
    if worst >= 2**31:
        raise ValueError(
            f"per-round rejoin bytes can reach {worst} for m={m}, "
            "which overflows the int32 byte column; use the host "
            "accounting at this scale")
    ref_uniq, ref_n = rkhs.sorted_unique(ref_ids)
    sorted_rows, _ = jax.vmap(rkhs.sorted_unique)(stacked_ids)
    overlap = jax.vmap(
        lambda row: rkhs.count_members(ref_uniq, row))(sorted_rows)  # (m,)
    per = ref_n * bm.B_alpha + (ref_n - overlap) * bm.B_x
    # reprolint: allow[ACC01] int32 is safe here: the worst >= 2**31 guard above rejects overflow
    return jnp.sum(jnp.where(rejoin, per, 0)).astype(jnp.int32)


class CommunicationLedger:
    """Running C(T, m) with per-round records, used by the simulation
    driver and the figure benchmarks."""

    def __init__(self, bm: ByteModel):
        self.bm = bm
        self.coordinator_known: set = set()
        self.total = 0
        self.rounds: list[int] = []          # bytes per round
        self.sync_rounds: list[int] = []     # round indices of syncs

    def record_no_sync(self) -> None:
        self.rounds.append(0)

    def record_kernel_sync(self, local_ids: Sequence[np.ndarray], t: int) -> int:
        b, known = sync_bytes_kernel(self.bm, local_ids, self.coordinator_known)
        self.coordinator_known = known
        self.total += b
        self.rounds.append(b)
        self.sync_rounds.append(t)
        return b

    def record_linear_sync(self, num_params: int, m: int, t: int) -> int:
        b = sync_bytes_linear(num_params, m, self.bm.dtype_bytes)
        self.total += b
        self.rounds.append(b)
        self.sync_rounds.append(t)
        return b

    @property
    def cumulative(self) -> np.ndarray:
        return np.cumsum(np.asarray(self.rounds, dtype=np.int64))
