"""MemberEpoch repack: remap the member axis at an epoch boundary.

The union-registry invariant (``membership.epoch``) makes this pass
cheap by construction: member indices never change, joins *append* rows,
leaves keep their rows with stake zeroed.  So an epoch repack is

- **host side**: extend the live :class:`~tpu_swirld.packing.Packer`
  with the new member rows (``add_member``) and swap its stake vector
  (``set_stake``) — the anc/sees slabs, ssm column store, witness
  tables, and fork-pair ledgers are event- or (round, slot)-indexed and
  survive untouched;
- **device side**: one jitted stage (:func:`repack_stage`) that pads the
  ``(M, K)`` member table with fresh ``-1`` rows and emplaces the new
  epoch's stake vector.  The stage is registered with the flow-audit
  spec catalog (``analysis.flow.stages``) so the scale audit covers its
  memory envelope like every other pipeline stage.

Cost model (README "Dynamic membership & stake"): O(M' · K) int32 for
the member table copy plus O(M') for the stake swap — independent of
the event count, so repack latency is flat while ev/s scales.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_swirld.membership.epoch import MemberEpoch
from tpu_swirld.packing import Packer


@functools.partial(jax.jit, static_argnames=("n_members_new",))
def repack_stage(member_table, stake_new, *, n_members_new: int):
    """Device member-axis extension: pad ``member_table`` from ``(M, K)``
    to ``(n_members_new, K)`` with ``-1`` rows (new members own no packed
    events yet) and return it alongside the new epoch's stake vector.

    Shapes are static per (M, M', K) triple, so a steady churn rate hits
    the jit cache after one compile per epoch-size bucket.
    """
    m, k = member_table.shape
    pad = n_members_new - m
    table = jnp.concatenate(
        [
            member_table,
            jnp.full((pad, k), -1, dtype=member_table.dtype),
        ],
        axis=0,
    ) if pad > 0 else member_table
    return table, jnp.asarray(stake_new, dtype=jnp.int32)


@dataclasses.dataclass(frozen=True)
class RepackStats:
    """One epoch boundary's member-axis repack, for bench/obs."""

    epoch_id: int
    activation_round: int
    members_before: int
    members_after: int
    rows_added: int
    seconds: float

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def repack_packer(packer: Packer, epoch: MemberEpoch) -> RepackStats:
    """Apply ``epoch`` to a live packer: append the joined members'
    rows, swap the stake vector, and run the device stage so the padded
    member table + stake land on the accelerator the same way the
    pipeline's ``prepare_inputs`` ships them."""
    t0 = time.perf_counter()
    before = len(packer.members)
    for pk in epoch.members:
        if pk not in packer.member_index:
            packer.add_member(pk)
    after = len(packer.members)
    if after != len(epoch.members):
        raise ValueError(
            "epoch registry is not an extension of the packer's members "
            "(union-registry invariant violated)"
        )
    packer.set_stake(epoch.stake)
    # device-side extension: same arrays pack() would snapshot, and
    # dispatched through obs.stage_call so the engine recorder and the
    # flow-audit coverage probe see the boundary like any other
    # pipeline stage
    from tpu_swirld import obs

    k = max(int(packer._member_counts.max(initial=0)), 1)
    table = packer._member_table[:before, :k]
    new_table, new_stake = obs.stage_call(
        "membership.repack_stage",
        repack_stage,
        np.ascontiguousarray(table),
        np.asarray(epoch.stake, dtype=np.int32),
        n_members_new=after,
    )
    if new_table.shape != (after, k) or new_stake.shape != (after,):
        raise AssertionError("repack stage shape mismatch")
    return RepackStats(
        epoch_id=epoch.epoch_id,
        activation_round=epoch.activation_round,
        members_before=before,
        members_after=after,
        rows_added=after - before,
        seconds=time.perf_counter() - t0,
    )
