"""Configuration for the hashgraph framework.

The reference keeps its constants inline in source (coin period ``C = 6``,
stake passed to ``Node.__init__``, sim sizes as function args — SURVEY.md §5
"Config / flag system: none").  Here they live in one dataclass shared by the
oracle, the simulator, and the TPU pipeline so that both backends always agree
on the protocol parameters.

Archive knobs additionally honor ``SWIRLD_ARCHIVE_*`` environment
variables so a deployment can retune the background spill pipeline
without touching code: an explicit ``SwirldConfig`` field wins, then the
environment variable, then the built-in default (see
:func:`resolve_archive_settings`).  The flight-recorder knobs
(``SWIRLD_FLIGHTREC_*``, :func:`resolve_flightrec_settings`) and the
socket/cluster knobs (``SWIRLD_NET_*``, :func:`resolve_net_settings`)
follow the same precedence.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Tuple

#: built-in archive defaults (field -> (env var, default, parser))
_ARCHIVE_ENV = {
    "archive_compress_level": ("SWIRLD_ARCHIVE_COMPRESS_LEVEL", 1, int),
    "archive_queue_depth": ("SWIRLD_ARCHIVE_QUEUE_DEPTH", 8, int),
    "archive_async": (
        "SWIRLD_ARCHIVE_ASYNC", True,
        lambda v: v.strip().lower() not in ("0", "", "no", "false", "off"),
    ),
}


#: built-in flight-recorder defaults (field -> (env var, default, parser)).
#: Same precedence as the archive knobs: explicit SwirldConfig field >
#: SWIRLD_FLIGHTREC_* env var > built-in default.
_FLIGHTREC_ENV = {
    "flightrec_capacity": ("SWIRLD_FLIGHTREC_CAPACITY", 256, int),
    "flightrec_max_dumps": ("SWIRLD_FLIGHTREC_MAX_DUMPS", 16, int),
    "flightrec_dir": ("SWIRLD_FLIGHTREC_DIR", None, str),
}


def resolve_flightrec_settings(
    config: Optional["SwirldConfig"] = None,
) -> Dict:
    """Concrete flight-recorder settings: explicit config field >
    ``SWIRLD_FLIGHTREC_*`` env var > built-in default.  Returns
    ``{"capacity", "max_dumps", "dump_dir"}`` (``dump_dir`` may be
    ``None`` = record in memory, never auto-dump)."""
    out = {}
    names = {
        "flightrec_capacity": "capacity",
        "flightrec_max_dumps": "max_dumps",
        "flightrec_dir": "dump_dir",
    }
    for field, (env, default, parse) in _FLIGHTREC_ENV.items():
        v = getattr(config, field, None) if config is not None else None
        if v is None:
            raw = os.environ.get(env)
            v = parse(raw) if raw is not None else default
        out[names[field]] = v
    return out


#: built-in socket/cluster defaults (field -> (env var, default, parser)).
#: Same precedence as the archive/flightrec knobs: explicit SwirldConfig
#: field > SWIRLD_NET_* env var > built-in default.  Units are wall
#: seconds (the net layer is the deployment edge; consensus stays
#: logical-time) except the byte/count caps.
_NET_ENV = {
    "net_connect_timeout_s": ("SWIRLD_NET_CONNECT_TIMEOUT", 5.0, float),
    "net_call_timeout_s": ("SWIRLD_NET_CALL_TIMEOUT", 10.0, float),
    "net_max_frame_bytes": (
        "SWIRLD_NET_MAX_FRAME", (1 << 24) + (1 << 16), int,
    ),
    "net_tx_batch_bytes": ("SWIRLD_NET_TX_BATCH_BYTES", 64 << 10, int),
    "net_tx_max_bytes": ("SWIRLD_NET_TX_MAX_BYTES", 16 << 10, int),
    "net_tx_pool_txs": ("SWIRLD_NET_TX_POOL", 4096, int),
    "net_max_undecided": ("SWIRLD_NET_MAX_UNDECIDED", 2048, int),
    "net_gossip_interval_s": ("SWIRLD_NET_GOSSIP_INTERVAL", 0.01, float),
    "net_checkpoint_every_s": ("SWIRLD_NET_CHECKPOINT_EVERY", 1.0, float),
    "net_retry_tick_s": ("SWIRLD_NET_RETRY_TICK", 0.02, float),
    "net_redial_probe_s": ("SWIRLD_NET_REDIAL_PROBE", 0.05, float),
}


def resolve_net_settings(config: Optional["SwirldConfig"] = None) -> Dict:
    """Concrete socket/cluster settings: explicit config field >
    ``SWIRLD_NET_*`` env var > built-in default.  Returns
    ``{"connect_timeout_s", "call_timeout_s", "max_frame_bytes",
    "tx_batch_bytes", "tx_max_bytes", "tx_pool_txs", "max_undecided",
    "gossip_interval_s", "checkpoint_every_s", "retry_tick_s",
    "redial_probe_s"}`` (plain values, never ``None``).
    ``retry_tick_s`` converts the logical backoff ticks
    :class:`~tpu_swirld.transport.RetryPolicy` computes into real sleep
    seconds for socket deployments; ``redial_probe_s`` bounds the single
    re-probe wait after a failed transparent redial (a peer mid-restart
    whose new listener is not yet bound)."""
    out = {}
    for field, (env, default, parse) in _NET_ENV.items():
        v = getattr(config, field, None) if config is not None else None
        if v is None:
            raw = os.environ.get(env)
            v = parse(raw) if raw is not None else default
        out[field[len("net_"):]] = v
    return out


#: built-in production-day-soak defaults (field -> (env var, default,
#: parser)).  Same precedence as every other knob family: explicit
#: SwirldConfig field > SWIRLD_SOAK_* env var > built-in default.  The
#: soak orchestrator (:mod:`tpu_swirld.soak`) reads these for its spec
#: defaults; wall-second units, like the net knobs — the soak is a
#: deployment-edge harness, never part of the consensus core.
_SOAK_ENV = {
    "soak_horizon_s": ("SWIRLD_SOAK_HORIZON", 8.0, float),
    "soak_nodes": ("SWIRLD_SOAK_NODES", 4, int),
    "soak_tx_rate": ("SWIRLD_SOAK_TX_RATE", 150.0, float),
    "soak_clients": ("SWIRLD_SOAK_CLIENTS", 3, int),
    "soak_tx_bytes": ("SWIRLD_SOAK_TX_BYTES", 64, int),
    "soak_pareto_alpha": ("SWIRLD_SOAK_PARETO_ALPHA", 1.5, float),
    "soak_finality_budget_s": ("SWIRLD_SOAK_FINALITY_BUDGET", 6.0, float),
}


def resolve_soak_settings(config: Optional["SwirldConfig"] = None) -> Dict:
    """Concrete production-day-soak settings: explicit config field >
    ``SWIRLD_SOAK_*`` env var > built-in default.  Returns
    ``{"horizon_s", "nodes", "tx_rate", "clients", "tx_bytes",
    "pareto_alpha", "finality_budget_s"}`` (plain values, never
    ``None``).  ``finality_budget_s`` is the composite verdict's p99
    submission→decided latency ceiling; ``pareto_alpha`` shapes the
    traffic generator's heavy-tailed inter-arrival draw."""
    out = {}
    for field, (env, default, parse) in _SOAK_ENV.items():
        v = getattr(config, field, None) if config is not None else None
        if v is None:
            raw = os.environ.get(env)
            v = parse(raw) if raw is not None else default
        out[field[len("soak_"):]] = v
    return out


def resolve_archive_settings(config: Optional["SwirldConfig"] = None) -> Dict:
    """Concrete archive settings: explicit config field > ``SWIRLD_ARCHIVE_*``
    env var > built-in default.  Returns ``{"compress_level", "queue_depth",
    "async_spill"}`` (plain values, never ``None``)."""
    out = {}
    names = {
        "archive_compress_level": "compress_level",
        "archive_queue_depth": "queue_depth",
        "archive_async": "async_spill",
    }
    for field, (env, default, parse) in _ARCHIVE_ENV.items():
        v = getattr(config, field, None) if config is not None else None
        if v is None:
            raw = os.environ.get(env)
            v = parse(raw) if raw is not None else default
        out[names[field]] = v
    return out


@dataclasses.dataclass(frozen=True)
class SwirldConfig:
    """Protocol + engine parameters.

    Attributes:
      n_members: number of members (nodes) in the population.
      stake: per-member stake; ``None`` means one unit each.  Supermajority
        is *strictly more than* 2/3 of total stake, evaluated in exact
        integer arithmetic (``3 * x > 2 * tot``) on both backends.
      coin_period: every ``coin_period``-th fame-voting round is a coin
        round (the reference's ``C = 6``).
      backend: ``"python"`` (oracle) or ``"tpu"`` (batched JAX pipeline) —
        the pluggable seam named in BASELINE.json.
      seed: base RNG seed for simulations.
      mesh_shape: device mesh as ``{axis_name: size}`` for the sharded
        pipeline; ``None`` → single device.
      block_size: event-block tile for the blockwise ancestry kernel.
      max_rounds: static bound on the number of created rounds for device
        tables (checked against the actual data; raise if exceeded).
    """

    n_members: int = 4
    stake: Optional[Tuple[int, ...]] = None
    coin_period: int = 6
    backend: str = "python"
    seed: int = 0
    mesh_shape: Optional[Dict[str, int]] = None
    block_size: int = 256
    max_rounds: int = 256
    max_orphans: int = 4096      # unknown-parent events parked per node
    max_orphan_bytes: int = 8 << 20  # byte budget for the orphan buffer
                                 # (count cap alone admits ~4 GiB of
                                 # max-payload events from one signer)
    max_want_rounds: int = 32    # want-list round-trips per sync
    want_ancestor_depth: int = 64  # ask_events ships, per wanted event, a
                                 # self-ancestor chain of up to this many
                                 # events (the wanted event included), so
                                 # one successful want round-trip closes a
                                 # whole chain gap (not one parent level)
                                 # — deep-orphan recovery under loss
    tpu_min_batch: int = 1       # backend='tpu': min new events per device
                                 # pass (higher amortizes the batch replay;
                                 # consensus output is identical, delayed)

    # --- gossip resilience (transport retry / reply caps / quarantine) ---
    # Retry/backoff units are logical clock ticks (see transport.RetryPolicy);
    # nothing sleeps — the sim records delays, real deployments may sleep.
    retry_attempts: int = 3      # total transport attempts per call
    retry_backoff: float = 1.0   # first-retry backoff (doubles per retry)
    retry_backoff_cap: float = 8.0
    retry_jitter: float = 0.5    # extra uniform [0, jitter*delay] per retry
    retry_deadline: float = 16.0  # per-peer total backoff budget per pull
    breaker_failures: int = 4    # consecutive transport failures to open
    breaker_misbehavior: int = 12  # attributable-garbage strikes to open
    breaker_cooldown: float = 24.0  # ticks before a half-open probe
    max_reply_bytes: int = 1 << 24  # reject larger sync/want replies
    max_reply_events: int = 65536   # server-side cap on events per reply
    quarantine_forkers: bool = False  # detected equivocators trip the
                                      # circuit breaker immediately
    max_fork_branches: int = 8   # sync-reply amplification bound: branch
                                 # tails walked per forked creator per
                                 # reply (deterministic sorted selection;
                                 # the earliest fork-group proof always
                                 # ships, residue recovers via want-lists)

    # --- slab archive / background spill pipeline (store.archive) ---
    # None = fall back to SWIRLD_ARCHIVE_* env var, then built-in default
    # (resolve_archive_settings).
    archive_compress_level: Optional[int] = None  # zlib level for spilled
                                                  # rows (default 1)
    archive_queue_depth: Optional[int] = None     # bounded spill-queue depth;
                                                  # a full queue backpressures
                                                  # the spiller (default 8)
    archive_async: Optional[bool] = None          # background packing worker
                                                  # on/off (default on; results
                                                  # are identical either way —
                                                  # drain barriers serialize
                                                  # every read)

    # --- black-box flight recorder (obs.flightrec) ---
    # None = fall back to SWIRLD_FLIGHTREC_* env var, then built-in
    # default (resolve_flightrec_settings).
    flightrec_capacity: Optional[int] = None  # ring entries kept per node
                                              # (default 256)
    flightrec_max_dumps: Optional[int] = None  # post-mortem dump files per
                                               # recorder before triggers
                                               # stop writing (default 16)
    flightrec_dir: Optional[str] = None       # dump directory; None =
                                              # in-memory only, no files

    # --- socket transport / real-process cluster (net/) ---
    # None = fall back to SWIRLD_NET_* env var, then built-in default
    # (resolve_net_settings).  Wall-second knobs live HERE, at the
    # deployment edge; the consensus core stays logical-time.
    net_connect_timeout_s: Optional[float] = None  # TCP connect deadline
    net_call_timeout_s: Optional[float] = None     # per-RPC reply deadline
    net_max_frame_bytes: Optional[int] = None      # frame ceiling (must
                                                   # admit max_reply_bytes)
    net_tx_batch_bytes: Optional[int] = None       # tx batch payload cap
    net_tx_max_bytes: Optional[int] = None         # per-tx size cap
    net_tx_pool_txs: Optional[int] = None          # pending-pool cap
    net_max_undecided: Optional[int] = None        # undecided-window
                                                   # admission threshold
    net_gossip_interval_s: Optional[float] = None  # gossip loop pacing
    net_checkpoint_every_s: Optional[float] = None  # checkpoint cadence
    net_retry_tick_s: Optional[float] = None       # seconds per logical
                                                   # RetryPolicy backoff tick

    # --- dynamic membership (membership/) ---
    membership_delay: int = 4    # rounds between a membership tx's decision
                                 # (round_received of its carrier) and the
                                 # first round the new MemberEpoch governs

    def stakes(self) -> Tuple[int, ...]:
        if self.stake is not None:
            if len(self.stake) != self.n_members:
                raise ValueError(
                    f"stake has {len(self.stake)} entries for "
                    f"{self.n_members} members"
                )
            return tuple(int(s) for s in self.stake)
        return tuple(1 for _ in range(self.n_members))

    @property
    def total_stake(self) -> int:
        return sum(self.stakes())

    def supermajority(self, amount: int) -> bool:
        """True iff ``amount`` is strictly more than 2/3 of total stake."""
        return 3 * amount > 2 * self.total_stake
