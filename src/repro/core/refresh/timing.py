"""JEDEC-style timing parameters for the DRAM refresh simulator.

Values follow the HPCA-14 DSARP paper (Table 2/3): DDR3-1333-class device
timings, with tRFC scaling across 8/16/32 Gb densities. All times in ns.

A part with bank groups (DDR4, DDR5) sets `n_bank_groups` > 1 and both
bank-group column-to-column delays, `tCCD_L` (same group) and `tCCD_S`
(other group). Its per-bank level refreshes a same-bank set (DDR5
REFsb): bank k of every group of a rank at once (docs/tick-contract.md
section 3), with `tRFC_pb` as the set's refresh latency (tRFCsb).

A part whose controller addresses each per-bank refresh (LPDDR4 REFpb)
may state the two issue rules of such refreshes over the refresh units of
one rank (docs/tick-contract.md section 3): `tpbR2pbR`, the least time
between two of them on a rank (0: none), and `refpb_rounds`, that no unit
is refreshed again before every unit of its rank has been.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class DramTiming:
    density_gb: int = 8
    n_banks: int = 8              # banks PER RANK
    n_subarrays: int = 8          # subarrays exposed for SARP
    n_ranks: int = 1              # ranks per channel
    n_channels: int = 1           # channels (one data bus each)
    n_bank_groups: int = 1        # bank groups per rank (divides n_banks)

    # core timings (ns)
    tRCD: float = 13.75           # activate -> column
    tRP: float = 13.75            # precharge
    tCL: float = 13.75            # CAS latency
    tBL: float = 6.0              # burst on the shared data bus
    tWR: float = 15.0             # write recovery
    tWTR: float = 7.5             # write->read turnaround
    tRTW: float = 7.5             # read->write turnaround
    tRTR: float = 3.0             # rank-to-rank bus turnaround (ODT swap)
    # bank-group column-to-column delays; given iff n_bank_groups > 1 (a
    # part without groups has one tCCD, folded into the one start per
    # channel per tick)
    tCCD_L: Optional[float] = None    # same bank group
    tCCD_S: Optional[float] = None    # different bank group

    # refresh
    tREFI: float = 7812.5         # per-rank refresh interval
    tRFC_ab: float = 350.0        # all-bank refresh latency (density-scaled)
    tRFC_pb: float = 90.0         # per-bank refresh latency (density-scaled)
    refresh_budget: int = 8       # max postponed/pulled-in commands (JEDEC)
    # per-bank refresh issue rules over the refresh units of one rank
    tpbR2pbR: float = 0.0         # least gap between two starts (0: none)
    refpb_rounds: bool = False    # one per unit per round of the rank

    # SARP: a refreshing bank can serve other-subarray accesses with a small
    # added latency for the shared peripheral handoff (paper §5: row-address
    # mux + separate subarray sense amps; I/O bus is untouched).
    sarp_penalty: float = 4.5

    def __post_init__(self):
        if self.n_bank_groups < 1 or self.n_banks % self.n_bank_groups:
            raise ValueError(
                f"n_bank_groups={self.n_bank_groups} must divide "
                f"n_banks={self.n_banks}")
        given = [k for k in ("tCCD_L", "tCCD_S")
                 if getattr(self, k) is not None]
        # like a missing or unexpected argument: the bank-group timings
        # come with bank groups, and only with them
        if self.n_bank_groups > 1 and len(given) < 2:
            raise TypeError(
                f"DramTiming with n_bank_groups={self.n_bank_groups} "
                "needs both tCCD_L and tCCD_S")
        if self.n_bank_groups == 1 and given:
            raise TypeError(
                f"DramTiming got {', '.join(given)} with n_bank_groups=1: "
                "bank-group timings need bank groups")
        if given and self.tCCD_L < self.tCCD_S:
            raise ValueError(f"tCCD_L={self.tCCD_L} < tCCD_S={self.tCCD_S}")
        if not self.tpbR2pbR >= 0:
            raise ValueError(f"tpbR2pbR={self.tpbR2pbR} must be >= 0 ns")
        if self.tpbR2pbR * self.banks_per_group > self.tREFI:
            # a rank's round of spaced refreshes outlasts tREFI: no
            # controller can keep the postpone budget
            raise ValueError(
                f"tpbR2pbR={self.tpbR2pbR} ns x {self.banks_per_group} "
                f"refresh units a rank exceeds tREFI={self.tREFI} ns")
        if not isinstance(self.refpb_rounds, bool):
            raise TypeError(
                f"refpb_rounds={self.refpb_rounds!r} must be a bool")

    @property
    def has_refpb_rules(self) -> bool:
        """Whether per-bank refreshes obey either issue rule."""
        return self.tpbR2pbR > 0 or self.refpb_rounds

    @property
    def n_ranks_total(self) -> int:
        """Global rank count: every (channel, rank) pair. Global rank
        index gr = channel * n_ranks + rank; global bank index
        gb = gr * n_banks + bank."""
        return self.n_channels * self.n_ranks

    @property
    def n_banks_total(self) -> int:
        return self.n_ranks_total * self.n_banks

    @property
    def tREFI_pb(self) -> float:
        """Per-bank refresh cadence: tREFI spread uniformly over every
        bank in the hierarchy (reduces to tREFI / n_banks at one rank)."""
        return self.tREFI / self.n_banks_total

    @property
    def banks_per_group(self) -> int:
        return self.n_banks // self.n_bank_groups

    @property
    def n_refresh_units(self) -> int:
        """Per-bank-level refresh units over the hierarchy: one per bank,
        or with bank groups one same-bank set per (rank, bank of a
        group)."""
        return self.n_ranks_total * self.banks_per_group

    def rank_of(self, gb: int) -> int:
        """Global rank index of global bank `gb`."""
        return gb // self.n_banks

    def channel_of(self, gb: int) -> int:
        """Channel index of global bank `gb`."""
        return gb // (self.n_ranks * self.n_banks)

    @property
    def row_hit(self) -> float:
        return self.tCL + self.tBL

    @property
    def row_miss(self) -> float:
        return self.tRP + self.tRCD + self.tCL + self.tBL


# density -> (tRFC_ab, tRFC_pb), HPCA-14 Table 3 density projections
# (tRFC_pb/tRFC_ab ~ 0.43, the LPDDR3 8Gb ratio, held across densities)
_TRFC = {8: (350.0, 150.0), 16: (530.0, 230.0), 32: (890.0, 380.0)}

DENSITIES = tuple(sorted(_TRFC))


def spacing_ticks(ns: float, dt_ns: float) -> int:
    """A least spacing in whole ticks: ``ceil(ns / dt_ns)``, 0 for none.
    Rounded up, unlike the latencies' nearest tick, since it is a floor
    the commands must keep (a hair of float error does not add a tick)."""
    return max(1, math.ceil(ns / dt_ns - 1e-9)) if ns > 0 else 0


def timing_for_density(density_gb: int, **kw) -> DramTiming:
    ab, pb = _TRFC[density_gb]
    return DramTiming(density_gb=density_gb, tRFC_ab=ab, tRFC_pb=pb, **kw)


def refresh_units(n_banks_total: int, n_banks: int,
                  n_bank_groups: int = 1) -> list[tuple[int, ...]]:
    """Global banks of each per-bank-level refresh unit, in unit order
    ``u = gr * K + k`` (K = banks per group): bank k of every group of
    global rank gr, ``gr * n_banks + g * K + k`` for each group g. Without
    bank groups every unit is one bank (``u == gb``)."""
    K = n_banks // n_bank_groups
    return [tuple(gr * n_banks + g * K + k for g in range(n_bank_groups))
            for gr in range(n_banks_total // n_banks) for k in range(K)]
