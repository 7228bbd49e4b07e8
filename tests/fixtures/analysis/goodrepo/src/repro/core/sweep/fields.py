"""Packed arbitration-score bit-field layout — the single source of truth.

The sweep engine's arbitration step packs its FR-FCFS-style priority into
one int32 per (cell, bank) so a single argmax picks the winner. The field
layout below is shared by every consumer — `sweep/arbiter.py` (the
xp-generic scoring definitions) and the normative field table in
`docs/tick-contract.md` — and is mechanically cross-checked by the
`bitfield` pass of `repro.analysis` (`python tools/check_contract.py
--pass bitfield`): redefining any of these names downstream, or letting
the doc table drift, fails CI.

Layout (descending priority):

    bit 25      W_WRITE   drain-mode write
    bits 22-24  W_OCC     demand occupancy, clamped to OCC_CAP (closed mode)
    bit 21      W_HIT     row-buffer hit
    bit 20      W_NOCONF  no subarray of the bank mid-refresh
    bits 0-19   age       min(t - arrive, AGE_CAP)

The maximum packed score is W_WRITE + OCC_CAP * W_OCC + W_HIT + W_NOCONF + AGE_CAP
< 2**26, leaving int32 headroom (scores must stay strictly positive and
-1 is the ineligible sentinel).
"""
from __future__ import annotations

#: bits of the age field; age saturates to AGE_CAP so the packed score
#: stays within int32
AGE_BITS = 20
AGE_CAP = (1 << AGE_BITS) - 1

#: no-refresh-conflict flag (single bit; set when no subarray of the
#: bank is mid-refresh)
NOCONF_SHIFT = 20
W_NOCONF = 1 << NOCONF_SHIFT

#: row-buffer hit flag (single bit)
HIT_SHIFT = 21
W_HIT = 1 << HIT_SHIFT

#: demand-side occupancy field (closed-loop queue depth), OCC_BITS wide
OCC_SHIFT = 22
OCC_BITS = 3
W_OCC = 1 << OCC_SHIFT
OCC_CAP = (1 << OCC_BITS) - 1

#: drain-mode write flag (single bit; top of the packed score)
WRITE_SHIFT = 25
W_WRITE = 1 << WRITE_SHIFT

#: exclusive top bit of the packed layout — must stay < 31 for int32
SCORE_BITS = WRITE_SHIFT + 1

__all__ = ["AGE_BITS", "AGE_CAP", "NOCONF_SHIFT", "W_NOCONF", "HIT_SHIFT", "W_HIT", "OCC_SHIFT",
           "OCC_BITS", "W_OCC", "OCC_CAP", "WRITE_SHIFT", "W_WRITE",
           "SCORE_BITS"]
