"""Demand generators of the benchmark, copied from the program's scenario
library so that a later change to that library does not move the
yardstick.

Originals: `repro.core.refresh.scenarios` (the open-loop trace kinds,
`_rs`, `_assemble`, `_locality`, `_poisson_arrivals`, the closed-loop
seed derivation) and `repro.core.refresh.workload` (`Workload.generate`,
`quantize_streams`). The calls on each `RandomState` are made in the
originals' order, so one (name, seed) gives the same streams here as
there. What differs: every parameter is read from a traffic file, and
the kinds are named by what they generate rather than by scenario.

Two shapes of demand:

  * `OpenTrace`: one open-loop arrival trace, parallel arrays sorted by
    arrival tick;
  * `ClosedStreams`: closed-loop per-core request streams, ``[C, N]``
    arrays with think gaps quantized to ticks.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass

import numpy as np

#: rows per bank exposed to the generators
N_ROWS = 4096


@dataclass(frozen=True)
class OpenTrace:
    """Open-loop request trace: parallel arrays sorted by `arrive`."""
    name: str
    arrive: np.ndarray          # int32 ticks, non-decreasing
    bank: np.ndarray            # int32 in [0, n_banks)
    row: np.ndarray             # int32 in [0, N_ROWS)
    sub: np.ndarray             # int32 in [0, n_subarrays)
    is_write: np.ndarray        # bool
    n_banks: int
    n_subarrays: int

    def __len__(self) -> int:
        return int(self.arrive.shape[0])

    def validate(self) -> "OpenTrace":
        n = len(self)
        if n == 0 or any(len(a) != n for a in
                         (self.bank, self.row, self.sub, self.is_write)):
            raise ValueError(f"{self.name}: ragged or empty trace")
        if (np.diff(self.arrive) < 0).any() or self.arrive[0] < 0:
            raise ValueError(f"{self.name}: arrivals must be sorted, >= 0")
        for a, hi in ((self.bank, self.n_banks), (self.row, N_ROWS),
                      (self.sub, self.n_subarrays)):
            if (a < 0).any() or (a >= hi).any():
                raise ValueError(f"{self.name}: index out of range")
        return self


@dataclass(frozen=True)
class ClosedStreams:
    """Closed-loop demand of one scenario: `params` holds the workload's
    parameters (n_cores, mlp, think_ns, row_hit_rate, write_ratio) and
    `seed` the seed its streams were drawn from."""
    name: str
    params: dict
    seed: int
    is_write: np.ndarray        # [C, N] bool
    bank: np.ndarray            # [C, N] int32
    row: np.ndarray             # [C, N] int32
    sub: np.ndarray             # [C, N] int32
    think: np.ndarray           # [C, N] int32 ticks (>= 0)
    n_banks: int
    n_subarrays: int

    @property
    def n_cores(self) -> int:
        return int(self.is_write.shape[0])

    @property
    def mlp(self) -> int:
        return int(self.params["mlp"])

    def validate(self) -> "ClosedStreams":
        C, N = self.is_write.shape
        if C != self.params["n_cores"] or N < 1 or self.mlp < 1:
            raise ValueError(f"{self.name}: bad stream shape {C}x{N}")
        for a in (self.bank, self.row, self.sub, self.think):
            if a.shape != (C, N):
                raise ValueError(f"{self.name}: ragged streams")
        if ((self.bank < 0).any() or (self.bank >= self.n_banks).any()
                or (self.sub < 0).any()
                or (self.sub >= self.n_subarrays).any()
                or (self.think < 0).any()):
            raise ValueError(f"{self.name}: index out of range")
        return self


# ------------------------------------------------------------- seeding
def _digest_int(key: str) -> int:
    return int.from_bytes(hashlib.sha256(key.encode()).digest()[:4],
                          "little")


def open_rs(name: str, seed: int) -> np.random.RandomState:
    """Per-(scenario, seed) stream of an open-loop trace."""
    return np.random.RandomState(_digest_int(f"{name}:{seed}"))


def closed_seed(name: str, seed: int) -> int:
    """The seed a closed-loop scenario's streams are drawn from."""
    return _digest_int(f"closed:{name}:{seed}")


# ----------------------------------------------------------- closed loop
def closed_streams(name: str, params: dict, n_banks: int, n_subarrays: int,
                   reqs: int, seed: int, dt_ns: float) -> ClosedStreams:
    """Per-core streams of one closed-loop workload, drawn from `seed`
    (an already-derived seed, `closed_seed`), think gaps quantized to
    ``int(think / dt_ns + 0.5)`` ticks."""
    rs = np.random.RandomState(seed)
    C = int(params["n_cores"])
    n = max(1, reqs // C)
    cols = {k: [] for k in ("is_write", "bank", "row", "sub", "think")}
    for _ in range(C):
        is_write = rs.rand(n) < params["write_ratio"]
        bank = rs.randint(0, n_banks, n)
        row = rs.randint(0, N_ROWS, n)
        reuse = rs.rand(n) < params["row_hit_rate"]
        for i in range(1, n):
            if reuse[i]:
                bank[i] = bank[i - 1]
                row[i] = row[i - 1]
        think = rs.exponential(params["think_ns"], n)
        cols["is_write"].append(is_write)
        cols["bank"].append(bank.astype(np.int32))
        cols["row"].append(row.astype(np.int32))
        cols["sub"].append((row % n_subarrays).astype(np.int32))
        cols["think"].append(np.maximum(
            0, np.floor(think / dt_ns + 0.5)).astype(np.int32))
    return ClosedStreams(name=name, params=dict(params), seed=seed,
                         n_banks=n_banks, n_subarrays=n_subarrays,
                         **{k: np.stack(v) for k, v in cols.items()}
                         ).validate()


# ------------------------------------------------------------ open loop
def _assemble(name, n_banks, n_subarrays, arrive, bank, row, is_write,
              sub=None) -> OpenTrace:
    order = np.argsort(arrive, kind="stable")
    arrive = np.asarray(arrive, np.int32)[order]
    bank = np.asarray(bank, np.int32)[order]
    row = np.asarray(row, np.int32)[order]
    is_write = np.asarray(is_write, bool)[order]
    sub = (row % n_subarrays if sub is None
           else np.asarray(sub, np.int32)[order])
    return OpenTrace(name, arrive, bank, row, np.asarray(sub, np.int32),
                     is_write, n_banks, n_subarrays)


def _locality(rs, bank, row, p_reuse: float):
    """With probability p_reuse, repeat the previous (bank, row)."""
    reuse = rs.rand(len(bank)) < p_reuse
    for i in range(1, len(bank)):
        if reuse[i]:
            bank[i] = bank[i - 1]
            row[i] = row[i - 1]
    return bank, row


def _poisson_arrivals(rs, n: int, mean_gap: float) -> np.ndarray:
    return np.floor(np.cumsum(rs.exponential(mean_gap, n))).astype(np.int64)


def poisson_locality(name, B, S, reqs, rs, *, mean_gap, p_reuse, write_p):
    """Poisson arrivals, uniform banks and rows, repeated (bank, row)
    with probability `p_reuse` (read_heavy, mixed, streaming)."""
    arrive = _poisson_arrivals(rs, reqs, mean_gap)
    bank = rs.randint(0, B, reqs)
    row = rs.randint(0, N_ROWS, reqs)
    bank, row = _locality(rs, bank, row, p_reuse)
    is_write = rs.rand(reqs) < write_p
    return _assemble(name, B, S, arrive, bank, row, is_write)


def write_bursts(name, B, S, reqs, rs, *, burst, phase_reads, read_gap,
                 drain_gap):
    """Quiet read phases punctuated by dense write bursts sized to trip
    the write-drain watermark (write_burst_draining)."""
    arrive, bank, row, is_write = [], [], [], []
    t, left = 0, reqs
    while left > 0:
        nr = min(phase_reads, left)
        for g in rs.exponential(read_gap, nr):
            t += max(1, int(g))
            arrive.append(t)
        bank.extend(rs.randint(0, B, nr))
        row.extend(rs.randint(0, N_ROWS, nr))
        is_write.extend([False] * nr)
        left -= nr
        nw = min(burst, left)
        for i in range(nw):
            arrive.append(t + 1 + i // 2)      # ~2 writes per tick
        bank.extend(rs.randint(0, B, nw))
        row.extend(rs.randint(0, N_ROWS, nw))
        is_write.extend([True] * nw)
        t += 1 + nw // 2 + drain_gap
        left -= nw
    return _assemble(name, B, S, arrive, bank, row, is_write)


def row_runs(name, B, S, reqs, rs, *, mean_gap, run_len, write_p):
    """Long same-row runs per bank (row_buffer_friendly)."""
    arrive = _poisson_arrivals(rs, reqs, mean_gap)
    n_runs = reqs // run_len + 1
    run_bank = rs.randint(0, B, n_runs)
    run_row = rs.randint(0, N_ROWS, n_runs)
    idx = np.arange(reqs) // run_len
    is_write = rs.rand(reqs) < write_p
    return _assemble(name, B, S, arrive, run_bank[idx], run_row[idx],
                     is_write)


def hot_banks(name, B, S, reqs, rs, *, hot_frac, n_hot, p_reuse, mean_gap,
              write_p):
    """Most traffic camps on `n_hot` banks (bank_camping)."""
    hot = rs.rand(reqs) < hot_frac
    bank = np.where(hot, rs.randint(0, n_hot, reqs), rs.randint(0, B, reqs))
    row = rs.randint(0, N_ROWS, reqs)
    bank, row = _locality(rs, bank.copy(), row, p_reuse)
    arrive = _poisson_arrivals(rs, reqs, mean_gap)
    is_write = rs.rand(reqs) < write_p
    return _assemble(name, B, S, arrive, bank, row, is_write)


def subarray_chase(name, B, S, reqs, rs, *, mean_gap, refi_pb_ticks,
                   write_p):
    """Accesses chase the subarray the per-bank round-robin refresh
    counter targets next (subarray_conflict_adversarial)."""
    arrive = _poisson_arrivals(rs, reqs, mean_gap)
    bank = rs.randint(0, B, reqs)
    target_sub = (arrive // refi_pb_ticks) % S
    row = (target_sub + S * rs.randint(0, N_ROWS // S, reqs)) % N_ROWS
    is_write = rs.rand(reqs) < write_p
    return _assemble(name, B, S, arrive, bank, row, is_write)


def replay_capture(name, B, S, reqs, rs, *, source, capture):
    """Replay the RD/WR serves of a small closed-loop source run as an
    open-loop trace, tiled to `reqs` (trace_replay). `capture(params,
    reqs, seed)` runs the source cell and returns its serves as
    (tick, bank, row, is_write) arrays; the source seed is drawn from
    `rs`."""
    src_seed = int(rs.randint(0, 2 ** 31 - 1))
    tick, bank, row, is_write = capture(
        source, int(source["reqs"]),
        closed_seed(source["scenario"], src_seed))
    base_n = len(tick)
    reps = max(1, -(-reqs // base_n))
    span = int(tick[-1]) + 16
    arrive = np.concatenate([tick + r * span for r in range(reps)])
    return _assemble(name, B, S, arrive[:reqs],
                     np.tile(bank, reps)[:reqs] % B,
                     np.tile(row, reps)[:reqs] % N_ROWS,
                     np.tile(is_write, reps)[:reqs])


#: open-loop kinds a traffic file may name
OPEN_KINDS = {f.__name__: f for f in (poisson_locality, write_bursts,
                                      row_runs, hot_banks, subarray_chase,
                                      replay_capture)}
