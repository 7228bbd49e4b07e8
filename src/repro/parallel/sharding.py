"""Logical-axis sharding (MaxText-style) for the whole framework.

Model code annotates tensors with *logical* axis names via :func:`shd`;
a context-installed rule table maps them to physical mesh axes. With no
context installed (CPU smoke tests), :func:`shd` is the identity.

Physical meshes (launch/mesh.py):
  single-pod: (data=16, model=16)          -- 256 chips
  multi-pod : (pod=2, data=16, model=16)   -- 512 chips

Logical axes:
  batch    -> data (and pod when multi-pod): DP/FSDP batch axis
  embed    -> None: the residual d_model axis (replicated in compute)
  fsdp     -> data: parameter d_model rows (ZeRO-3 sharding of params/opt)
  seq      -> model: sequence-parallel residual stream between layers
  heads    -> model: attention-head TP
  kv_heads -> model IF the arch's kv head count divides, else None
  ff       -> model: MLP hidden TP
  vocab    -> model: embedding/logits TP
  expert   -> model: expert parallelism (MoE)
  pages    -> model: decode KV-cache sequence ("bank") sharding
  stack    -> None: the scanned layer axis (never sharded)
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LOGICAL_RULES_SINGLE_POD: dict[str, tuple] = {
    "batch": ("data",),
    "fsdp": ("data",),
    "embed": (),
    "seq": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),   # masked off per-arch when not divisible
    "ff": ("model",),
    "vocab": ("model",),
    "expert": ("model",),
    "pages": ("model",),
    "stack": (),
    "state": (),
}

LOGICAL_RULES_MULTI_POD = dict(LOGICAL_RULES_SINGLE_POD, batch=("pod", "data"))


class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: Optional[dict] = None
    disabled: set = set()


_CTX = _Ctx()


def set_sharding_context(mesh: Optional[Mesh], rules: Optional[dict],
                         disabled: Optional[set] = None) -> None:
    _CTX.mesh = mesh
    _CTX.rules = rules
    _CTX.disabled = disabled or set()


@contextlib.contextmanager
def sharding_context(mesh: Optional[Mesh], rules: Optional[dict],
                     disabled: Optional[set] = None):
    prev = (_CTX.mesh, _CTX.rules, _CTX.disabled)
    set_sharding_context(mesh, rules, disabled)
    try:
        yield
    finally:
        set_sharding_context(*prev)


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def axis_size(logical: str) -> int:
    """Product of mesh-axis sizes a logical axis maps to (1 w/o context)."""
    if _CTX.mesh is None or _CTX.rules is None or logical in _CTX.disabled:
        return 1
    n = 1
    for ax in _CTX.rules.get(logical, ()):
        n *= _CTX.mesh.shape[ax]
    return n


def logical_to_spec(axes: tuple) -> P:
    """Resolve a tuple of logical axis names (or None) to a PartitionSpec."""
    rules = _CTX.rules or {}
    out = []
    for a in axes:
        if a is None or a in _CTX.disabled:
            out.append(None)
            continue
        phys = tuple(ax for ax in rules.get(a, ()) if ax is not None)
        out.append(phys if len(phys) > 1 else (phys[0] if phys else None))
    return P(*out)


def shd(x: jax.Array, *axes) -> jax.Array:
    """Constrain `x`'s sharding by logical axis names; identity w/o context."""
    if _CTX.mesh is None or _CTX.rules is None:
        return x
    assert len(axes) == x.ndim, f"rank mismatch: {axes} vs {x.shape}"
    spec = logical_to_spec(axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_CTX.mesh, spec))


def named_sharding(*axes) -> Optional[NamedSharding]:
    if _CTX.mesh is None:
        return None
    return NamedSharding(_CTX.mesh, logical_to_spec(axes))
