"""The program's named scopes and kernel names as compiled HLO text shows
them, for the tests that check where they land.

Each instruction's ``metadata={op_name="..."}`` holds the path of
``jax.named_scope`` names it was traced under; backward and recomputed
copies keep the forward's names inside transform wrappers
(``transpose(jvp(attention))``).  A Pallas kernel's ``name=`` is the path
entry just above ``pallas_call``.
"""

import re
from typing import Iterator, NamedTuple, Optional

# the scopes that models/, train/ and serve/ open
SCOPES = ("embed", "layers", "attention", "mlp", "moe", "head", "kv_cache",
          "optimizer", "grad_reduce")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+) = (\(.*?\)|\S+) ([a-z][\w-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")
_COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


class Instruction(NamedTuple):
    name: str
    shape: str        # result shape, a tuple's in parentheses
    opcode: str
    text: str
    op_name: str      # "" without metadata


def instructions(hlo: str) -> Iterator[Instruction]:
    """Every instruction of an HLO module's text, fused computations
    included."""
    for line in hlo.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            meta = _OP_NAME.search(line)
            yield Instruction(m.group(1), m.group(2), m.group(3), line.strip(),
                              meta.group(1) if meta else "")


def _unwrap(part: str) -> str:
    m = _WRAPPED.match(part)
    while m:
        part = m.group(1)
        m = _WRAPPED.match(part)
    return part


def scope_of(op_name: str) -> Optional[str]:
    """The innermost of ``SCOPES`` on an ``op_name`` path, or None."""
    for part in reversed(op_name.split("/")):
        if _unwrap(part) in SCOPES:
            return _unwrap(part)
    return None


def kernel_of(op_name: str) -> Optional[str]:
    """The ``name=`` of the Pallas kernel an ``op_name`` path calls."""
    parts = [_unwrap(p) for p in op_name.split("/")]
    if len(parts) >= 2 and parts[-1] == "pallas_call":
        return parts[-2]
    return None


def is_collective(ins: Instruction) -> bool:
    """A collective, an async one's start or done, or a fusion XLA named
    after the collective in it."""
    return bool(_COLLECTIVE.match(ins.opcode) or _COLLECTIVE.match(ins.name))


def is_scalar(ins: Instruction) -> bool:
    """Every element of the result is a scalar (``f32[]``)."""
    return all(dims == "" for dims in re.findall(r"[a-z]+\d*\[([\d,]*)\]", ins.shape))
