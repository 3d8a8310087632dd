"""Split one program's device time by the model's named scopes.

The model marks regions of its steps with ``jax.named_scope``
(``repro.models.scopes``: ``qkv``, ``kv_write``, ``kv_carry``,
``attention``, ``mlp``, ``lm_head``, ``ssd``).  Each name lands in the
``op_name`` metadata of the instructions traced inside it, in the program's
compiled HLO text.  A device trace names each operation after its
instruction (``%fusion.12 = bf16[...] fusion(...), ...``).

:func:`instruction_scopes` maps every instruction of a compiled text to the
innermost scope its ``op_name`` names.  A fusion with no ``op_name`` of its
own takes the scope its result is computed in: the scope of the fused
instructions that feed its root, where they all name one.  An instruction
in no scope is ``unscoped``: scan machinery, the embedding gather, the
final norm, and the copies and selects the compiler adds without scope
metadata.  In the serving cells' decode step those include the token
write's whole-slice select and the slice's layout copies into the write's
scatter loop and into attention's chunk loop (``PERF.md`` section 5): the
price of ``kv_write`` and ``attention`` that reads in ``unscoped``.

:func:`split` sums one program's operation time per scope.  Where
operations nest (a loop around its body), each instant counts once, for the
innermost operation.  Where under 99% of the program's operation time
belongs to instructions of the text, the text is not the program that ran,
and the split is None: a map gone stale must not read as a small number.

:func:`decode_ms` is what the ``decode_ms.<scope>`` readers share: it
compiles the serving cell's decode step again (the same function at the
shapes the window ran, which the persistent compile cache holds), splits
the traced window's ``_decode_step`` programs, and gives device ms per
step.  A program without the scopes gives None.
"""

from __future__ import annotations

import collections
import re
import weakref
from bisect import bisect_left, bisect_right
from typing import Dict, List, Optional, Sequence, Tuple

UNSCOPED = "unscoped"
MIN_MATCHED = 0.99
DECODE_PROGRAM = "_decode_step"

_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,]+)")
_OPERAND = re.compile(r"(?<![=\w])%([\w.\-]+)")  # not a called computation


def _innermost(op_name: str, names: Sequence[str]) -> Optional[str]:
    found = None
    for part in op_name.split("/"):
        if part in names:
            found = part
    return found


def instruction_scopes(text: str, names: Sequence[str]) -> Dict[str, str]:
    """Instruction name -> the innermost of ``names`` in its ``op_name``
    (fusions without one: see the module's docstring), or ``unscoped``."""
    own: Dict[str, Optional[str]] = {}
    marked = set()                       # instructions with an op_name
    operands: Dict[str, List[str]] = {}
    roots: Dict[str, str] = {}           # computation -> its ROOT
    fused: Dict[str, str] = {}           # fusion -> the computation it calls
    computation = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head and not line.startswith(" "):
            computation = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        own[name] = _innermost(op.group(1), names) if op else None
        if op:
            marked.add(name)
        operands[name] = _OPERAND.findall(rest)
        if line.lstrip().startswith("ROOT ") and computation is not None:
            roots[computation] = name
        called = _CALLS.search(rest)
        if called and " fusion(" in rest:
            fused[name] = called.group(1)

    feeding: Dict[str, set] = {}

    def feeds(name: str) -> set:
        """The scopes of the nearest instructions with an ``op_name`` that
        the value of ``name`` is computed from, inside its computation."""
        if name not in feeding:
            feeding[name] = set()  # a cycle reads as empty
            if name in marked:
                found = {own[name] or UNSCOPED}
            else:
                found = set().union(*(feeds(o) for o in operands.get(name, ())))
            feeding[name] = found
        return feeding[name]

    out = {}
    for name, scope in own.items():
        if name not in marked and name in fused:
            found = feeds(roots.get(fused[name], ""))
            scope = next(iter(found)) if len(found) == 1 else None
        out[name] = scope or UNSCOPED
    return out


def _exclusive(ops: List[Tuple[str, int, int]]):
    """``(name, ns)`` for each operation: its own time, less the time of the
    operations nested in it (each instant once, for the innermost)."""
    ops = sorted(ops, key=lambda op: (op[1], -op[2]))
    own = [e - s for _, s, e in ops]
    stack: List[int] = []
    for i, (_, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent = stack[-1]
            own[parent] -= min(e, ops[parent][2]) - s
        stack.append(i)
    return [(ops[i][0], own[i]) for i in range(len(ops))]


def instruction_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def program_ops(trace, program: str) -> Tuple[Dict[str, int], int]:
    """(own ns of each instruction, runs) over the programs whose name holds
    ``program`` and that lie inside the trace's window, on every device."""
    lo, hi = trace.window
    own: Dict[str, int] = collections.defaultdict(int)
    runs = 0
    for device in trace.devices:
        ops = sorted(device.ops, key=lambda op: op[1])
        starts = [s for _, s, _ in ops]
        for name, start, end in device.programs:
            if program not in name or start < lo or end > hi:
                continue
            runs += 1
            inside = [op for op in ops[bisect_left(starts, start):bisect_right(starts, end)]
                      if op[2] <= end]
            for op, ns in _exclusive(inside):
                own[instruction_name(op)] += ns
    return dict(own), runs


def split(trace, program: str, scopes: Dict[str, str]) -> Optional[Tuple[Dict[str, float], int]]:
    """(device seconds per scope, runs) of :func:`program_ops`; None where
    no such program ran or under 99% of its operation time matched an
    instruction of ``scopes``."""
    own, runs = program_ops(trace, program)
    totals: Dict[str, float] = collections.defaultdict(float)
    seen = sum(own.values())
    for name, ns in own.items():
        if name in scopes:
            totals[scopes[name]] += ns
    if not runs or not seen or sum(totals.values()) < MIN_MATCHED * seen:
        return None
    return {k: v * 1e-9 for k, v in totals.items()}, runs


# -- the serving cells' decode step -------------------------------------------------


def decode_text(cfg, slots: int, max_len: int, device=None) -> str:
    """The compiled HLO text of ``Server``'s decode step for the model
    ``cfg``: ``slots`` tokens against a cache of ``max_len`` positions, on
    ``device`` (the first of JAX's by default)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.models import Model
    from repro.runtime.serving import ServeConfig, Server

    chip = SingleDeviceSharding(device if device is not None else jax.devices()[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)

    params = on_chip(Model(cfg).abstract_init()[0])
    server = Server(cfg, ServeConfig(batch_slots=slots, max_len=max_len), params)
    one = jax.eval_shape(server._prefill_fn, params, jax.ShapeDtypeStruct((1, 1), jnp.int32))[1]
    state = on_chip(Server._tree_map_batch(
        lambda x, ax: jax.ShapeDtypeStruct(x.shape[:ax] + (slots,) + x.shape[ax + 1:], x.dtype),
        one))
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=chip)
    return server._decode.lower(params, tokens, state).compile().as_text()


def cell_decode_text(cell, device=None) -> str:
    """:func:`decode_text` at the serving cell's sizes, which its window runs."""
    from chipbench.kinds.serve import program_config

    opts = cell.config["serve"]
    return decode_text(program_config(cell.config), int(opts["slots"]), int(opts["max_len"]),
                       device)


def model_scope_names() -> Optional[Tuple[str, ...]]:
    """The program's scope names, or None where the program has none."""
    try:
        from repro.models import scopes
    except ImportError:
        return None
    return tuple(scopes.ALL)


_last: list = [None, None]  # the run last split, held weakly, and its split


def decode_split(run) -> Optional[Tuple[Dict[str, float], int]]:
    """:func:`split` of a traced serving run's decode programs, once a run."""
    if _last[0] is not None and _last[0]() is run:
        return _last[1]
    result = None
    names = model_scope_names()
    if run.trace is not None and run.trace.devices and names is not None:
        text = cell_decode_text(run.cell)
        if any(f"/{n}/" in text for n in names):
            result = split(run.trace, DECODE_PROGRAM, instruction_scopes(text, names))
    _last[:] = [weakref.ref(run), result]
    return result


def decode_ms(run, scope: str) -> Optional[float]:
    """Device ms per decode step in ``scope``; None where the run has no
    trace, the program no scopes, or the split no match."""
    found = decode_split(run)
    if found is None:
        return None
    seconds, steps = found
    return 1e3 * seconds.get(scope, 0.0) / steps
