"""Named scopes of the jitted train step, and where each compiled
instruction belongs (DESIGN.md §10.1).

The step's parts carry ``jax.named_scope`` names, kept here so that a
refactor cannot orphan the reduction that reads them:

  model          forward and backward over the microbatch scan
  sync           the bucketed gradient exchange (``comm.execute_plan`` /
                 ``reduce_buckets``)
  sync/compress  error-feedback add, bucketed top-k, residual fold
  sync/qsgd      QSGD's rounding bits, quantize and dequantize
  update         clipping and the optimizer update, ZeRO-1's parameter
                 all-gather included

A nested scope is entered by its last component (:func:`scope`), so
inside ``sync`` the ops of ``sync/compress`` read ``.../sync/compress/...``
in their ``op_name`` metadata. :func:`op_scopes` maps each instruction of
a compiled module's text to the innermost of these scopes its
``op_name`` names: the profiler's device events carry the instruction
name, so a trace reduction charges each op's time to its scope. An
instruction the compiler made without that metadata (a copy it
inserted, a loop body it rewrote) takes the scope of the loop or call
whose body holds it, else that of its first operand that has one.
"""
from __future__ import annotations

import re

import jax

MODEL = "model"
SYNC = "sync"
COMPRESS = "sync/compress"
QSGD = "sync/qsgd"
UPDATE = "update"
SCOPES = (MODEL, SYNC, COMPRESS, QSGD, UPDATE)

_BY_LEAF = {s.rsplit("/", 1)[-1]: s for s in SCOPES}
# `[ENTRY ]%computation (params...) -> result {`
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
# `[ROOT ]%name = type opcode(operands), attributes`
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLS = re.compile(
    r"\b(?:body|condition|calls|to_apply|true_computation|"
    r"false_computation)=%?([\w.\-]+)|\bbranch_computations=\{([^}]*)\}")
_NAME = re.compile(r"%([\w.\-]+)")
# transform wrappers around a name-stack component: jvp(model),
# transpose(jvp(model)), vmap(...)
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


def scope(name: str):
    """``jax.named_scope`` for one of :data:`SCOPES`, entered by its last
    component."""
    if name not in SCOPES:
        raise ValueError(f"unknown step scope {name!r}; one of {SCOPES}")
    return jax.named_scope(name.rsplit("/", 1)[-1])


def scope_of(op_name: str) -> str | None:
    """The innermost of :data:`SCOPES` named in one ``op_name`` (the
    name stack, components joined by ``/``, the primitive last), or
    None."""
    found = None
    for comp in op_name.split("/")[:-1]:
        while (m := _WRAPPED.match(comp)) is not None:
            comp = m.group(1)
        found = _BY_LEAF.get(comp, found)
    return found


def op_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction name: scope}`` over a compiled module's text
    (``compiled.as_text()``): each instruction's innermost scope of
    :data:`SCOPES` by its ``op_name`` metadata, else by the instruction
    that calls its computation, else by its first scoped operand;
    instructions none of these place are left out."""
    comp_of: dict[str, str] = {}      # instruction -> its computation
    direct: dict[str, str | None] = {}
    operands: dict[str, list[str]] = {}
    caller: dict[str, str] = {}       # computation -> first caller
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        direct[name] = scope_of(op.group(1)) if op else None
        comp_of[name] = comp
        operands[name] = _NAME.findall(rest.split("),", 1)[0])
        for one, many in _CALLS.findall(rest):
            for callee in [one] if one else _NAME.findall(many):
                caller.setdefault(callee, name)

    # callees print before their callers and operands before their
    # users, so this settles in a pass or two
    out = dict(direct)
    changed = True
    while changed:
        changed = False
        for name, found in out.items():
            if found is not None:
                continue
            up = caller.get(comp_of[name])
            found = out.get(up) if up is not None else None
            if found is None:
                found = next((out[a] for a in operands[name]
                              if out.get(a) is not None), None)
            if found is not None:
                out[name] = found
                changed = True
    return {k: v for k, v in out.items() if v is not None}
