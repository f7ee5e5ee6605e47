"""Device time by the program's own scopes: the device-side twin of ``span()``.

A device program names its parts with ``jax.named_scope``; the names survive
compilation as the ``op_name`` of every optimized instruction, and a profiler
trace names each device operation by its instruction (``%fusion.12 = ...``).
What joins the two is the compiled module's text, which only the process that
built the program can give. So a step program is REGISTERED where it is called
(:func:`register_program`: the callable, weakly, and the shapes of its
arguments, never a buffer), and whoever reads a trace asks for the map
(:func:`program_scopes`), which is built then, by lowering each registered
program from its shapes and reading its executable's text. With a persistent
compile cache that fetches the executable that ran. Nothing here runs on a
step's path but the look-up of one key.

The cache's key leaves debug info out: an executable cached before a scope was
written carries the old names, in the trace and in this map alike.
"""

from __future__ import annotations

import dataclasses
import re
import threading
import weakref
from typing import Callable, Dict, Optional

import jax

from multiverso_tpu.utils.log import log

__all__ = ["register_program", "program_scopes", "parse_scopes",
           "scope_names", "reset_device_scopes"]

_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*\bop_name="([^"]*)"', re.M)
_WRAPPED = re.compile(r"^(?:[A-Za-z_]\w*\()+(.*?)\)+$")


@dataclasses.dataclass
class _Program:
    ref: Callable                   # weakref to the jitted callable
    args: tuple                     # ShapeDtypeStructs where arrays were
    kwargs: dict                    # the static arguments, as they are
    scopes: Optional[tuple] = None  # (module name, {instruction: op_name})


_programs: Dict[tuple, _Program] = {}
_lock = threading.Lock()


def _signature(leaf):
    try:
        return leaf.shape, leaf.dtype
    except AttributeError:          # a Python scalar: traced by its type
        return type(leaf)


def _abstract(leaf):
    """The leaf as jit saw it: shape, dtype, weak type, and its sharding
    only where it was COMMITTED to one (an uncommitted array, like a host
    one, leaves the placement to the program: another executable, and
    another key in the compile cache)."""
    aval = jax.typeof(leaf)
    committed = getattr(leaf, "committed", False)
    return jax.ShapeDtypeStruct(
        aval.shape, aval.dtype, weak_type=aval.weak_type,
        sharding=leaf.sharding if committed else None)


def register_program(jitted, args: tuple, kwargs: Optional[dict] = None
                     ) -> None:
    """Note, once a program and shape, that ``jitted`` runs with positional
    arguments shaped as ``args`` (arrays, host or device, or Python scalars:
    all traced) and the STATIC keyword arguments ``kwargs`` (hashable, kept
    as they are). Call it beside the program's own call; a program and shape
    already noted costs one look-up."""
    key = (id(jitted), tuple(map(_signature, jax.tree_util.tree_leaves(args))),
           tuple(sorted(kwargs.items())) if kwargs else ())
    if key in _programs:
        return
    entry = _Program(
        weakref.ref(jitted, lambda _, key=key: _programs.pop(key, None)),
        jax.tree_util.tree_map(_abstract, args), dict(kwargs or {}))
    with _lock:
        _programs.setdefault(key, entry)


def reset_device_scopes() -> None:
    """Forget every registration (tests)."""
    with _lock:
        _programs.clear()


def parse_scopes(hlo_text: str) -> tuple:
    """``(module name as a trace prints it, {instruction name: op_name
    path})`` of a compiled module's text; instructions of every computation
    (``while`` bodies, fused ones), those without metadata left out."""
    module = _MODULE.match(hlo_text)
    return (module.group(1) if module else "",
            dict(_INSTRUCTION.findall(hlo_text)))


def scope_names(path: str) -> list:
    """The components of an ``op_name`` path, the wrappers jax puts around
    a scope peeled off: ``jit(f)/transpose(jvp(lm_mla))/while/body/mul`` is
    ``[f, lm_mla, while, body, mul]``."""
    names = []
    for part in path.split("/"):
        wrapped = _WRAPPED.match(part)
        names.append(wrapped.group(1) if wrapped else part)
    return names


def program_scopes() -> Dict[str, Dict[str, str]]:
    """``{module name ("jit_lm_delta_step"): {instruction name
    ("fusion.12"): op_name path}}`` over the registered programs that are
    still alive. Each is lowered and compiled from its shapes on the first
    ask (a fetch where the persistent cache holds it) and remembered. Where
    programs of one name disagree on an instruction (two shapes of one
    function), the instruction is left out: it cannot be told whose it
    is."""
    out: Dict[str, Dict[str, str]] = {}
    clashes = set()
    with _lock:
        programs = list(_programs.values())
    for entry in programs:
        jitted = entry.ref()
        if jitted is None:
            continue
        if entry.scopes is None:
            try:
                entry.scopes = parse_scopes(jitted.lower(
                    *entry.args, **entry.kwargs).compile().as_text())
            except Exception as e:  # a reader's question never ends a run
                log.error("device_scopes: %r does not lower from its "
                          "registered shapes: %s", jitted, e)
                entry.scopes = ("", {})
        module, scopes = entry.scopes
        if not module:
            continue
        have = out.setdefault(module, {})
        for name, path in scopes.items():
            if have.setdefault(name, path) != path:
                clashes.add((module, name))
    for module, name in clashes:
        del out[module][name]
    return out
