"""The ONE lock-construction seam for the hot planes: ``make_lock(name)``.

Every shipped cross-module deadlock and convoy in this repo — the
fsync-held-across-``_io_lock`` throughput hit (PR 15), the ``json.dump``
encoder convoy (PR 16), the compute-then-publish ``_slots_lock`` stale
gauge (PR 14) — was a *lock-discipline* bug invisible to per-lock unit
tests. ``graftsan`` closes the loop from both sides: the static side
(``analysis/interproc.py``) proves properties about the acquisition
graph, and the runtime side (``telemetry/lockwitness.py``) *watches* the
real acquisition order under load and cross-checks the static claims.

This module is the seam between them.  A plane that constructs its locks
through :func:`make_lock` / :func:`make_rlock` / :func:`make_condition`:

* gives the static analysis a stable **witness name** (the literal first
  argument) that survives refactors, so static edges and runtime edges
  join on the same key;
* costs **exactly zero** when the witness is off (the default): the
  factory returns the bare ``threading`` primitive — same type, same
  C implementation, no wrapper frame anywhere near the hot path.  The
  serve_bench A/B gate asserts this stays true by construction
  (``type(make_lock("x")) is type(threading.Lock())``);
* becomes a :class:`~multiverso_tpu.telemetry.lockwitness.WitnessLock`
  when the witness is on (``-lockwitness`` flag, the
  ``MULTIVERSO_LOCKWITNESS`` env var, or :func:`set_witness_enabled`),
  feeding per-thread acquisition-order pairs, ``lock.<name>.held_ms``
  histograms, and blocking-while-held flight events into the ledger
  ``check_inversions()`` audits.

Naming convention: ``<plane>.<what>`` — ``wal.staging``, ``wal.io``,
``serve.cache``, ``fleet.supervisor`` … (docs/CONCURRENCY.md carries the
full hierarchy table with ranks and allowed nesting).  Names must be
string LITERALS at the call site: the static side reads them from the
AST, and the metric family they feed must stay bounded.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

__all__ = ["make_lock", "make_rlock", "make_condition", "set_lock_order",
           "witness_enabled", "set_witness_enabled"]

#: Tri-state override: None = follow env/flag; True/False = forced by a
#: bench leg or test. Only the single-threaded bring-up path writes it.
_forced: Optional[bool] = None


def set_witness_enabled(on: Optional[bool]) -> None:
    """Force the witness on/off for locks constructed FROM NOW ON
    (``None`` restores env/flag control). Existing locks keep whatever
    they were built as — enable the witness *before* constructing the
    plane under test."""
    global _forced
    _forced = on


def witness_enabled() -> bool:
    if _forced is not None:
        return _forced
    env = os.environ.get("MULTIVERSO_LOCKWITNESS", "")
    if env:
        return env.strip().lower() not in ("0", "false", "off", "no")
    try:
        from multiverso_tpu.utils.configure import flag_or
        return bool(flag_or("lockwitness", False))
    except Exception:  # noqa: BLE001 - bare library use, flags unparsed
        return False


def make_lock(name: str) -> threading.Lock:
    """A named mutex. Witness off (default): the bare ``threading.Lock``
    — zero added cost, by construction. Witness on: an instrumented
    lock recording acquisition-order edges and hold times under
    ``name``."""
    if not witness_enabled():
        return threading.Lock()
    from multiverso_tpu.telemetry.lockwitness import wrap_lock
    return wrap_lock(name)


def set_lock_order(lock, key: int) -> None:
    """Declare ``lock``'s key in an ORDERED FAMILY: same-named locks that
    code may hold together, always acquired in ascending key order (a
    ``TableGroup`` takes its members' ``core.store`` locks in table-id
    order). The witness then checks the discipline instead of reporting
    every such nesting as a self-loop: ascending keys record no edge,
    anything else does. Witness off: the bare lock carries nothing."""
    if hasattr(lock, "order"):
        lock.order = key


def make_rlock(name: str) -> threading.RLock:
    """A named re-entrant mutex (same contract as :func:`make_lock`;
    re-acquisition by the owning thread records no self-edge)."""
    if not witness_enabled():
        return threading.RLock()
    from multiverso_tpu.telemetry.lockwitness import wrap_rlock
    return wrap_rlock(name)


def make_condition(name: str, lock=None) -> threading.Condition:
    """A named condition variable. ``lock=None`` builds the underlying
    (witnessed, when on) mutex too; passing a lock made by
    :func:`make_lock` shares it the usual way."""
    if not witness_enabled():
        return threading.Condition(lock)
    from multiverso_tpu.telemetry.lockwitness import wrap_condition
    return wrap_condition(name, lock)
